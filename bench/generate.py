"""The benchmark's one traffic generator: seeded plain inputs from a mix.

A traffic mix is a JSON file under `bench/traffic/`; its `kind` names the
request type and the rest are its parameters.  Everything here is NumPy
arithmetic on the seed: the same (mix, configuration, seed) gives the
same arrays, and nothing of the program under test is imported.  The
clients (`clients.py`) turn these arrays into the program's objects and
the reference (`reference.py`) reads them as they are.

Streams are keyed by (seed, purpose, request index), so request k's
inputs do not depend on how many requests ran before it.
"""
from __future__ import annotations

import numpy as np

#: purpose tags of the seed streams
ARCHIVE, CANDIDATES, ASSIGNMENTS, SEARCH = 1, 2, 3, 4

#: seed of the inputs a warm-up request uses where the program compiles
#: for the data itself: the same for every run, so that after a
#: checkout's first run set-up finds its programs in the compile cache
WARM_UP_SEED = 0


def rng(seed: int, *key: int) -> np.random.Generator:
    """The generator of one stream.  Any integer seed works; negative
    ones wrap into 64 bits."""
    return np.random.default_rng([int(seed) % 2 ** 64, *key])


def archive(carbon: dict, days: int, seed: int) -> np.ndarray:
    """A seeded hourly grid-carbon archive (kg CO2e/kWh): a diurnal shape
    around a seeded base level, a weekend dip, and multiplicative noise,
    floored.  `carbon` is the configuration's `carbon` section."""
    g = rng(seed, ARCHIVE)
    lo, hi = carbon["base_range"]
    base = lo + (hi - lo) * g.random()
    h = np.arange(int(days) * 24)
    weekend = np.where((h // 24) % 7 >= 5, carbon["weekend_factor"], 1.0)
    noise = 1.0 + carbon["noise"] * g.standard_normal(h.size)
    diurnal = np.asarray(carbon["diurnal"], dtype=float)[h % 24]
    return np.maximum(base * diurnal * weekend * noise, carbon["floor"])


def archive_days(forecast: dict) -> int:
    """Days of archive that `max_requests` shifted slices need."""
    shift_h = forecast["shift_h"] * forecast["max_requests"]
    return int(forecast["archive_days"] + -(-shift_h // 24))


def forecast_slice(arc: np.ndarray, forecast: dict, k: int) -> np.ndarray:
    """Request k's year-long slice: it starts k shifts after request 0's."""
    if k >= forecast["max_requests"]:
        raise ValueError(f"request {k} is past the mix's max_requests "
                         f"({forecast['max_requests']})")
    o = k * forecast["shift_h"]
    return arc[o:o + forecast["archive_days"] * 24]


def windows(series: np.ndarray, window_h: int, stride_h: int) -> np.ndarray:
    """(E, window_h) sliding windows of an hourly series."""
    starts = range(0, len(series) - window_h + 1, stride_h)
    return np.stack([series[o:o + window_h] for o in starts])


def parametric_logits(spec: dict, n: int, g: np.random.Generator
                      ) -> np.ndarray:
    """(n, slots) logits of day schedules."""
    return g.normal(spec["logit_mean"], spec["logit_std"],
                    (n, spec["slots"]))


def refresh_candidates(spec: dict, seed: int) -> dict:
    """The candidate set a refresh scores: day schedules with seeded
    logits and pace keepers with deadlines spread over a range."""
    par, ddl = spec["parametric"], spec["deadline"]
    return {"logits": parametric_logits(par, par["count"],
                                        rng(seed, CANDIDATES)),
            "deadline_h": np.linspace(*ddl["deadline_h"], ddl["count"])}


def fleet_assignments(spec: dict, n_campaigns: int, seed: int, k: int
                      ) -> np.ndarray:
    """(assignments, campaigns, slots) logits of request k."""
    s = spec["schedule"]
    g = rng(seed, ASSIGNMENTS, k)
    return parametric_logits(s, spec["assignments"] * n_campaigns, g
                             ).reshape(spec["assignments"], n_campaigns,
                                       s["slots"])


def search_seed(seed: int, k: int) -> int:
    """The optimizer seed of re-plan k (fits 31 bits)."""
    return int(rng(seed, SEARCH, k).integers(0, 2 ** 31 - 1))
