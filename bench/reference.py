"""Plain reference of the campaign model the benchmark's cells are scored on.

A straightforward NumPy implementation of the documented semantics,
written from the configuration files and the model's description; it
imports nothing of the program under test and takes none of its outputs
except the answers being checked.

The model (arXiv:2605.24561 §2-3, as the configuration files state it):

- A campaign runs `n_scenarios` in batches.  At worker intensity u,
  orchestration batch size b and office background g the effective
  throughput is R u max(1 - gamma g, 0.05); a batch takes
  overhead + b / R_eff seconds; the machine draws
  idle + dyn max(u + g, 0)^alpha while working and
  idle + dyn max(f_oh u + g, 0)^alpha during the overhead.
- Calibration solves R, then dyn, by bisection so that the calibration
  policy reproduces the measured (hours, kWh) on the segment simulator.
- The trace grid steps hour by hour from the campaign's start.  A
  schedule's decisions are sampled per slot at progress-bucket centres
  (b + 0.5) / B and linearly interpolated at the live progress.  Every
  `chunk_days` the campaigns whose remaining work is at most
  `finish_frac` of their total stop.
- A site cap couples the campaigns of one fleet: per slot the summed
  draw of the active campaigns is curtailed by one shared factor, found
  by a fixed number of damped fixed-point steps.
"""
from __future__ import annotations

import bisect
import math
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

CONTENTION_FLOOR = 0.05       # throughput never drops below 5 % of nominal
RATE_EPS = 1e-9               # guards divisions by a rate
SITE_THROTTLE_FLOOR = 0.05    # curtailment keeps 5 % of a campaign's demand


# ---------------------------------------------------------------------------
# The rate model
# ---------------------------------------------------------------------------
def power_w(load, machine: dict):
    return machine["idle_w"] + machine["dyn_w"] * np.maximum(load, 0.0) \
        ** machine["alpha"]


def rates(u, batch, bg, rate, overhead_s, machine: dict) -> Dict[str, object]:
    """Scenarios per second, average power and kWh per second at one
    operating point (arrays broadcast)."""
    r_eff = rate * u * np.maximum(1.0 - machine["gamma"] * bg,
                                  CONTENTION_FLOOR)
    work_t = batch / np.maximum(r_eff, RATE_EPS)
    batch_t = overhead_s + work_t
    work_frac = work_t / batch_t
    p_work = power_w(u + bg, machine)
    p_oh = power_w(machine["overhead_w_frac"] * u + bg, machine)
    p_avg = work_frac * p_work + (1.0 - work_frac) * p_oh
    return {"scen_per_s": batch / batch_t, "p_avg_w": p_avg,
            "kwh_per_s": p_avg / 3.6e6}


def _rates_scalar(u: float, batch: float, bg: float, rate: float,
                  overhead_s: float, machine: dict) -> Tuple[float, float]:
    """(scenarios/s, average W) in Python floats, for the segment
    simulator."""
    r_eff = rate * u * max(1.0 - machine["gamma"] * bg, CONTENTION_FLOOR)
    work_t = batch / max(r_eff, RATE_EPS)
    batch_t = overhead_s + work_t
    work_frac = work_t / batch_t

    def pw(load):
        return machine["idle_w"] + machine["dyn_w"] * max(load, 0.0) \
            ** machine["alpha"]

    p_avg = (work_frac * pw(u + bg)
             + (1.0 - work_frac) * pw(machine["overhead_w_frac"] * u + bg))
    return batch / batch_t, p_avg


# ---------------------------------------------------------------------------
# Time bands
# ---------------------------------------------------------------------------
class Bands:
    """Hour of day -> band -> office background, as the configuration
    states them (first matching range wins: peak, load-sensitive,
    shoulder; night otherwise)."""

    ORDER = ("peak", "load_sensitive", "shoulder")

    def __init__(self, cfg: dict):
        self.ranges = {b: [tuple(r) for r in cfg[b]] for b in self.ORDER}
        self.level = dict(cfg["background"])

    def band_at(self, hour: float) -> str:
        h = hour % 24.0
        for b in self.ORDER:
            for lo, hi in self.ranges[b]:
                if lo <= h < hi:
                    return b
        return "night"

    def background(self, hour: float) -> float:
        return self.level[self.band_at(hour)]

    def edges(self) -> list:
        hs = {0.0}
        for b in self.ORDER:
            for lo, hi in self.ranges[b]:
                hs.add(float(lo) % 24.0)
                hs.add(float(hi) % 24.0)
        return sorted(hs)

    def table(self) -> np.ndarray:
        """Background of each hour-of-day slot."""
        return np.array([self.background(float(h)) for h in range(24)])


# ---------------------------------------------------------------------------
# Calibration
# ---------------------------------------------------------------------------
def _segment_run(n_scen: float, rate: float, overhead_s: float,
                 machine: dict, bands: Bands, u: float, batch: float,
                 start_hour: float) -> Tuple[float, float]:
    """(hours, kWh) of a constant-intensity campaign on the segment
    simulator: time advances from band edge to band edge, and the last
    segment ends when the work does."""
    grid = bands.edges()
    remaining = float(n_scen)
    t_h = start_hour
    kwh = 0.0
    while remaining > 0:
        h = t_h % 24.0
        b = bands.background((h + 1e-9) % 24.0)
        i = bisect.bisect_right(grid, h + 1e-9)
        seg_h = (grid[i] if i < len(grid) else 24.0 + grid[0]) - h
        scen_per_s, p_avg = _rates_scalar(u, batch, b, rate, overhead_s,
                                          machine)
        seg_s = seg_h * 3600.0
        if scen_per_s * seg_s >= remaining:
            seg_s = remaining / scen_per_s
            done = remaining
        else:
            done = scen_per_s * seg_s
        kwh += p_avg * seg_s / 3.6e6
        remaining -= done
        t_h += seg_s / 3600.0
    return t_h - start_hour, kwh


def calibrate(campaign: dict, bands: Bands) -> Tuple[float, dict]:
    """(rate_at_full, machine with dyn_w solved) for one campaign of a
    configuration: bisection on the rate for the measured hours, then on
    dyn_w for the measured kWh, as the campaign's `calibration` states."""
    wl, cal = campaign["workload"], campaign["calibration"]
    machine = dict(campaign["machine"])
    u, batch, tol = cal["intensity"], cal["batch_size"], cal["tol"]

    def run(rate, mach):
        return _segment_run(wl["n_scenarios"], rate, wl["batch_overhead_s"],
                            mach, bands, u, batch, campaign["start_hour"])

    lo, hi = cal["rate_bracket"]
    for _ in range(cal["max_iter"]):
        mid = math.sqrt(lo * hi)
        if run(mid, machine)[0] > wl["measured_hours"]:
            lo = mid
        else:
            hi = mid
        if hi / lo < 1 + tol:
            break
    rate = math.sqrt(lo * hi)
    lo, hi = cal["dyn_bracket"]
    for _ in range(cal["max_iter"]):
        mid = 0.5 * (lo + hi)
        if run(rate, dict(machine, dyn_w=mid))[1] < wl["measured_kwh"]:
            lo = mid
        else:
            hi = mid
        if hi - lo < tol * wl["measured_kwh"]:
            break
    return rate, dict(machine, dyn_w=0.5 * (lo + hi))


# ---------------------------------------------------------------------------
# Decisions
# ---------------------------------------------------------------------------
def parametric_table(logits, u_min, u_max) -> np.ndarray:
    """(L, 24) hourly intensities of day schedules: one logit per hour
    squashed into [u_min, u_max]."""
    z = np.asarray(logits, dtype=float)
    return u_min + (u_max - u_min) * (0.5 * (np.tanh(0.5 * z) + 1.0))


def deadline_intensity(elapsed_h, progress, deadline_h, u_low, u_high,
                       band):
    """Pace keeper: full boost at or behind the linear pace to the
    deadline, easing to u_low once `band` ahead of it."""
    pace = np.minimum(elapsed_h / deadline_h, 1.0)
    frac = np.clip((pace - progress) / band + 1.0, 0.0, 1.0)
    return u_low + (u_high - u_low) * frac


# ---------------------------------------------------------------------------
# The trace-grid scan
# ---------------------------------------------------------------------------
class Lanes:
    """A batch of independent campaigns to scan.

    `n_scen`, `rate`, `overhead_s` are (L,); `machine` maps each machine
    field to an (L,) array.  Decisions: `table` (L, 24) hourly
    intensities for day schedules, and for pace keepers `deadline` (L,)
    hours with `pace` = (u_low, u_high, band) (NaN deadline = day
    schedule).  `batch` (L,) batch sizes.  `carbon(t)` gives the (L, E)
    grid factors of absolute slot t."""

    def __init__(self, *, n_scen, rate, overhead_s, machine, table,
                 batch, deadline=None, pace=(0.35, 0.95, 0.1)):
        self.n_scen = np.asarray(n_scen, dtype=float)
        self.rate = np.asarray(rate, dtype=float)
        self.overhead_s = np.asarray(overhead_s, dtype=float)
        self.machine = {k: np.asarray(v, dtype=float)
                        for k, v in machine.items()}
        self.table = np.asarray(table, dtype=float)
        self.batch = np.asarray(batch, dtype=float)
        L = len(self.n_scen)
        self.deadline = (np.full(L, np.nan) if deadline is None
                         else np.asarray(deadline, dtype=float))
        self.pace = pace


def _decisions(lanes: Lanes, row: int, elapsed_h: float, prog: np.ndarray,
               buckets: int) -> np.ndarray:
    """Intensity of every lane at hour-of-day `row`, interpolated between
    the two progress-bucket centres around `prog`."""
    u = lanes.table[:, row].copy()
    ddl = ~np.isnan(lanes.deadline)
    if ddl.any():
        x = prog[ddl] * buckets - 0.5
        b0 = np.clip(np.floor(x), 0, buckets - 2)
        w = np.clip(x - b0, 0.0, 1.0)
        lo_c = (b0 + 0.5) / buckets
        hi_c = (b0 + 1.5) / buckets
        d = lanes.deadline[ddl]
        u_lo = deadline_intensity(elapsed_h, lo_c, d, *lanes.pace)
        u_hi = deadline_intensity(elapsed_h, hi_c, d, *lanes.pace)
        u[ddl] = (1.0 - w) * u_lo + w * u_hi
    return u


def scan(lanes: Lanes, bg_day: np.ndarray, carbon, *, start_hour: float,
         buckets: int, chunk_slots: Optional[int], finish_frac: float,
         max_slots: int, cap_kw=None, office_kw: float = 0.0,
         groups: Optional[np.ndarray] = None,
         throttle_iters: int = 0) -> Dict[str, np.ndarray]:
    """Step every lane hour by hour until each has finished.

    `chunk_slots` None scans without the chunk-end stop (the objective's
    fixed-horizon scan).  `cap_kw` not None couples the lanes of each
    group (`groups` (L,) group ids) under the site cap with office draw
    `office_kw` times the hour's background, with `throttle_iters`
    curtailment steps per slot.  Returns runtime_h, kwh,
    co2 (L, E) and, coupled, peak_kw (L,)."""
    L = len(lanes.n_scen)
    m = lanes.machine
    g0 = math.floor(start_hour)
    remaining = lanes.n_scen.copy()
    rt = np.zeros(L)
    kwh = np.zeros(L)
    co2 = None
    peak = np.zeros(L)
    live = np.ones(L, dtype=bool)
    n_groups = int(groups.max()) + 1 if groups is not None else 0
    for t in range(max_slots):
        if chunk_slots is not None and t and t % chunk_slots == 0:
            live &= remaining > finish_frac * lanes.n_scen
        if not (live & (remaining > 0.0)).any():
            break
        t_abs = g0 + t
        row = t_abs % 24
        ln = 3600.0 if t else (g0 + 1.0 - start_hour) * 3600.0
        bg = bg_day[row]
        prog = 1.0 - remaining / lanes.n_scen
        u = _decisions(lanes, row, t_abs - start_hour, prog, buckets)
        r = rates(u, lanes.batch, bg, lanes.rate, lanes.overhead_s, m)
        if cap_kw is not None:
            active = live & (remaining > finish_frac * lanes.n_scen)
            base = np.bincount(groups, np.where(active, power_w(bg, m), 0.0)
                               / 1000.0, minlength=n_groups)
            head = cap_kw - office_kw * bg
            f = np.ones(n_groups)
            for _ in range(throttle_iters):
                draw = np.bincount(groups, np.where(active, r["p_avg_w"], 0.0)
                                   / 1000.0, minlength=n_groups)
                f = np.maximum(np.minimum(
                    f * np.maximum(head - base, 0.0)
                    / np.maximum(draw - base, RATE_EPS), 1.0),
                    SITE_THROTTLE_FLOOR)
                r = rates(u * f[groups], lanes.batch, bg, lanes.rate,
                          lanes.overhead_s, m)
            site = np.bincount(groups, np.where(active, r["p_avg_w"], 0.0)
                               / 1000.0, minlength=n_groups) + office_kw * bg
            peak = np.where(active, np.maximum(peak, site[groups]), peak)
        run = live & (remaining > 0.0)
        dt = np.where(run, np.minimum(
            ln, remaining / np.maximum(r["scen_per_s"], 1e-30)), 0.0)
        e = r["kwh_per_s"] * dt
        cf = carbon(t_abs)
        co2 = e[:, None] * cf if co2 is None else co2 + e[:, None] * cf
        remaining = remaining - r["scen_per_s"] * dt
        rt = rt + dt
        kwh = kwh + e
    else:
        raise RuntimeError(f"reference: lanes still running after "
                           f"{max_slots} slots")
    out = {"runtime_h": rt / 3600.0, "kwh": kwh, "co2": co2,
           "remaining": remaining}
    if cap_kw is not None:
        out["peak_kw"] = peak
    return out


def trace_carbon(members: np.ndarray, lanes_per_member_row: Optional[
        np.ndarray] = None):
    """carbon(t) for hourly traces anchored at hour 0 that hold their
    first and last value outside their range.  `members` (K, E, T):
    K forecasts of E members; `lanes_per_member_row` (L,) picks each
    lane's forecast (all lanes use forecast 0 when None)."""
    members = np.asarray(members, dtype=float)
    if members.ndim == 2:
        members = members[None]
    idx = np.zeros(0, dtype=int) if lanes_per_member_row is None \
        else np.asarray(lanes_per_member_row, dtype=int)
    T = members.shape[2]

    def carbon(t_abs: int) -> np.ndarray:
        col = members[:, :, min(max(int(t_abs), 0), T - 1)]   # (K, E)
        return col[idx] if idx.size else col[0][None, :]

    return carbon


def constant_carbon(factor: float):
    """carbon(t) of a flat grid factor (one member)."""
    col = np.array([[float(factor)]])
    return lambda t_abs: col


def lanes_for(campaign: dict, rate: float, machine: dict, *, table, batch,
              deadline=None, pace=(0.35, 0.95, 0.1)) -> Lanes:
    """Lanes of one calibrated campaign, one per schedule."""
    L = len(table)
    wl = campaign["workload"]
    return Lanes(n_scen=np.full(L, float(wl["n_scenarios"])),
                 rate=np.full(L, rate),
                 overhead_s=np.full(L, float(wl["batch_overhead_s"])),
                 machine={k: np.full(L, float(v))
                          for k, v in machine.items()},
                 table=table, batch=batch, deadline=deadline, pace=pace)


def concat(parts: Sequence[Lanes]) -> Lanes:
    """One batch of lanes from several (same pace parameters)."""
    cat = np.concatenate
    return Lanes(n_scen=cat([p.n_scen for p in parts]),
                rate=cat([p.rate for p in parts]),
                overhead_s=cat([p.overhead_s for p in parts]),
                machine={k: cat([p.machine[k] for p in parts])
                         for k in parts[0].machine},
                table=cat([p.table for p in parts]),
                batch=cat([p.batch for p in parts]),
                deadline=cat([p.deadline for p in parts]),
                pace=parts[0].pace)


def relative_gap(got, want) -> float:
    """Largest |got - want| / |want| over paired arrays (inf when a value
    is missing or not finite)."""
    got = np.asarray(got, dtype=float)
    want = np.asarray(want, dtype=float)
    if got.shape != want.shape:
        return math.inf
    if not np.isfinite(got).all():
        return math.inf
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want),
                                                         1e-300)))
