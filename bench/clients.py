"""Request clients: one per traffic `kind`.

A client turns the generator's plain arrays into the program's own
objects, issues one closed-loop request through the program's public
entry point, keeps what the request returned, and after the window
compares every returned answer with the plain reference.

    client = CLIENTS[spec["kind"]](carina, cfg, spec, seed, chips)
    client.build()              # inputs from the seed
    client.warm_up()            # brings the program to steady state
    units = client.request(k)   # the timed call; cases (or re-plans) done
    checks = client.check()     # {name: (value, limit)} over the window

`precision` is the program's dtype policy; the configuration's own is the
default, and the lower-precision control passes the program's "mixed".
"""
from __future__ import annotations

import contextlib
import math
from typing import Dict, List, Tuple

import numpy as np

import generate
import reference as ref


@contextlib.contextmanager
def compile_cache_off():
    """The persistent compile cache off for the compiles inside."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def program_campaigns(carina, cfg: dict):
    """(bands, [Campaign]) built from the configuration's numbers."""
    b = cfg["bands"]
    bands = carina.TimeBands(
        peak=tuple(tuple(r) for r in b["peak"]),
        load_sensitive=tuple(tuple(r) for r in b["load_sensitive"]),
        shoulder=tuple(tuple(r) for r in b["shoulder"]))
    out = []
    for c in cfg["campaigns"]:
        w = c["workload"]
        wl = carina.OEMWorkload(w["name"], int(w["n_scenarios"]),
                                rate_at_full=0.0,
                                batch_overhead_s=w["batch_overhead_s"],
                                measured_hours=w["measured_hours"],
                                measured_kwh=w["measured_kwh"])
        out.append(carina.Campaign(wl, machine=carina.MachineProfile(
            **c["machine"]), bands=bands, start_hour=c["start_hour"]))
    return bands, out


def scan_settings(cfg: dict) -> dict:
    """The reference scan's grid settings from the configuration."""
    e = cfg["engine"]
    sph = e["slots_per_hour"]
    if sph != 1:
        raise ValueError("the reference steps hourly slots only")
    return {"start_hour": cfg["campaigns"][0]["start_hour"],
            "buckets": e["progress_buckets"],
            "chunk_slots": e["chunk_days"] * 24 * sph,
            "finish_frac": e["finish_frac"],
            "max_slots": e["max_days"] * 24 * sph}


def useful_slots(runtime_h) -> int:
    """Hourly slots in which campaigns of these runtimes did work."""
    return int(np.ceil(np.asarray(runtime_h, dtype=float) - 1e-9).sum())


class Client:
    kernel = "plain"        # chunk kernel the timed path runs

    def __init__(self, carina, cfg: dict, spec: dict, seed: int, chips: int,
                 precision: str = ""):
        self.carina = carina
        self.cfg = cfg
        self.spec = spec
        self.seed = int(seed)
        self.chips = int(chips)
        self.precision = precision or cfg["engine"]["precision"]
        self.records: List[Tuple[int, object]] = []

    def warm_up(self) -> None:
        """Request 0 compiles or loads every program the window's
        requests run (their shapes do not depend on the seed); its
        answers are not checked."""
        self.request(0)
        self.records.clear()

    def work(self) -> Dict[str, int]:
        """Useful work of the window's requests, for the work function."""
        return {}

    def size(self) -> int:
        """Units one request asks for."""
        return 1

    def nothing_to_compare(self) -> Dict[str, Tuple[float, float]]:
        """The checks of a window that returned no answer."""
        return {"max_rel_gap": (0.0, self.spec["check"]["max_rel_gap"]),
                "unscored": (0.0, 0.0)}


class Refresh(Client):
    """`kind: refresh`: one campaign re-scores a fixed candidate set
    against a new forecast ensemble per request, through the engine's
    `sweep` on the cases `Campaign.sweep` builds."""

    def build(self) -> None:
        c = self.carina
        self.bands, (self.camp,) = program_campaigns(c, self.cfg)
        self.wl, self.mach = self.camp.calibrated()
        self.cand = generate.refresh_candidates(self.spec["candidates"],
                                                self.seed)
        par, ddl = (self.spec["candidates"]["parametric"],
                    self.spec["candidates"]["deadline"])
        self.schedules = [
            c.ParametricSchedule(tuple(float(v) for v in row),
                                 u_min=par["u_min"], u_max=par["u_max"],
                                 batch_size=par["batch_size"],
                                 name=f"parametric-{i}")
            for i, row in enumerate(self.cand["logits"])] + [
            c.deadline_schedule(float(d), u_low=ddl["u_low"],
                                u_high=ddl["u_high"], band=ddl["band"],
                                batch_size=ddl["batch_size"],
                                name=f"deadline-{i}")
            for i, d in enumerate(self.cand["deadline_h"])]
        fc = self.spec["forecast"]
        self.arc = generate.archive(self.cfg["carbon"],
                                    generate.archive_days(fc), self.seed)

    def members(self, k: int) -> np.ndarray:
        fc = self.spec["forecast"]
        return generate.windows(generate.forecast_slice(self.arc, fc, k),
                                fc["window_h"], fc["stride_h"])

    def request(self, k: int) -> int:
        c, fc = self.carina, self.spec["forecast"]
        year = generate.forecast_slice(self.arc, fc, k)
        ens = c.trace_windows(year, fc["window_h"], fc["stride_h"],
                              name=f"forecast-{k}")
        cases = [c.SweepCase(s, self.wl, self.mach, self.camp.bands, ens,
                             self.camp.start_hour, label=s.name)
                 for s in self.schedules]
        eng = self.cfg["engine"]
        res = c.sweep(cases, progress_buckets=eng["progress_buckets"],
                      max_days=eng["max_days"], precision=self.precision,
                      devices=self.chips)
        self.records.append((k, res))
        return len(res) * len(ens)

    def size(self) -> int:
        return len(self.schedules) * len(self.members(0))

    def answers(self):
        """(runtime_h, kWh, CO2 mean, per-member CO2) arrays, shaped
        (requests, schedules[, members]); NaN where an answer is
        missing."""
        E = len(self.members(0))
        S = len(self.schedules)
        rt, kwh, co2 = (np.full((len(self.records), S), np.nan)
                        for _ in range(3))
        mem = np.full((len(self.records), S, E), np.nan)
        for i, (_, res) in enumerate(self.records):
            for j, r in enumerate(res[:S]):
                rt[i, j], kwh[i, j], co2[i, j] = (r.runtime_h, r.energy_kwh,
                                                  r.co2_kg)
                if r.co2_ensemble is not None and \
                        len(r.co2_ensemble.samples) == E:
                    mem[i, j] = r.co2_ensemble.samples
        return rt, kwh, co2, mem

    def check(self) -> Dict[str, Tuple[float, float]]:
        if not self.records:
            return self.nothing_to_compare()
        rt, kwh, co2, mem = self.answers()
        bands = ref.Bands(self.cfg["bands"])
        campaign = self.cfg["campaigns"][0]
        rate, machine = ref.calibrate(campaign, bands)
        par = self.spec["candidates"]["parametric"]
        ddl = self.spec["candidates"]["deadline"]
        n_par = len(self.cand["logits"])
        table = np.concatenate([
            ref.parametric_table(self.cand["logits"], par["u_min"],
                                 par["u_max"]),
            np.zeros((len(self.cand["deadline_h"]), 24))])
        deadline = np.concatenate([np.full(n_par, np.nan),
                                   self.cand["deadline_h"]])
        batch = np.concatenate([np.full(n_par, float(par["batch_size"])),
                                np.full(len(deadline) - n_par,
                                        float(ddl["batch_size"]))])
        one = ref.lanes_for(campaign, rate, machine, table=table,
                            batch=batch, deadline=deadline,
                            pace=(ddl["u_low"], ddl["u_high"], ddl["band"]))
        K, S = rt.shape
        lanes = ref.concat([one] * K)
        members = np.stack([self.members(k) for k, _ in self.records])
        carbon = ref.trace_carbon(members, np.repeat(np.arange(K), S))
        out = ref.scan(lanes, bands.table(), carbon,
                       **scan_settings(self.cfg))
        want_mem = out["co2"].reshape(K, S, -1)
        gap = max(ref.relative_gap(rt.ravel(), out["runtime_h"]),
                  ref.relative_gap(kwh.ravel(), out["kwh"]),
                  ref.relative_gap(co2, want_mem.mean(axis=2)),
                  ref.relative_gap(mem, want_mem))
        return {"max_rel_gap": (gap, self.spec["check"]["max_rel_gap"]),
                "unscored": (float(self.unscored()), 0.0)}

    def unscored(self) -> int:
        rt, kwh, co2, mem = self.answers()
        ok = (np.isfinite(rt) & np.isfinite(kwh) & np.isfinite(co2)
              & np.isfinite(mem).all(axis=2))
        return int((~ok).sum()) * mem.shape[2]

    def work(self) -> Dict[str, int]:
        rt = self.answers()[0]
        return {"lane_slots": useful_slots(rt[np.isfinite(rt)]),
                "group_slots": 0, "members": len(self.members(0))}


class FleetRefresh(Client):
    """`kind: fleet_refresh`: the site's fleet scores fresh seeded joint
    assignments per request through `Fleet.sweep` (the site-coupled
    kernel under a finite cap)."""
    kernel = "coupled"

    def build(self) -> None:
        c = self.carina
        self.bands, self.camps = program_campaigns(c, self.cfg)
        site = self.cfg["site"]
        self.fleet = c.Fleet(self.camps, c.Site(
            power_cap_kw=site["power_cap_kw"], office_kw=site["office_kw"],
            bands=self.bands, carbon=c.GridCarbonModel(
                factor_kg_per_kwh=self.cfg["carbon"]["kg_per_kwh"])))
        for camp in self.camps:
            camp.calibrated()
        self.logits: Dict[int, np.ndarray] = {}

    def request(self, k: int) -> int:
        c, s = self.carina, self.spec["schedule"]
        if k >= self.spec["max_requests"]:
            raise ValueError(f"request {k} is past the mix's max_requests")
        M = len(self.camps)
        logits = generate.fleet_assignments(self.spec, M, self.seed, k)
        self.logits[k] = logits
        assignments = [tuple(
            c.ParametricSchedule(tuple(float(v) for v in logits[a, m]),
                                 u_min=s["u_min"], u_max=s["u_max"],
                                 batch_size=s["batch_size"],
                                 name=f"fleet-{k}-{a}-{m}")
            for m in range(M)) for a in range(len(logits))]
        rows = self.fleet.sweep(assignments, precision=self.precision,
                                devices=self.chips,
                                max_days=self.cfg["engine"]["max_days"])
        self.records.append((k, rows))
        return len(rows) * M

    def size(self) -> int:
        return self.spec["assignments"] * len(self.camps)

    def answers(self):
        """(runtime_h, kWh, CO2) shaped (requests, assignments, campaigns)
        and site peak (requests, assignments); NaN where missing."""
        K = len(self.records)
        A, M = self.spec["assignments"], len(self.camps)
        rt, kwh, co2 = (np.full((K, A, M), np.nan) for _ in range(3))
        peak = np.full((K, A), np.nan)
        for i, (_, rows) in enumerate(self.records):
            for a, fr in enumerate(rows[:A]):
                for m, r in enumerate(fr.campaigns[:M]):
                    rt[i, a, m], kwh[i, a, m], co2[i, a, m] = (
                        r.runtime_h, r.energy_kwh, r.co2_kg)
                peak[i, a] = fr.site.peak_kw
        return rt, kwh, co2, peak

    def check(self) -> Dict[str, Tuple[float, float]]:
        if not self.records:
            return self.nothing_to_compare()
        rt, kwh, co2, peak = self.answers()
        K, A, M = rt.shape
        bands = ref.Bands(self.cfg["bands"])
        s = self.spec["schedule"]
        parts = []
        for campaign in self.cfg["campaigns"]:
            rate, machine = ref.calibrate(campaign, bands)
            parts.append((campaign, rate, machine))
        lanes = []
        for k, _ in self.records:
            logits = self.logits[k]
            for a in range(A):
                for m, (campaign, rate, machine) in enumerate(parts):
                    lanes.append(ref.lanes_for(
                        campaign, rate, machine,
                        table=ref.parametric_table(logits[a, m][None],
                                                   s["u_min"], s["u_max"]),
                        batch=[float(s["batch_size"])]))
        lanes = ref.concat(lanes)
        site = self.cfg["site"]
        out = ref.scan(lanes, bands.table(),
                       ref.constant_carbon(self.cfg["carbon"]["kg_per_kwh"]),
                       cap_kw=site["power_cap_kw"],
                       office_kw=site["office_kw"],
                       groups=np.repeat(np.arange(K * A), M),
                       throttle_iters=self.cfg["engine"]["site_throttle_iters"],
                       **scan_settings(self.cfg))
        want_peak = out["peak_kw"].reshape(K, A, M).max(axis=2)
        gap = max(ref.relative_gap(rt.ravel(), out["runtime_h"]),
                  ref.relative_gap(kwh.ravel(), out["kwh"]),
                  ref.relative_gap(co2.ravel(), out["co2"][:, 0]),
                  ref.relative_gap(peak, want_peak))
        ok = (np.isfinite(rt) & np.isfinite(kwh) & np.isfinite(co2)).all(
            axis=2) & np.isfinite(peak)
        return {"max_rel_gap": (gap, self.spec["check"]["max_rel_gap"]),
                "unscored": (float((~ok).sum() * M), 0.0)}

    def work(self) -> Dict[str, int]:
        rt = self.answers()[0]
        slots = np.ceil(rt - 1e-9)
        return {"lane_slots": int(np.nansum(slots)),
                "group_slots": int(np.nansum(np.nanmax(slots, axis=2))),
                "members": 1}


class Replan(Client):
    """`kind: replan`: `Campaign.optimize` against a new year-long
    forecast per request (the objective layer, then one engine sweep of
    the chosen schedule)."""

    def build(self) -> None:
        self.bands, (self.camp,) = program_campaigns(self.carina, self.cfg)
        self.camp.calibrated()
        days = generate.archive_days(self.spec["forecast"])
        self.arc = generate.archive(self.cfg["carbon"], days, self.seed)
        self.warm_arc = generate.archive(self.cfg["carbon"], days,
                                         generate.WARM_UP_SEED)

    def year(self, k: int) -> np.ndarray:
        return generate.forecast_slice(self.arc, self.spec["forecast"], k)

    def replan(self, year: np.ndarray, search_seed: int):
        sp = self.spec
        return self.camp.optimize(
            sp["objective"], deadline_h=sp["deadline_h"],
            carbon_trace=year, method=sp["method"],
            candidates=sp["candidates"], iterations=sp["iterations"],
            steps=sp["steps"], seed=search_seed, precision=self.precision)

    def warm_up(self) -> None:
        """The program compiles an objective for each forecast.  The first
        warm-up re-plan finds every program in the compile cache after a
        checkout's first run; the second compiles its objective with the
        cache off, as the window's re-plans do (the first compile of a
        process costs seconds more than later ones)."""
        fc = self.spec["forecast"]
        for k in (0, 1):
            with (compile_cache_off() if k else contextlib.nullcontext()):
                self.replan(generate.forecast_slice(self.warm_arc, fc, k),
                            generate.search_seed(generate.WARM_UP_SEED, k))

    def request(self, k: int) -> int:
        res = self.replan(self.year(k), generate.search_seed(self.seed, k))
        self.records.append((k, res))
        return 1

    def check(self) -> Dict[str, Tuple[float, float]]:
        """Each returned schedule's engine row (`result`) against the
        reference with the executor's chunk-end stop, and the objective's
        own reading of it (`metrics`) against the reference scanned
        without that stop, as the objective scans; one gap over both."""
        bands = ref.Bands(self.cfg["bands"])
        campaign = self.cfg["campaigns"][0]
        rate, machine = ref.calibrate(campaign, bands)
        settings = scan_settings(self.cfg)
        gap, unscored = 0.0, 0
        for k, res in self.records:
            sched = res.schedule
            lanes = ref.lanes_for(
                campaign, rate, machine,
                table=ref.parametric_table(np.asarray(sched.logits)[None],
                                           sched.u_min, sched.u_max),
                batch=[float(sched.batch_size)])
            carbon = ref.trace_carbon(self.year(k)[None, None, :])
            swept = ref.scan(lanes, bands.table(), carbon, **settings)
            searched = ref.scan(lanes, bands.table(), carbon,
                                **dict(settings, chunk_slots=None))
            got, m = res.result, res.metrics
            row = [got.runtime_h, got.energy_kwh, got.co2_kg]
            seen = [m.runtime_h, m.energy_kwh, float(np.ravel(m.co2_kg)[0])]
            if not all(math.isfinite(float(v)) for v in row + seen):
                unscored += 1
            gap = max(gap,
                      ref.relative_gap(row, [swept["runtime_h"][0],
                                             swept["kwh"][0],
                                             swept["co2"][0, 0]]),
                      ref.relative_gap(seen, [searched["runtime_h"][0],
                                              searched["kwh"][0],
                                              searched["co2"][0, 0]]),
                      abs(float(m.unfinished)))
        return {"max_rel_gap": (gap, self.spec["check"]["max_rel_gap"]),
                "unscored": (float(unscored), 0.0)}


CLIENTS = {"refresh": Refresh, "fleet_refresh": FleetRefresh,
           "replan": Replan}
