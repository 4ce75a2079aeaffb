"""The request client of traffic kind `rescore`, found by
`clients.client_class`."""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

import generate
import reference as ref
from clients import Client, program_campaigns, scan_settings, useful_slots

#: purpose tags of this kind's seed streams (`generate.rng`), apart from
#: the generator's own
REVISIONS, WARM_UP_REVISIONS = 11, 12

#: candidates the reference scans at once
REFERENCE_BLOCK = 4096


class Rescore(Client):
    """`kind: rescore`: the operator's refresh cycle under a fixed
    forecast.  The first cycle scores the whole candidate set (the
    `refresh` mix's) through `compile_plan`, `execute_plan` and
    `summarize_plan`; each request then replaces `changed` candidates,
    half day schedules and half pace keepers as the set holds them, by
    fresh ones of the same family and re-scores the set through
    `delta_sweep`, chained on the previous request's plan and results.
    Every request answers every (candidate x window) case, re-scanned or
    spliced."""

    faults = Client.faults + ("stale_splice",)

    def build(self) -> None:
        c = self.carina
        _, (self.camp,) = program_campaigns(c, self.cfg)
        self.wl, self.mach = self.camp.calibrated()
        self._par = par = self.spec["candidates"]["parametric"]
        self._ddl = self.spec["candidates"]["deadline"]
        first = generate.refresh_candidates(self.spec["candidates"],
                                            self.seed)
        self.n_par = len(first["logits"])
        S = self.n_par + len(first["deadline_h"])
        # every candidate met, by id: logits (day schedules) or a
        # deadline (pace keepers, NaN for day schedules)
        self.logits: List[np.ndarray] = list(first["logits"]) + [
            np.zeros(par["slots"])] * (S - self.n_par)
        self.deadline_h: List[float] = [np.nan] * self.n_par + [
            float(d) for d in first["deadline_h"]]
        self.current = np.arange(S)          # candidate id in each slot
        fc = self.spec["forecast"]
        year = generate.archive(self.cfg["carbon"], fc["archive_days"],
                                self.seed)
        self.members = generate.windows(year, fc["window_h"], fc["stride_h"])
        self.forecast = c.trace_windows(year, fc["window_h"], fc["stride_h"],
                                        name="forecast")
        self.plan = self.results = None

    # -- candidates ------------------------------------------------------
    def _schedule(self, cid: int):
        c = self.carina
        if np.isnan(self.deadline_h[cid]):
            return c.ParametricSchedule(
                tuple(float(v) for v in self.logits[cid]),
                u_min=self._par["u_min"], u_max=self._par["u_max"],
                batch_size=self._par["batch_size"], name=f"parametric-{cid}")
        return c.deadline_schedule(
            self.deadline_h[cid], u_low=self._ddl["u_low"],
            u_high=self._ddl["u_high"], band=self._ddl["band"],
            batch_size=self._ddl["batch_size"], name=f"deadline-{cid}")

    def _revise(self, g: np.random.Generator, n_par: int, n_ddl: int
                ) -> Dict[int, object]:
        """{slot: fresh schedule} for `n_par` day-schedule slots and
        `n_ddl` pace-keeper slots drawn without replacement, each new
        candidate drawn as the mix draws the family's candidates."""
        S = len(self.current)
        slots = np.concatenate([
            g.choice(self.n_par, n_par, replace=False),
            self.n_par + g.choice(S - self.n_par, n_ddl, replace=False)])
        logits = generate.parametric_logits(self._par, n_par, g)
        deadlines = g.uniform(*self._ddl["deadline_h"], n_ddl)
        new = {}
        for j, slot in enumerate(slots):
            cid = len(self.logits)
            if j < n_par:
                self.logits.append(logits[j])
                self.deadline_h.append(np.nan)
            else:
                self.logits.append(np.zeros(self._par["slots"]))
                self.deadline_h.append(float(deadlines[j - n_par]))
            self.current[slot] = cid
            new[int(slot)] = self._schedule(cid)
        return new

    def _split(self, n: int) -> Tuple[int, int]:
        """(day schedules, pace keepers) among `n` revisions, in the
        candidate set's proportion."""
        n_par = n * self.n_par // len(self.current)
        return n_par, n - n_par

    # -- requests --------------------------------------------------------
    def _first_cycle(self) -> None:
        c, eng = self.carina, self.cfg["engine"]
        cases = [c.SweepCase(self._schedule(int(cid)), self.wl, self.mach,
                             self.camp.bands, self.forecast,
                             self.camp.start_hour, label=f"slot-{j}")
                 for j, cid in enumerate(self.current)]
        self.plan = c.compile_plan(cases,
                                   progress_buckets=eng["progress_buckets"],
                                   max_days=eng["max_days"],
                                   precision=self.precision)
        self.results = c.summarize_plan(
            self.plan, c.execute_plan(self.plan, devices=self.chips))

    def _rescore(self, k: int, new: Dict[int, object]) -> int:
        d = self.carina.delta_sweep(self.plan, self.results, schedules=new,
                                    devices=self.chips)
        self.plan, self.results = d.plan, d.results
        self.records.append((k, d.results, d.recomputed,
                             self.current.copy()))
        return len(d.results) * len(self.members)

    def warm_up(self) -> None:
        """The first cycle, then one rescore for each lane bucket up to
        the one `changed` revisions pad to, with pace keepers among the
        revisions and without (chunks with a chunk-built lane take wider
        inputs): every chunk shape the window's rescores meet, as later
        chunks run fewer lanes of the same kinds."""
        self._first_cycle()
        changed = self.spec["changed"]
        g = generate.rng(self.seed, WARM_UP_REVISIONS)
        bucket = 8
        while True:
            n = min(bucket, changed)
            self._rescore(0, self._revise(g, *self._split(n)))
            self._rescore(0, self._revise(g, min(n, self.n_par), 0))
            if bucket >= changed:
                break
            bucket *= 2
        self.records.clear()

    def request(self, k: int) -> int:
        if k >= self.spec["max_requests"]:
            raise ValueError(f"request {k} is past the mix's max_requests "
                             f"({self.spec['max_requests']})")
        if self.plan is None:
            self._first_cycle()
        g = generate.rng(self.seed, REVISIONS, k)
        return self._rescore(k, self._revise(g, *self._split(
            self.spec["changed"])))

    def size(self) -> int:
        return len(self.current) * len(self.members)

    def plant(self, fault: str, monkeypatch) -> None:
        """`stale_splice`: the delta screen takes every revised schedule
        for the incumbent, so its last answer is spliced in."""
        if fault != "stale_splice":
            return super().plant(fault, monkeypatch)
        from repro.core import engine_jax
        monkeypatch.setattr(engine_jax, "_value_changed",
                            lambda old, new: False)

    # -- comparison with the reference ------------------------------------
    def _pairs(self):
        """Every (answer, candidate) pair the window's answers hold, once:
        (the answer objects, their candidate ids, and for each slot of
        each request the index of its pair).  A spliced answer is the
        same object request after request, so the pairs grow with the
        revisions, not with the requests."""
        answers = [r for _, res, _, _ in self.records for r in res]
        ids = np.fromiter(map(id, answers), np.int64, len(answers))
        cids = np.concatenate([cur for _, _, _, cur in self.records])
        _, first, where = np.unique(np.stack([ids, cids], axis=1), axis=0,
                                    return_index=True, return_inverse=True)
        return [answers[i] for i in first], cids[first], where.ravel()

    def reference(self, cids: np.ndarray) -> Dict[str, np.ndarray]:
        """The plain reference's runtime_h, kWh and per-member CO2 of the
        candidates `cids` under the forecast, scanned in blocks."""
        bands = ref.Bands(self.cfg["bands"])
        campaign = self.cfg["campaigns"][0]
        rate, machine = ref.calibrate(campaign, bands)
        par, ddl = self._par, self._ddl
        deadline = np.asarray(self.deadline_h)[cids]
        day = np.isnan(deadline)
        table = np.zeros((len(cids), par["slots"]))
        table[day] = ref.parametric_table(
            np.asarray(self.logits)[cids[day]], par["u_min"], par["u_max"])
        batch = np.where(day, float(par["batch_size"]),
                         float(ddl["batch_size"]))
        out = {"runtime_h": [], "kwh": [], "co2": []}
        for lo in range(0, len(cids), REFERENCE_BLOCK):
            part = slice(lo, lo + REFERENCE_BLOCK)
            lanes = ref.lanes_for(campaign, rate, machine, table=table[part],
                                  batch=batch[part], deadline=deadline[part],
                                  pace=(ddl["u_low"], ddl["u_high"],
                                        ddl["band"]))
            got = ref.scan(lanes, bands.table(),
                           ref.trace_carbon(self.members),
                           **scan_settings(self.cfg))
            for key in out:
                out[key].append(got[key])
        return {key: np.concatenate(v) for key, v in out.items()}

    def check(self) -> Dict[str, Tuple[float, float]]:
        if not self.records:
            return self.nothing_to_compare()
        answers, cids, where = self._pairs()
        E = len(self.members)
        rt, kwh, co2 = (np.array([getattr(r, f) for r in answers],
                                 dtype=float)
                        for f in ("runtime_h", "energy_kwh", "co2_kg"))
        mem = np.full((len(answers), E), np.nan)
        for i, r in enumerate(answers):
            if r.co2_ensemble is not None and \
                    len(r.co2_ensemble.samples) == E:
                mem[i] = r.co2_ensemble.samples
        ok = (np.isfinite(rt) & np.isfinite(kwh) & np.isfinite(co2)
              & np.isfinite(mem).all(axis=1))
        unique, back = np.unique(cids, return_inverse=True)
        want = {k: v[back.ravel()] for k, v in self.reference(unique).items()}
        gap = max(ref.relative_gap(rt, want["runtime_h"]),
                  ref.relative_gap(kwh, want["kwh"]),
                  ref.relative_gap(co2, want["co2"].mean(axis=1)),
                  ref.relative_gap(mem, want["co2"]))
        unscored = int((~ok)[where].sum()) * E
        return {"max_rel_gap": (gap, self.spec["check"]["max_rel_gap"]),
                "unscored": (float(unscored), 0.0)}

    def work(self) -> Dict[str, int]:
        """Useful lane-slots of the re-scanned cases alone: a spliced
        answer cost the kernel nothing."""
        rt = np.array([res[i].runtime_h for _, res, rescanned, _ in
                       self.records for i in rescanned], dtype=float)
        return {"lane_slots": useful_slots(rt[np.isfinite(rt)]),
                "group_slots": 0, "members": len(self.members)}


CLIENT = Rescore
