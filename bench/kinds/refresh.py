"""The request client of traffic kind `refresh`, found by
`clients.client_class`."""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

import generate
import reference as ref
from clients import Client, program_campaigns, scan_settings, useful_slots


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


CLIENT = Refresh
