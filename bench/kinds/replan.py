"""The request client of traffic kind `replan`, found by
`clients.client_class`."""
from __future__ import annotations

import contextlib
import math
from typing import Dict, Tuple

import numpy as np

import generate
import reference as ref
from clients import Client, compile_cache_off, program_campaigns, scan_settings


class Replan(Client):
    """`kind: replan`: `Campaign.optimize` against a new year-long
    forecast per request (the objective layer, then one engine sweep of
    the chosen schedule)."""
    #: one answer per request: there is no batch to leave half of out
    faults = ("state_unchanged", "answer_altered")

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


CLIENT = Replan
