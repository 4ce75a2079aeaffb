"""The request client of traffic kind `fleet_refresh`, found by
`clients.client_class`."""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

import generate
import reference as ref
from clients import Client, program_campaigns, scan_settings


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


CLIENT = FleetRefresh
