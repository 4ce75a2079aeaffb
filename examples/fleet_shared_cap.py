"""Two OEM-scale campaigns under one site power envelope.

The paper's two database-generation campaigns (1.48M and 3.66M
scenarios) ran on shared company infrastructure — the interesting
coupling is *between* the workflows: one office background load, one
site power budget, one grid carbon signal.  This example builds a
`Fleet` of both campaigns under a `Site` with an active power cap,
then:

  1. sweeps fleet-wide assignments (fixed policies and the bundled
     `AllocationSchedule` families) — each row is M per-campaign
     results plus a site rollup with the peak site draw;
  2. shows the cap biting: coupled runtimes vs free-running ones;
  3. synthesizes a *joint* schedule with `Fleet.optimize` — per-campaign
     deadlines, shared cap — and compares its site CO2 against the
     independently-optimized per-campaign schedules run under the same
     cap (the joint planner staggers the campaigns instead of letting
     the curtailment throttle both at once).

    PYTHONPATH=src python examples/fleet_shared_cap.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import repro.carina as carina

FAST = bool(os.environ.get("CARINA_EXAMPLE_FAST"))   # CI smoke mode

DEADLINES = [300.0, 480.0]                           # hours, per campaign


def fmt(fr: "carina.FleetResult") -> str:
    s = fr.site
    peak = f"{s.peak_kw:.3f} kW" if s.peak_kw is not None else "untracked"
    return (f"makespan {s.runtime_h:6.1f} h  energy {s.energy_kwh:6.1f} kWh"
            f"  CO2 {s.co2_kg:5.1f} kg  peak {peak}")


def main():
    site = carina.Site(power_cap_kw=0.45, office_kw=0.12)
    fleet = carina.Fleet([carina.Campaign(carina.OEM_CASE_1),
                          carina.Campaign(carina.OEM_CASE_2)], site)
    print(f"=== fleet of {fleet.n_campaigns} campaigns under a "
          f"{site.power_cap_kw} kW site cap (office draw "
          f"{site.office_kw} kW at full background)\n")

    assignments = [
        carina.BASELINE,
        carina.PEAK_AWARE_BOOSTED,
        carina.proportional_split(0.8),
        carina.carbon_gated_cap(0.45),
        carina.deadline_weighted_split(DEADLINES),
    ]
    carina.reset_scan_stats()
    rows = fleet.sweep(assignments, deadlines=DEADLINES)
    st = carina.scan_stats()
    print("=== fleet-wide assignments (grouped-lane sweep, coupled)")
    print(f"  engine: devices_used={st.devices_used} "
          f"precision={st.precision_mode or 'fp64'} "
          f"chunks={st.chunks} jit_shapes={st.jit_compiles}")
    for fr in rows:
        print(f"  {fr.policy:28s} {fmt(fr)}")
        for r in fr.campaigns:
            print(f"      {r.policy:44s} {r.runtime_h:6.1f} h "
                  f"{r.energy_kwh:5.1f} kWh")

    free = carina.Fleet(fleet.campaigns).sweep([carina.BASELINE])[0]
    capped = rows[0]
    print("\n=== the cap bites (baseline assignment)")
    for f, c in zip(free.campaigns, capped.campaigns):
        print(f"  {f.policy:24s} free {f.runtime_h:6.1f} h -> "
              f"capped {c.runtime_h:6.1f} h "
              f"({100 * (c.runtime_h / f.runtime_h - 1):+.1f}%)")

    kw = (dict(candidates=32, iterations=4, steps=40) if FAST
          else dict(candidates=128, iterations=20, steps=300))
    t0 = time.perf_counter()
    res = fleet.optimize("co2", deadlines=DEADLINES, **kw)
    dt = time.perf_counter() - t0
    print(f"\n=== joint optimization ({res.method}, {res.evaluations} "
          f"evaluations, {dt:.1f} s)")
    print(f"  joint       {fmt(carina.FleetResult(res.schedules[0].name, res.results, res.site))}")

    # the independently-optimized schedules, evaluated under the same cap
    wl_m = [c.calibrated() for c in fleet.campaigns]
    ind_cases = [
        carina.SweepCase(r.schedule, wl, mach, site.bands,
                         carina.GridCarbonModel(), 9.0,
                         label=r.schedule.name, deadline_h=d)
        for r, (wl, mach), d in zip(res.independent, wl_m, DEADLINES)]
    ind = carina.fleet_sweep([ind_cases], site, names=["independent"])[0]
    print(f"  independent {fmt(ind)}")
    saved = ind.site.co2_kg - res.site.co2_kg
    if saved > 1e-3:
        print(f"  -> joint planning saves {saved:.2f} kg CO2 "
              f"({100 * saved / ind.site.co2_kg:.1f}%) over per-campaign "
              "optima that fight for the same headroom")
    else:
        print("  -> on this cap the independent optima already stagger "
              "cleanly; tighter caps separate them further")

    for r, d in zip(res.results, DEADLINES):
        assert r.runtime_h <= d * 1.02, (r.policy, r.runtime_h, d)
    print("\nall campaigns met their deadlines under the shared cap")
    st = carina.scan_stats()
    print(f"engine totals: devices_used={st.devices_used} "
          f"precision={st.precision_mode or 'fp64'} "
          f"chunks={st.chunks} jit_shapes={st.jit_compiles} "
          "(scale-out knobs: Fleet.sweep(devices=, precision=))")


if __name__ == "__main__":
    main()
