"""Drive CARINA's main path once on a TPU, at the OEM campaign's full scale.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # the lane-sharded path only: 4 vs 1

Phases on one chip, each through the entry points a user calls:

  device    the default JAX device must be a TPU (else exit non-zero);
            prints the jax / jaxlib / libtpu versions and device count
  sweep     `Campaign(OEM_CASE_1).sweep`: 1.48 M scenarios calibrated to
            the measured 180.30 h / 48.67 kWh, 2048 seeded carbon-blind
            schedules (1024 `ParametricSchedule`s with seeded logits, 1024
            progress-aware `DeadlineSchedule`s with spread deadlines)
            against a ~50-member carbon ensemble (`trace_windows`: 14-day
            windows, 7-day stride, over a seeded year-long archive).
            64 cases are checked against the host NumPy backend (1e-6
            relative) and 4 against `simulate_campaign_exact` (0.5 %)
  fleet     `Fleet(OEM_CASE_1, OEM_CASE_2)` under a 0.45 kW site cap:
            256 assignments through the site-coupled kernel; 4 checked
            against `simulate_fleet` (0.5 %) and the NumPy backend (1e-6)
  serving   a seeded one-day stream of 1 M requests through
            `ServingSession` submit/drain with the carbon-gated greedy
            policy; per-request energy/CO2 must sum to the lane totals and
            the executed window must match the NumPy backend (1e-6)
  optimize  a short `Campaign.optimize("co2", deadline_h=214)` (a few CEM
            population steps, then a few gradient steps through the
            jitted, differentiated scan); the result must be finite and
            meet the deadline

`--chips N` (N > 1) runs only what spans chips: the uncoupled sweep and
the coupled fleet sweep with `devices=N`, then with `devices=1`, in this
one process; the results must be bitwise equal.

Each phase prints lanes, slots, chunks, jit shapes, bytes uploaded per
chunk, wall time including compilation, and the device's
`peak_bytes_in_use`: informational lines, not speeds.  Any failure ends
the script with a non-zero exit and no result line.  The last line of
stdout is {"ok": true, "device": {"platform", "kind", "count"}} as JAX
reports the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
N_SCHEDULES = 2048          # half parametric, half deadline-paced
PARAM_U_MIN = 0.45          # keeps every campaign inside one 14-day window
DEADLINES_H = (190.0, 320.0)
ARCHIVE_DAYS = 365
WINDOW_H, STRIDE_H = 14 * 24, 7 * 24
N_HOST_CHECK = 64
N_ORACLE = 4
N_ASSIGNMENTS = 256
SITE_CAP_KW, OFFICE_KW = 0.45, 0.12
N_REQUESTS = 1_000_000
DEADLINE_OPT_H = 214.0
HOST_RTOL = 1e-6            # the documented fp64 <-> mixed bar
ORACLE_RTOL = 5e-3          # the documented sequential-oracle band


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-300)


def max_rel(got, want, fields=("runtime_h", "energy_kwh", "co2_kg")):
    """Largest relative difference over `fields` of paired SimResults."""
    return max(rel(getattr(g, f), getattr(w, f))
               for g, w in zip(got, want) for f in fields)


class Phase:
    """Zeroes the scan counters on entry; `report` prints the phase's
    shape and counters with its wall time (compilation included)."""

    def __init__(self, name: str):
        import repro.carina as carina
        self.name = name
        carina.reset_scan_stats()
        self.t0 = time.perf_counter()

    def say(self, msg: str) -> None:
        print(f"[{self.name}] {msg}", flush=True)

    def report(self, lanes: int, slots: int):
        import jax

        import repro.carina as carina
        st = carina.scan_stats()
        wall = time.perf_counter() - self.t0
        mem = jax.devices()[0].memory_stats() or {}
        self.say(f"lanes={lanes} slots={slots} chunks={st.chunks} "
                 f"slot_work={st.slot_work} jit_shapes={st.jit_compiles} "
                 f"bytes_uploaded_per_chunk="
                 f"{st.bytes_uploaded // max(st.chunks, 1)} "
                 f"devices_used={st.devices_used} "
                 f"precision={st.precision_mode} wall_s={wall:.3f} "
                 f"peak_bytes_in_use={mem.get('peak_bytes_in_use')}")
        for sig in sorted(st.jit_shapes, key=str):
            self.say(f"jit shape {sig}")
        require(st.chunks > 0, f"{self.name}: no chunk ran on the device")
        return st


def device_phase(chips: int):
    import jax
    import jaxlib
    from importlib import metadata

    devs = jax.devices()
    d0 = devs[0]
    require(d0.platform == "tpu",
            f"JAX found no TPU: the default device is {d0.platform!r}")
    require(len(devs) >= chips,
            f"--chips {chips} needs {chips} devices, JAX sees {len(devs)}")
    print(f"[device] jax {jax.__version__} jaxlib {jaxlib.__version__} "
          f"libtpu {metadata.version('libtpu')} platform {d0.platform} "
          f"kind {d0.device_kind!r} devices {len(devs)}", flush=True)
    return d0, len(devs)


# ---------------------------------------------------------------------------
# Workload construction (seeded)
# ---------------------------------------------------------------------------
def carbon_inputs(tmp: str):
    """A seeded year-long grid archive -> (year trace, window ensemble)."""
    import repro.carina as carina
    path = carina.write_synthetic_archive(
        os.path.join(tmp, "grid-year.csv"), zones=("ZONE-A",),
        days=ARCHIVE_DAYS, seed=SEED)
    year = carina.load_carbon_archive(path).to_trace()
    return year, carina.trace_windows(year, WINDOW_H, STRIDE_H,
                                      name="year-windows")


def sweep_schedules():
    import repro.carina as carina
    rng = np.random.default_rng(SEED)
    n = N_SCHEDULES
    half = n // 2
    par = [carina.ParametricSchedule(tuple(float(v) for v in row),
                                     u_min=PARAM_U_MIN, u_max=1.0,
                                     name=f"parametric-{i}")
           for i, row in enumerate(rng.normal(0.0, 1.5, (half, 24)))]
    ddl = [carina.deadline_schedule(float(d), name=f"deadline-{i}")
           for i, d in enumerate(np.linspace(*DEADLINES_H, n - half))]
    return par + ddl


def fleet_assignments():
    import repro.carina as carina
    rng = np.random.default_rng(SEED + 1)
    return [tuple(carina.ParametricSchedule(
        tuple(float(v) for v in rng.normal(0.0, 1.5, 24)),
        u_min=PARAM_U_MIN, u_max=1.0, name=f"fleet-{k}-{m}")
        for m in range(2)) for k in range(N_ASSIGNMENTS)]


def oem_fleet():
    import repro.carina as carina
    return carina.Fleet([carina.Campaign(carina.OEM_CASE_1),
                         carina.Campaign(carina.OEM_CASE_2)],
                        carina.Site(power_cap_kw=SITE_CAP_KW,
                                    office_kw=OFFICE_KW))


def campaign_cases(camp, schedules, carbon):
    """The `SweepCase`s `Campaign.sweep` builds for these schedules."""
    import repro.carina as carina
    wl, m = camp.calibrated()
    return [carina.SweepCase(s, wl, m, camp.bands, carbon, camp.start_hour,
                             label=s.name) for s in schedules]


def fleet_cases(fleet, assignment):
    """The member `SweepCase`s of one fleet assignment (site signals)."""
    import repro.carina as carina
    carbon = fleet.site.carbon or carina.GridCarbonModel()
    return [carina.SweepCase(s, *c.calibrated(), fleet.site.bands, carbon,
                             c.start_hour, label=s.name)
            for c, s in zip(fleet.campaigns, assignment)]


def slots_of(results) -> int:
    """Hourly slots the longest lane scanned."""
    return int(math.ceil(max(r.runtime_h for r in results)))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------
def sweep_phase(year, ens):
    import repro.carina as carina
    camp = carina.Campaign(carina.OEM_CASE_1)
    base = camp.baseline()
    print(f"[sweep] calibrated baseline {base.runtime_h:.2f} h "
          f"{base.energy_kwh:.2f} kWh (measured 180.30 h / 48.67 kWh)",
          flush=True)
    require(abs(base.runtime_h - 180.30) < 0.01
            and abs(base.energy_kwh - 48.67) < 0.01,
            "OEM case 1 did not calibrate to its measured run")
    scheds = sweep_schedules()
    ph = Phase("sweep")
    ph.say(f"{len(scheds)} schedules x {len(ens)} carbon members, "
           f"{carina.OEM_CASE_1.n_scenarios} scenarios each")
    res = camp.sweep(scheds, carbon_ensemble=ens)
    st = ph.report(lanes=len(scheds), slots=slots_of(res))
    scanned = st.plan_hits + st.plan_misses + st.disk_hits
    require(scanned == len(scheds),
            f"only {scanned} of {len(scheds)} cases went through the scan")
    for r in res:
        require(all(math.isfinite(v) for v in
                    (r.runtime_h, r.energy_kwh, r.co2_kg)),
                f"non-finite result for {r.policy}")

    idx = np.linspace(0, len(scheds) - 1, N_HOST_CHECK).astype(int)
    t0 = time.perf_counter()
    host = carina.trace_sweep(
        campaign_cases(camp, [scheds[i] for i in idx], ens),
        backend="numpy")
    dev = [res[i] for i in idx]
    err = max_rel(dev, host)
    err_members = max(rel(a, b) for d, h in zip(dev, host)
                      for a, b in zip(d.co2_ensemble.samples,
                                      h.co2_ensemble.samples))
    ph.say(f"host NumPy check: {len(idx)} cases, max rel err "
           f"(runtime, kWh, CO2 mean) {err:.3e}, per-member CO2 "
           f"{err_members:.3e} ({time.perf_counter() - t0:.1f} s)")
    require(err <= HOST_RTOL, f"sweep vs host NumPy: {err:.3e} > 1e-6")

    wl, m = camp.calibrated()
    picks = [0, len(scheds) // 2 - 1, len(scheds) // 2,
             len(scheds) - 1][:N_ORACLE]
    worst = 0.0
    for j, i in enumerate(picks):
        e = (7 * j + 3) % len(ens)
        ref = carina.simulate_campaign_exact(
            wl, scheds[i], m, camp.bands, carbon=ens.member(e),
            start_hour=camp.start_hour)
        got = res[i]
        errs = (rel(got.runtime_h, ref.runtime_h),
                rel(got.energy_kwh, ref.energy_kwh),
                rel(got.co2_ensemble.samples[e], ref.co2_kg))
        ph.say(f"oracle {scheds[i].name} member {e}: "
               f"{got.runtime_h:.3f} h {got.energy_kwh:.4f} kWh "
               f"{got.co2_ensemble.samples[e]:.4f} kg vs "
               f"{ref.runtime_h:.3f} h {ref.energy_kwh:.4f} kWh "
               f"{ref.co2_kg:.4f} kg, max rel err {max(errs):.3e}")
        worst = max(worst, *errs)
    require(worst <= ORACLE_RTOL,
            f"sweep vs simulate_campaign_exact: {worst:.3e} > 0.5 %")
    return camp


def fleet_phase():
    import repro.carina as carina
    fleet = oem_fleet()
    assignments = fleet_assignments()
    ph = Phase("fleet")
    ph.say(f"{len(assignments)} assignments x {fleet.n_campaigns} "
           f"campaigns under a {SITE_CAP_KW} kW cap")
    rows = fleet.sweep(assignments)
    members = [r for fr in rows for r in fr.campaigns]
    st = ph.report(lanes=len(members), slots=slots_of(members))
    require(st.grouped_lanes > 0, "no lane ran through the coupled kernel")

    picks = np.linspace(0, len(assignments) - 1, 4).astype(int)
    t0 = time.perf_counter()
    host = fleet.sweep([assignments[k] for k in picks], backend="numpy")
    err_host = max(max_rel(rows[k].campaigns, h.campaigns)
                   for k, h in zip(picks, host))
    err_peak = max(rel(rows[k].site.peak_kw, h.site.peak_kw)
                   for k, h in zip(picks, host))
    err_orc = 0.0
    for k in picks:
        orc = carina.simulate_fleet(fleet_cases(fleet, assignments[k]),
                                    fleet.site)
        err_orc = max(err_orc, max_rel(rows[k].campaigns, orc.campaigns))
    ph.say(f"checks on {len(picks)} assignments: host NumPy max rel err "
           f"{err_host:.3e} (site peak {err_peak:.3e}), simulate_fleet "
           f"max rel err {err_orc:.3e} ({time.perf_counter() - t0:.1f} s)")
    require(err_host <= HOST_RTOL and err_peak <= HOST_RTOL,
            f"fleet vs host NumPy: {max(err_host, err_peak):.3e} > 1e-6")
    require(err_orc <= ORACLE_RTOL,
            f"fleet vs simulate_fleet: {err_orc:.3e} > 0.5 %")


def serving_phase():
    import repro.carina as carina
    carbon = carina.HourlySignal(tuple(float(v) * carina.DTE_FACTOR
                                       for v in carina.MIDWEST_HOURLY))
    sess = carina.ServingSession(policy="greedy", carbon=carbon,
                                 start_hour=6.0,
                                 service_rate=N_REQUESTS * 3e-5, seed=SEED)
    window = sess.window()
    ph = Phase("serving")
    sess.submit(n=N_REQUESTS, shape="camel", seed=SEED, slack_h=(4.0, 12.0),
                camel_fracs=(0.2, 0.55), tier_mix=(0.8, 0.15, 0.05))
    roll = sess.drain()
    rep = sess.reports[-1]
    ph.report(lanes=len(rep.lanes), slots=window.n_slots)
    ph.say(f"{roll.n_requests} requests: admitted {roll.n_admitted}, "
           f"rejected {roll.n_rejected}, degraded {roll.n_degraded}, "
           f"SLO-miss {roll.slo_miss_rate:.4%}, {roll.energy_kwh:.4f} kWh, "
           f"{roll.co2_kg:.4f} kg CO2")
    require(roll.n_requests == N_REQUESTS and roll.n_windows == 1,
            "the session did not serve the whole stream in one window")
    err_e = rel(float(rep.request_energy_kwh.sum()), rep.energy_kwh)
    err_c = rel(float(rep.request_co2_kg.sum()), rep.co2_kg)
    host, _, _ = carina.execute_assignment(rep.assignment, window,
                                           sess.tiers, backend="numpy")
    err_host = max_rel(rep.lanes, host)
    ph.say(f"per-request sums vs lane totals: kWh {err_e:.3e}, CO2 "
           f"{err_c:.3e}; window vs host NumPy max rel err {err_host:.3e}")
    require(err_e <= 1e-9 and err_c <= 1e-9,
            "per-request attribution does not sum to the lane totals")
    require(len(host) == len(rep.lanes) and err_host <= HOST_RTOL,
            f"serving window vs host NumPy: {err_host:.3e} > 1e-6")


def optimize_phase(camp, year):
    ph = Phase("optimize")
    res = camp.optimize("co2", deadline_h=DEADLINE_OPT_H, carbon_trace=year,
                        method="cem+grad", candidates=256, iterations=4,
                        steps=25, seed=SEED)
    r = res.result
    ph.report(lanes=256, slots=int(math.ceil(r.runtime_h)))
    ph.say(f"method {res.method}, {res.evaluations} evaluations: "
           f"{r.runtime_h:.3f} h {r.energy_kwh:.4f} kWh {r.co2_kg:.4f} kg "
           f"(deadline {DEADLINE_OPT_H} h)")
    require(all(math.isfinite(v) for v in
                (r.runtime_h, r.energy_kwh, r.co2_kg, res.value)),
            "optimize returned a non-finite result")
    require(r.runtime_h <= DEADLINE_OPT_H * (1.0 + ORACLE_RTOL),
            f"optimized schedule misses the deadline: {r.runtime_h:.3f} h")


def sharded_phase(n_dev: int, ens):
    """The lane-sharded path: devices=n_dev vs devices=1, bitwise."""
    import repro.carina as carina
    camp = carina.Campaign(carina.OEM_CASE_1)
    cases = campaign_cases(camp, sweep_schedules(), ens)
    fleet = oem_fleet()
    assignments = fleet_assignments()

    def key(r):
        ens_s = r.co2_ensemble.samples if r.co2_ensemble else ()
        return (r.runtime_h, r.energy_kwh, r.co2_kg, r.cost_usd, ens_s)

    for devices in (n_dev, 1):
        ph = Phase(f"sharded-sweep devices={devices}")
        res = carina.sweep(cases, devices=devices)
        st = ph.report(lanes=len(cases), slots=slots_of(res))
        require(st.devices_used == devices,
                f"sweep ran on {st.devices_used} devices, not {devices}")
        ph = Phase(f"sharded-fleet devices={devices}")
        rows = fleet.sweep(assignments, devices=devices)
        members = [r for fr in rows for r in fr.campaigns]
        st = ph.report(lanes=len(members), slots=slots_of(members))
        require(st.devices_used == devices,
                f"fleet ran on {st.devices_used} devices, not {devices}")
        if devices == n_dev:
            sharded = (res, rows)
    diff_sweep = sum(key(a) != key(b) for a, b in zip(sharded[0], res))
    diff_fleet = sum(
        [key(a) for a in fa.campaigns] != [key(b) for b in fb.campaigns]
        or fa.site.peak_kw != fb.site.peak_kw
        for fa, fb in zip(sharded[1], rows))
    print(f"[sharded] devices={n_dev} vs devices=1: {diff_sweep} of "
          f"{len(res)} sweep cases and {diff_fleet} of {len(rows)} fleet "
          "assignments differ bitwise", flush=True)
    require(diff_sweep == 0 and diff_fleet == 0,
            "sharded results are not bitwise equal to one device")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="N > 1 runs only the lane-sharded path on N chips "
                         "against one chip")
    args = ap.parse_args(argv)
    d0, count = device_phase(args.chips)
    with tempfile.TemporaryDirectory() as tmp:
        year, ens = carbon_inputs(tmp)
    if args.chips > 1:
        sharded_phase(args.chips, ens)
    else:
        camp = sweep_phase(year, ens)
        fleet_phase()
        serving_phase()
        optimize_phase(camp, year)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": count}}),
        flush=True)


if __name__ == "__main__":
    main()
