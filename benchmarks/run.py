"""Benchmark harness — one function per paper table/figure plus the
roofline/kernel benches.  Prints ``name,us_per_call,derived`` CSV rows.

  fig1_policy_frontier   Figure 1: runtime-penalty vs energy-savings frontier
  frontier_sweep         vectorized sweep engine vs sequential simulation
                         (120 schedules in one NumPy pass; core/engine.py)
  trace_sweep            trace-grid JAX scan vs sequential simulation on a
                         7-day carbon trace at S in {10, 120, 1000} cases
                         (core/engine_jax.py)
  ensemble_sweep         chunked resumable scan + carbon ensembles: S x E
                         scenarios/sec, chunked-vs-monolithic wasted-work
                         ratio on a mixed-finish S=1000 batch, jit-recompile
                         count across repeated sweeps
  optimize_sweep         schedule-optimizer objective throughput: one jitted
                         population step (256+ candidates/call) vs the NumPy
                         loop backend, plus end-to-end Campaign.optimize
                         (core/optimize.py)
  fleet_sweep            grouped-lane fleet engine under a site cap: M x S
                         scenarios/sec, grouped-lane vs python-loop-over-
                         campaigns speedup at M=8 S=500, oracle agreement,
                         jit-recompile count across varying fleet widths
                         (core/fleet.py + the coupled chunk kernels)
  scaleout_sweep         device fan-out + precision policy: scenarios/sec
                         vs device count (1 up to every device jax sees) at
                         S in {1e3,1e4,1e5}, fp64 vs mixed, all in this
                         process; also writes BENCH_scaleout.json
  recurrence_sweep       recurrence as a cache hit: cold vs warm
                         compile+sweep end-to-end via the disk plan cache
                         (in-memory memo cleared between the two; bar >=5x),
                         delta_sweep slot-work ratio at S=1000 for K in
                         {1,10,100} changed schedules; writes
                         BENCH_recurrence.json
  calibration_sweep      measured-run calibration: fit wall-time and
                         recovered-parameter error at U in {1e3, 1e4}
                         synthetic logged units (jax Adam vs the numpy FD
                         fallback), multi-zone (S, zone) batched sweep vs a
                         per-zone python loop; writes BENCH_calibration.json
                         for the CI artifact trail (core/calibrate.py +
                         core/data.py)
  serving_sweep          request-level scheduler: batched window scheduling
                         + execution throughput at 20k requests across the
                         four load shapes, CO2 saved vs carbon-blind FIFO,
                         vectorized-FIFO vs per-request python loop speedup,
                         jit-shape count (core/serve.py)
  oem_case_studies       §3 case-study table (measured vs simulated vs paper)
  campaign_projection    CARINA applied to a TPU training campaign (dry-run
                         StepCost -> kWh/CO2e for a real recurring retrain)
  roofline_table         §Roofline terms per (arch x shape) from the dry-run
  kernel_micro           CPU micro-timings of the XLA twin paths
"""
from __future__ import annotations

import dataclasses as _dataclasses
import glob
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _t(fn, n=5, warmup=2):
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e6


def emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


# ---------------------------------------------------------------------------
def fig1_policy_frontier():
    from repro.core import policy_frontier
    from repro.core.workload import OEM_CASE_1

    t0 = time.perf_counter()
    res = policy_frontier(OEM_CASE_1)
    us = (time.perf_counter() - t0) * 1e6
    for r in res:
        emit(f"fig1/{r.policy}", us / len(res),
             f"dT={r.runtime_delta_pct:+.2f}%_dE={r.energy_delta_pct:+.2f}%")
    boosted = next(r for r in res if "boosted" in r.policy)
    emit("fig1/paper_claim_boosted", 0.0,
         f"paper(-9%,+7%)_ours({boosted.energy_delta_pct:+.1f}%,"
         f"{boosted.runtime_delta_pct:+.1f}%)")


def frontier_sweep():
    """Vectorized sweep engine vs sequential simulate_campaign on a
    120-schedule candidate set (acceptance bar: >=10x on >=100 schedules)."""
    from repro.core import (MachineProfile, SweepCase, calibrate_workload,
                            constant_schedule, hourly_schedule,
                            simulate_campaign, sweep)
    from repro.core.workload import OEM_CASE_1

    wl, m = calibrate_workload(OEM_CASE_1, MachineProfile())
    scheds = ([constant_schedule(0.10 + 0.90 * i / 59) for i in range(60)]
              + [hourly_schedule(f"hourly_{i}",
                                 [0.2 + 0.8 * ((3 * i + h) % 24) / 23
                                  for h in range(24)]) for i in range(60)])
    cases = [SweepCase(s, wl, m) for s in scheds]
    sweep(cases[:2])                      # warm engine caches
    simulate_campaign(wl, scheds[0], m)

    t0 = time.perf_counter()
    seq = [simulate_campaign(wl, s, m) for s in scheds]
    t_seq = time.perf_counter() - t0
    t0 = time.perf_counter()
    vec = sweep(cases)
    t_vec = time.perf_counter() - t0
    err = max(abs(a.energy_kwh / b.energy_kwh - 1) for a, b in zip(vec, seq))
    emit("sweep/sequential_120", t_seq * 1e6 / len(scheds),
         f"total_ms={t_seq * 1e3:.1f}")
    emit("sweep/vectorized_120", t_vec * 1e6 / len(scheds),
         f"total_ms={t_vec * 1e3:.1f}_speedup={t_seq / t_vec:.1f}x_"
         f"maxerr={err:.1e}")


def _week_trace():
    """The 7-day synthetic carbon trace shared by trace_sweep and
    optimize_sweep: diurnal swing + weekday drift + deterministic noise
    around the DTE grid factor."""
    from repro.core import DTE_FACTOR, TraceSignal

    rng = np.random.RandomState(7)
    h = np.arange(168)
    return TraceSignal(tuple(
        DTE_FACTOR * (1.0 + 0.30 * np.sin(2 * np.pi * h / 24.0)
                      + 0.08 * np.sin(2 * np.pi * h / 168.0)
                      + 0.05 * rng.randn(168))), name="week")


def trace_sweep():
    """Trace-grid scan engine (jitted jax.lax.scan over a 7-day carbon
    trace) vs sequential simulate_campaign at S in {10, 120, 1000} cases
    (acceptance bar: >=10x at S=1000, or document the measured ratio)."""
    from repro.core import (MachineProfile, SweepCase, calibrate_workload,
                            deadline_schedule, hourly_schedule,
                            simulate_campaign)
    from repro.core.engine_jax import trace_sweep as run_trace
    from repro.core.workload import OEM_CASE_1

    wl, m = calibrate_workload(OEM_CASE_1, MachineProfile())
    trace = _week_trace()

    def cases_for(S):
        scheds = [hourly_schedule(f"hourly_{i}",
                                  [0.25 + 0.75 * ((5 * i + hh) % 24) / 23
                                   for hh in range(24)]) for i in range(S)]
        return [SweepCase(s, wl, m, carbon=trace) for s in scheds]

    backend = "jax"
    for S in (10, 120, 1000):
        cases = cases_for(S)
        run_trace(cases, backend=backend)     # warm tables + jit cache
        t0 = time.perf_counter()
        vec = run_trace(cases, backend=backend)
        t_vec = time.perf_counter() - t0
        n_seq = min(S, 120)                   # sequential cost extrapolates
        t0 = time.perf_counter()
        seq = [simulate_campaign(c.workload, c.schedule, c.machine,
                                 carbon=trace) for c in cases[:n_seq]]
        t_seq = (time.perf_counter() - t0) * (S / n_seq)
        err = max(abs(a.co2_kg / b.co2_kg - 1)
                  for a, b in zip(vec[:n_seq], seq))
        emit(f"trace_sweep/{backend}_S{S}", t_vec * 1e6 / S,
             f"total_ms={t_vec * 1e3:.1f}_seq_ms={t_seq * 1e3:.1f}_"
             f"speedup={t_seq / t_vec:.1f}x_maxerr={err:.1e}")

    # a progress-aware fleet (deadline pace-keepers): the case family the
    # periodic engine cannot represent at all
    dls = [SweepCase(deadline_schedule(180.0 + 2.0 * i), wl, m, carbon=trace)
           for i in range(60)]
    run_trace(dls, backend=backend)
    t0 = time.perf_counter()
    run_trace(dls, backend=backend)
    t_vec = time.perf_counter() - t0
    t0 = time.perf_counter()
    for c in dls[:12]:
        simulate_campaign(c.workload, c.schedule, c.machine, carbon=trace,
                          deadline_h=c.deadline_h)
    t_seq = (time.perf_counter() - t0) * (len(dls) / 12)
    emit(f"trace_sweep/{backend}_deadline_60", t_vec * 1e6 / len(dls),
         f"total_ms={t_vec * 1e3:.1f}_seq_ms={t_seq * 1e3:.1f}_"
         f"speedup={t_seq / t_vec:.1f}x")


def ensemble_sweep():
    """Chunked trace engine + carbon-ensemble benchmarks (acceptance:
    the straggler re-scan is gone — >=3x reduction in scanned slot-work
    on a mixed-finish S=1000 batch — and repeated sweeps reuse the
    jitted chunk kernel instead of recompiling per shape).

    Rows: S x E ensemble scenario throughput; chunked-vs-monolithic
    slot-work ratio; jit-recompile count across repeated sweeps of
    varying batch sizes (bucketed padding keeps the signature set
    small)."""
    from repro.core import (MachineProfile, SweepCase, calibrate_workload,
                            hourly_schedule, trace_windows)
    from repro.core.engine_jax import (reset_scan_stats, scan_stats,
                                       trace_sweep as run_trace)
    from repro.core.workload import OEM_CASE_1

    backend = "jax"
    wl, m = calibrate_workload(OEM_CASE_1, MachineProfile())

    # --- S x E ensemble throughput -------------------------------------
    rng = np.random.RandomState(7)
    h = np.arange(24 * 7 * 7)
    series = 0.448 * (1.0 + 0.30 * np.sin(2 * np.pi * h / 24.0)
                      + 0.08 * np.sin(2 * np.pi * h / (24 * 7))
                      + 0.05 * rng.randn(len(h)))
    for S, E in ((32, 32), (120, 16)):
        ens = trace_windows(series, window_h=24 * 14, stride_h=24)
        assert len(ens) >= E, len(ens)
        ens = type(ens)(ens.members[:E], name=f"ens{E}")
        scheds = [hourly_schedule(f"e{i}",
                                  [0.3 + 0.65 * ((3 * i + hh) % 24) / 23
                                   for hh in range(24)]) for i in range(S)]
        cases = [SweepCase(s, wl, m, carbon=ens) for s in scheds]
        run_trace(cases, backend=backend)     # warm tables + jit cache
        t0 = time.perf_counter()
        res = run_trace(cases, backend=backend)
        dt = time.perf_counter() - t0
        emit(f"ensemble_sweep/{backend}_S{S}xE{E}", dt * 1e6 / (S * E),
             f"total_ms={dt * 1e3:.1f}_scenarios_per_s={S * E / dt:.0f}_"
             f"co2_std={res[0].co2_ensemble.std:.3f}")

    # --- chunked vs monolithic wasted work, mixed-finish S=1000 --------
    S = 1000
    scheds = [hourly_schedule(f"fast{i}",
                              [0.75 + 0.2 * ((i + hh) % 24) / 23
                               for hh in range(24)]) for i in range(S - 20)]
    scheds += [hourly_schedule(f"slow{i}", [0.12] * 24) for i in range(20)]
    cases = [SweepCase(s, wl, m) for s in scheds]
    for mode in ("chunked", "monolithic"):
        run_trace(cases, backend=backend, mode=mode)   # warm jit + plans
        reset_scan_stats()
        t0 = time.perf_counter()
        run_trace(cases, backend=backend, mode=mode)
        dt = time.perf_counter() - t0
        st = scan_stats()
        if mode == "chunked":
            work_chunked, t_chunked = st.slot_work, dt
        else:
            emit(f"ensemble_sweep/{backend}_straggler_S{S}",
                 t_chunked * 1e6 / S,
                 f"chunked_ms={t_chunked * 1e3:.0f}_mono_ms={dt * 1e3:.0f}_"
                 f"slot_work_ratio={st.slot_work / work_chunked:.1f}x_"
                 f"(bar>=3x)")

    # --- jit-recompile count across repeated, jittered sweeps ----------
    reset_scan_stats()
    for S in (64, 63, 61, 57, 49):            # same pow2 bucket: one shape
        sub = [SweepCase(s, wl, m) for s in scheds[:S]]
        run_trace(sub, backend=backend)
    st = scan_stats()
    emit(f"ensemble_sweep/{backend}_recompiles", 0.0,
         f"sweeps=5_jit_shapes={st.jit_compiles}_chunks={st.chunks}_"
         "(bucketed_padding_keeps_shapes_constant)")


def optimize_sweep():
    """Schedule-optimizer throughput (acceptance bar: a single jitted
    population step evaluates >=256 candidates; report candidates/sec for
    the jit and NumPy backends, and an end-to-end Campaign.optimize)."""
    from repro.core import (Campaign, MachineProfile, SweepCase,
                            calibrate_workload, parametric_schedule)
    from repro.core.engine_jax import TraceObjective
    from repro.core.workload import OEM_CASE_1

    wl, m = calibrate_workload(OEM_CASE_1, MachineProfile())
    case = SweepCase(parametric_schedule(24), wl, m, deadline_h=220.0)
    rng = np.random.RandomState(0)
    for N in (256, 1024):
        U = 0.05 + 0.90 * rng.rand(N, 24)
        for backend in ("jax", "numpy"):
            to = TraceObjective(case, horizon_h=280.0, backend=backend)
            to.evaluate_batch(U)          # warm tables (+ jit cache)
            us = _t(lambda: to.evaluate_batch(U), n=3, warmup=1)
            emit(f"optimize_sweep/{backend}_pop{N}", us / N,
                 f"cands_per_s={N / (us / 1e6):.0f}_"
                 f"step_ms={us / 1e3:.1f}_slots={len(to.lens)}")

    trace = _week_trace()
    c = Campaign(OEM_CASE_1)
    t0 = time.perf_counter()
    res = c.optimize("energy", deadline_h=214.0, carbon_trace=trace,
                     candidates=256, iterations=30, steps=400,
                     method="auto")
    dt = time.perf_counter() - t0
    emit("optimize_sweep/campaign_end_to_end", dt * 1e6,
         f"method={res.method}_evals={res.evaluations}_"
         f"energy_kwh={res.result.energy_kwh:.2f}_"
         f"runtime_h={res.result.runtime_h:.1f}")


def fleet_sweep():
    """Grouped-lane fleet engine benchmarks (acceptance: the coupled
    grouped-lane sweep is >=10x faster than the python per-slot loop
    over campaigns at M=8, S=500, while agreeing with that oracle to
    <0.5%; bucketed padding keeps the coupled kernel's jit-shape count
    small across varying fleet widths)."""
    import dataclasses

    from repro.core import (MachineProfile, Site, SweepCase,
                            calibrate_workload, hourly_schedule)
    from repro.core.engine_jax import reset_scan_stats, scan_stats
    from repro.core.fleet import fleet_sweep as run_fleet, simulate_fleet
    from repro.core.workload import OEM_CASE_1

    backend = "jax"
    wl, m = calibrate_workload(OEM_CASE_1, MachineProfile())
    site = Site(power_cap_kw=2.0, office_kw=0.12)

    M, S = 8, 500
    wls = [dataclasses.replace(wl, name=f"wl{j}",
                               n_scenarios=int(wl.n_scenarios
                                               * (0.5 + 0.12 * j)))
           for j in range(M)]

    def group(i, width=M):
        s = hourly_schedule(f"f{i}", [0.35 + 0.6 * ((3 * i + h) % 24) / 23
                                      for h in range(24)])
        return [SweepCase(s, w, m, site.bands, None, 9.0,
                          label=f"f{i}/{w.name}") for w in wls[:width]]

    groups = [group(i) for i in range(S)]
    run_fleet(groups[:8], site, backend=backend)    # warm tables + jit
    reset_scan_stats()
    t0 = time.perf_counter()
    res = run_fleet(groups, site, backend=backend)
    dt = time.perf_counter() - t0
    # the python loop over campaigns: the sequential per-slot oracle,
    # timed on a subset and extrapolated (like the trace_sweep bench)
    n_seq = 3
    t0 = time.perf_counter()
    orcs = [simulate_fleet(grp, site) for grp in groups[:n_seq]]
    t_seq = (time.perf_counter() - t0) * (S / n_seq)
    err = max(abs(a.runtime_h / b.runtime_h - 1)
              for fr, orc in zip(res[:n_seq], orcs)
              for a, b in zip(fr.campaigns, orc.campaigns))
    emit(f"fleet_sweep/{backend}_M{M}xS{S}", dt * 1e6 / (M * S),
         f"total_ms={dt * 1e3:.0f}_campaigns_per_s={M * S / dt:.0f}_"
         f"pyloop_ms={t_seq * 1e3:.0f}_speedup={t_seq / dt:.1f}x_"
         f"(bar>=10x)_maxerr={err:.1e}_(bar<0.5%)_"
         f"peak_kw={res[0].site.peak_kw:.2f}")

    # jit recompiles across varying fleet widths: pow2 bucketing of both
    # the lane and the group axes keeps the signature set small
    reset_scan_stats()
    for width in (2, 3, 5, 8):
        sub = [group(i, width) for i in range(16)]
        run_fleet(sub, site, backend=backend)
    st = scan_stats()
    emit(f"fleet_sweep/{backend}_recompiles_varyM", 0.0,
         f"fleet_widths=4_jit_shapes={st.jit_compiles}_chunks={st.chunks}_"
         f"grouped_lanes={st.grouped_lanes}")


def serving_sweep():
    """Request-level serving scheduler benchmarks (acceptance: the
    vectorized window scheduler is >=10x faster than the per-request
    python FIFO loop it replaces at 10k+ requests; report scheduled+
    executed requests/sec per load shape, CO2 saved vs the carbon-blind
    FIFO at equal SLO attainment, and the jit-shape count across all
    four shapes — one window signature, no per-shape recompiles)."""
    from repro.core import (DTE_FACTOR, HourlySignal, LOAD_SHAPES,
                            MIDWEST_HOURLY, ServingSession, arrival_stream,
                            serve_window)
    from repro.core.engine_jax import reset_scan_stats, scan_stats
    from repro.core.serve import (DEFAULT_TIERS, FifoServingPolicy,
                                  _fifo_assign_loop)

    n = 20_000
    carbon = HourlySignal(tuple(float(v) * DTE_FACTOR
                                for v in MIDWEST_HOURLY))
    sess = ServingSession(carbon=carbon, service_rate=n * 3e-5,
                          start_hour=6.0)
    w = sess.window()
    batches = {s: arrival_stream(n, shape=s, seed=42, slack_h=(4.0, 12.0),
                                 camel_fracs=(0.2, 0.55),
                                 tier_mix=(0.8, 0.15, 0.05))
               for s in LOAD_SHAPES}
    serve_window(batches["random"], w, policy="greedy")  # warm tables + jit
    reset_scan_stats()
    for shape, batch in batches.items():
        t0 = time.perf_counter()
        fifo = serve_window(batch, w, policy="fifo")
        greedy = serve_window(batch, w, policy="greedy")
        dt = time.perf_counter() - t0
        saved = (1.0 - greedy.co2_kg / fifo.co2_kg) * 100.0
        emit(f"serving_sweep/{shape}_n{n}", dt * 1e6 / (2 * n),
             f"req_per_s={2 * n / dt:.0f}_co2_saved_vs_fifo={saved:.1f}%_"
             f"slo_miss={greedy.slo_miss_rate:.4f}_"
             f"admitted={greedy.n_admitted}/{n}")
    st = scan_stats()
    emit("serving_sweep/recompiles_4shapes", 0.0,
         f"windows=8_jit_shapes={st.jit_compiles}_chunks={st.chunks}_"
         f"requests_seen={st.requests_seen}")

    # the vectorized FIFO vs the per-request python loop it replaces
    batch = batches["random"]
    pol = FifoServingPolicy()
    us_vec = _t(lambda: pol.assign(batch, w, DEFAULT_TIERS), n=3, warmup=1)
    us_loop = _t(lambda: _fifo_assign_loop(batch, w, DEFAULT_TIERS),
                 n=3, warmup=1)
    emit(f"serving_sweep/fifo_vectorized_n{n}", us_vec / n,
         f"total_ms={us_vec / 1e3:.1f}_pyloop_ms={us_loop / 1e3:.1f}_"
         f"speedup={us_loop / us_vec:.1f}x_(bar>=10x)")


def _scaleout_cell(S: int, devices: int, precision: str,
                   reps: int) -> dict:
    """One (S, devices, precision) cell of `scaleout_sweep`."""
    import dataclasses

    from repro.core import (MachineProfile, SweepCase, calibrate_workload,
                            hourly_schedule)
    from repro.core.engine_jax import (compile_plan, execute_plan,
                                       reset_scan_stats, scan_stats)
    from repro.core.workload import OEM_CASE_1

    wl, m = calibrate_workload(OEM_CASE_1, MachineProfile())
    # trim the campaign to ~2 days so one execute_plan is seconds, not
    # minutes, at S=1e5; the scan cost model (lanes x slots x buckets)
    # is unchanged
    wl = dataclasses.replace(wl, n_scenarios=300_000)
    trace = _week_trace()
    scheds = [hourly_schedule(f"sc{i}", [0.35 + 0.6 * ((3 * i + h) % 24) / 23
                                         for h in range(24)])
              for i in range(min(S, 64))]
    cases = [SweepCase(scheds[i % len(scheds)], wl, m, carbon=trace)
             for i in range(S)]
    plan = compile_plan(cases, progress_buckets=8, precision=precision)
    execute_plan(plan, devices=devices)           # warm the jit cache
    reset_scan_stats()
    t0 = time.perf_counter()
    for _ in range(reps):
        execute_plan(plan, devices=devices)
    dt = (time.perf_counter() - t0) / reps
    st = scan_stats()
    return {"S": S, "devices": devices, "precision": precision,
            "dt_s": dt, "scen_per_s": S / dt,
            "devices_used": st.devices_used,
            "precision_mode": st.precision_mode}


def scaleout_sweep():
    """Device fan-out + precision-policy scaling of the trace-scan engine
    (kWh/CO2 of mixed within 1e-6 of fp64 is pinned separately by
    tests/test_scaleout.py).

    Every cell runs in this process, on the devices jax reports: device
    counts go from 1 up to all of them in powers of two (one process per
    chip — a child could not reach a chip this process holds).  Every
    row names the platform and device kind it ran on.  Besides the CSV
    rows, writes machine-readable ``BENCH_scaleout.json`` (path
    override: ``CARINA_BENCH_JSON``)."""
    n_avail = len(jax.devices())
    counts = [d for d in (1, 2, 4, 8, 16) if d <= n_avail]
    s_values = (1_000, 10_000, 100_000)
    if os.environ.get("CARINA_BENCH_FAST"):
        s_values = (1_000, 10_000)
    grid = []
    for precision in ("fp64", "mixed"):
        for S in s_values:
            dev_counts = sorted({1, counts[-1]})
            if S == s_values[-1] and precision == "fp64":
                dev_counts = counts
            for devices in dev_counts:
                grid.append((precision, S, devices))
    rows = []
    for precision, S, devices in grid:
        rec = _scaleout_cell(S, devices, precision,
                             reps=2 if S < 100_000 else 1)
        rows.append(rec)
        emit(f"scaleout_sweep/{precision}_S{S}_d{devices}",
             rec["dt_s"] * 1e6 / S,
             f"scen_per_s={rec['scen_per_s']:.0f}_"
             f"total_ms={rec['dt_s'] * 1e3:.0f}_"
             f"devices_used={rec['devices_used']}")

    def rate(precision, S, devices):
        for r in rows:
            if (r["precision"], r["S"], r["devices"]) == (precision, S, devices):
                return r["scen_per_s"]
        return None

    speedups = {}
    for S in s_values:
        r1, rn = rate("fp64", S, 1), rate("fp64", S, counts[-1])
        if r1 and rn and counts[-1] > 1:
            speedups[f"fp64_S{S}_d{counts[-1]}_vs_d1"] = rn / r1
        rf, rm = rate("fp64", S, 1), rate("mixed", S, 1)
        if rf and rm:
            speedups[f"mixed_vs_fp64_S{S}_d1"] = rm / rf
    dev = jax.devices()[0]
    for key, val in sorted(speedups.items()):
        emit(f"scaleout_sweep/speedup_{key}", 0.0,
             f"x{val:.2f}_platform={dev.platform}")
    out_path = os.environ.get("CARINA_BENCH_JSON", "BENCH_scaleout.json")
    with open(out_path, "w") as f:
        json.dump({"bench": "scaleout_sweep",
                   "device": {"platform": dev.platform,
                              "kind": dev.device_kind, "count": n_avail},
                   "rows": rows, "speedups": speedups}, f, indent=2)
    emit("scaleout_sweep/json", 0.0, f"wrote_{out_path}_rows={len(rows)}")


def oem_case_studies():
    from repro.core import policy_frontier
    from repro.core.workload import OEM_CASE_1, OEM_CASE_2

    paper = {"oem-case-1": (48.67, 21.8, 44.3), "oem-case-2": (74.16, 33.2, 67.5)}
    for case in (OEM_CASE_1, OEM_CASE_2):
        t0 = time.perf_counter()
        res = {r.policy: r for r in policy_frontier(case)}
        us = (time.perf_counter() - t0) * 1e6
        b = res["baseline"]
        bo = res["peak_aware_boosted_offhours"]
        pk, pc, pb = paper[case.name]
        emit(f"oem/{case.name}/baseline", us / 2,
             f"kwh={b.energy_kwh:.2f}(paper {pk})_co2={b.co2_kg:.1f}(paper {pc})")
        emit(f"oem/{case.name}/boosted", us / 2,
             f"kwh={bo.energy_kwh:.2f}(paper~{pb})_co2={bo.co2_kg:.1f}")


def campaign_projection():
    """CARINA roofline-mode energy for a recurring retraining campaign on the
    production pod, per arch (uses dry-run step costs when available)."""
    from repro.core import EnergyModel, StepCost

    em = EnergyModel()
    files = sorted(glob.glob(os.path.join(
        ROOT, "experiments/dryrun/*.train_4k.pod16x16.json")))
    steps = 1000  # one scheduled retrain wave
    for f in files:
        rec = json.load(open(f))
        if rec.get("status") != "ok":
            continue
        pc = rec["per_chip"]
        cost = StepCost(pc["hlo_flops"], pc["hlo_bytes"],
                        pc["collective_bytes"], chips=rec["chips"])
        t0 = time.perf_counter()
        j = em.step_energy_j(cost)
        us = (time.perf_counter() - t0) * 1e6
        kwh = j * steps / 3.6e6
        co2 = kwh * 0.448
        emit(f"campaign/{rec['arch']}", us,
             f"1000steps_kwh={kwh:.1f}_co2kg={co2:.1f}_"
             f"step={cost.step_seconds():.3f}s")


def roofline_table():
    files = sorted(glob.glob(os.path.join(ROOT, "experiments/dryrun/*.pod16x16.json")))
    for f in files:
        rec = json.load(open(f))
        if rec.get("status") == "skipped":
            emit(f"roofline/{rec['arch']}/{rec['shape']}", 0.0, "skipped")
            continue
        if rec.get("status") != "ok":
            emit(f"roofline/{rec['arch']}/{rec['shape']}", 0.0,
                 f"status={rec.get('status')}")
            continue
        r = rec["roofline"]
        emit(f"roofline/{rec['arch']}/{rec['shape']}",
             r["step_seconds"] * 1e6,
             f"bottleneck={r['bottleneck']}_compute={r['compute_s']:.3f}s_"
             f"memory={r['memory_s']:.3f}s_coll={r['collective_s']:.3f}s_"
             f"useful={r['useful_flops_ratio']:.2f}")


def kernel_micro():
    from repro.models import layers as L
    from repro.models.loss import blocked_cross_entropy, cross_entropy
    from repro.models import ssm as SSM

    key = jax.random.PRNGKey(0)
    b, s, h, hkv, d = 1, 1024, 8, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)

    dense = jax.jit(lambda q, k, v: L.attention(q, k, v, causal=True,
                                                chunk_q=10_000))
    chunked = jax.jit(lambda q, k, v: L.attention(q, k, v, causal=True,
                                                  chunk_q=256))
    us_d = _t(lambda: jax.block_until_ready(dense(q, k, v)))
    us_c = _t(lambda: jax.block_until_ready(chunked(q, k, v)))
    emit("kernel/attention_dense_1k", us_d, "xla_cpu")
    emit("kernel/attention_chunked_1k", us_c, f"ratio={us_c/us_d:.2f}")

    t, dd, vv = 2048, 256, 32000
    x = jax.random.normal(ks[0], (t, dd), jnp.float32) * 0.5
    emb = jax.random.normal(ks[1], (vv, dd), jnp.float32) * 0.5
    lab = jax.random.randint(ks[2], (t,), 0, vv)
    f_dense = jax.jit(lambda x, e: cross_entropy(
        jnp.einsum("td,vd->tv", x, e), lab)[0])
    f_blk = jax.jit(lambda x, e: blocked_cross_entropy(x, e, lab, block=4096)[0])
    us1 = _t(lambda: jax.block_until_ready(f_dense(x, emb)))
    us2 = _t(lambda: jax.block_until_ready(f_blk(x, emb)))
    emit("kernel/xent_dense_32k_vocab", us1, "materializes_TxV")
    emit("kernel/xent_blocked_32k_vocab", us2,
         f"ratio={us2/us1:.2f}_peak_mem_1/{vv//4096}x")

    a = jax.random.uniform(ks[0], (2, 2048, 512), jnp.float32, 0.5, 1.0)
    bb = jax.random.normal(ks[1], (2, 2048, 512)) * 0.1
    f_scan = jax.jit(lambda a, b: SSM.chunked_diag_scan(a, b, chunk=64)[0])
    us3 = _t(lambda: jax.block_until_ready(f_scan(a, bb)))
    emit("kernel/ssm_chunked_scan_2k", us3, "chunk=64")


def mpc_sweep():
    """Receding-horizon MPC loop cost (ISSUE 8): re-plan latency and
    solve-time amortization vs the control interval K, plus the
    zero-recompute ratio — slots carried across re-plans over total
    slots executed (1.0 = every re-plan resumed, nothing re-scanned)."""
    import dataclasses as _dc

    from repro.core import MachineProfile, SweepCase, calibrate_workload
    from repro.core.engine_jax import reset_scan_stats, scan_stats
    from repro.core.mpc import MPCSession
    from repro.core.policy import constant_schedule
    from repro.core.signal import as_trace
    from repro.core.workload import OEM_CASE_1

    rng = np.random.RandomState(17)
    h = np.arange(24 * 21, dtype=float)
    day = h // 24
    vals = (0.40 + (0.18 + 0.10 * np.sin(day * 2.1))
            * np.sin((h % 24) * 2 * np.pi / 24 + 0.8 * np.sin(day * 0.9))
            + 0.02 * rng.randn(h.size)).clip(0.05)
    truth = as_trace(tuple(vals), name="bench-truth")
    wl, m = calibrate_workload(OEM_CASE_1, MachineProfile())
    wl = _dc.replace(wl, n_scenarios=wl.n_scenarios // 8)
    case = SweepCase(constant_schedule(1.0), wl, m, carbon=truth,
                     start_hour=9.0, deadline_h=96.0)
    solver = dict(method="cem", candidates=24, iterations=4, seed=0)
    for K in (None, 24.0, 8.0, 4.0):
        reset_scan_stats()
        t0 = time.perf_counter()
        out = MPCSession(case, truth, constraints={"runtime_h": 96.0},
                         forecast="day_ahead", replan_every_h=K,
                         solver=solver).run()
        dt = time.perf_counter() - t0
        stats = scan_stats(reset=True)
        replan_us = (sum(r.solve_s for r in out.replans[1:]) * 1e6
                     / max(out.n_replans, 1))
        emit(f"mpc_sweep/K_{'inf' if K is None else int(K)}", dt * 1e6,
             f"replans={out.n_replans}_replan_ms={replan_us / 1e3:.0f}_"
             f"solve_frac={out.solve_s / dt:.2f}_"
             f"slots_reused={stats.slots_reused}_"
             f"co2_kg={out.realized_co2_kg:.3f}")


@_dataclasses.dataclass(frozen=True)
class _ProbeHeavySchedule:
    """A progress/elapsed-aware schedule with only a plain `decide()`
    (no `decide_grid`), so compilation pays the full probe + per-bucket
    table lowering — the recurrence bench's stand-in for the
    user-written python schedules whose compile cost the plan cache
    amortizes.  A frozen dataclass, so it fingerprints by value."""
    phase: float
    depth: float
    batch_size: int = 50

    @property
    def name(self) -> str:
        return f"probe-heavy[{self.phase:.3f}]"

    def decide(self, ctx):
        from repro.core import Decision
        u = (1.0 - self.depth * ctx.progress
             + 0.25 * np.sin(ctx.hour_of_day * 2 * np.pi / 24 + self.phase))
        return Decision(float(np.clip(u, 0.3, 1.0)), self.batch_size)


def _recurrence_cycle(S: int, cache_dir: str) -> dict:
    """One full refresh cycle of `recurrence_sweep` (compile + execute +
    summarize) against a shared on-disk plan cache."""
    import dataclasses

    from repro.core import (MachineProfile, SweepCase, calibrate_workload,
                            trace_sweep)
    from repro.core.engine_jax import reset_scan_stats, scan_stats
    from repro.core.workload import OEM_CASE_1

    wl, m = calibrate_workload(OEM_CASE_1, MachineProfile())
    wl = dataclasses.replace(wl, n_scenarios=400.0)
    trace = _week_trace()
    cases = [SweepCase(_ProbeHeavySchedule(phase=0.37 * i, depth=0.5
                                           + 0.4 * i / S),
                       wl, m, carbon=trace, label=f"c{i}")
             for i in range(S)]
    reset_scan_stats()
    t0 = time.perf_counter()
    res = trace_sweep(cases, backend="numpy", cache_dir=cache_dir)
    dt = time.perf_counter() - t0
    st = scan_stats()
    return {"S": S, "dt_s": dt,
            "plan_misses": st.plan_misses, "disk_hits": st.disk_hits,
            "co2_sum": sum(r.co2_kg for r in res)}


def recurrence_sweep():
    """Recurrence as a cache hit (ISSUE 9): cold vs warm compile+sweep
    end-to-end (acceptance: >=5x — the warm cycle reads compiled tables
    off disk instead of re-probing S python schedules; the in-memory
    plan memo is cleared between the two, so only the disk cache
    carries over, as it does into a fresh process), plus the
    `delta_sweep` slot-work ratio at S=1000 for K changed schedules in
    {1, 10, 100} (acceptance at K=1, S=100: <=2% — pinned by
    tests/test_plancache.py; here the ratio is reported at production
    batch width).  Writes ``BENCH_recurrence.json`` (path override:
    ``CARINA_BENCH_RECURRENCE_JSON``)."""
    import dataclasses
    import shutil
    import tempfile

    from repro.core import (MachineProfile, SweepCase, calibrate_workload,
                            constant_schedule)
    from repro.core.engine_jax import (clear_plan_cache, compile_plan,
                                       delta_sweep, execute_plan,
                                       reset_scan_stats, scan_stats,
                                       summarize_plan)
    from repro.core.workload import OEM_CASE_1

    fast = bool(os.environ.get("CARINA_BENCH_FAST"))
    S_cycle = 24 if fast else 64
    cache_dir = tempfile.mkdtemp(prefix="carina-plancache-")
    runs = {}
    try:
        for label in ("cold", "warm"):
            clear_plan_cache()
            runs[label] = _recurrence_cycle(S_cycle, cache_dir)
            emit(f"recurrence_sweep/{label}_S{S_cycle}",
                 runs[label]["dt_s"] * 1e6,
                 f"plan_misses={runs[label]['plan_misses']}_"
                 f"disk_hits={runs[label]['disk_hits']}")
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    speedup = runs["cold"]["dt_s"] / max(runs["warm"]["dt_s"], 1e-9)
    bitwise = runs["cold"]["co2_sum"] == runs["warm"]["co2_sum"]
    emit(f"recurrence_sweep/warm_vs_cold_S{S_cycle}", 0.0,
         f"x{speedup:.1f}_(bar>=5x)_zero_compiles="
         f"{runs['warm']['plan_misses'] == 0}_bitwise={bitwise}")

    # delta-sweep slot-work ratios at production batch width
    S = 200 if fast else 1000
    wl, m = calibrate_workload(OEM_CASE_1, MachineProfile())
    wl = dataclasses.replace(wl, n_scenarios=400.0)
    trace = _week_trace()
    cases = [SweepCase(constant_schedule(0.35 + 0.65 * i / S), wl, m,
                       carbon=trace, label=f"c{i}")
             for i in range(S)]
    plan = compile_plan(cases)
    reset_scan_stats()
    state = execute_plan(plan, backend="numpy")
    base_work = scan_stats().slot_work
    prev = summarize_plan(plan, state)
    ratios = {}
    for K in (1, 10, 100):
        if K > S:
            continue
        deltas = {i: constant_schedule(0.9 - 0.4 * i / S)
                  for i in range(0, S, S // K)[:K]} if K > 1 else \
            {0: constant_schedule(0.9)}
        reset_scan_stats()
        t0 = time.perf_counter()
        delta_sweep(plan, prev, schedules=deltas, backend="numpy")
        dt = time.perf_counter() - t0
        st = scan_stats()
        ratios[f"K{K}"] = st.slot_work / max(base_work, 1)
        emit(f"recurrence_sweep/delta_S{S}_K{K}", dt * 1e6,
             f"slot_work_ratio={ratios[f'K{K}']:.4f}_"
             f"lanes_recomputed={st.lanes_recomputed}_"
             f"lanes_spliced={st.lanes_spliced}")

    out_path = os.environ.get("CARINA_BENCH_RECURRENCE_JSON",
                              "BENCH_recurrence.json")
    with open(out_path, "w") as f:
        json.dump({"bench": "recurrence_sweep", "S_cycle": S_cycle,
                   "cold": runs["cold"], "warm": runs["warm"],
                   "warm_vs_cold_speedup": speedup, "bitwise": bitwise,
                   "delta_S": S, "delta_slot_work_ratios": ratios},
                  f, indent=2)
    emit("recurrence_sweep/json", 0.0, f"wrote_{out_path}")


def calibration_sweep():
    """Measured-run calibration + the zone sweep axis (ISSUE 10): fit
    wall-time and recovered-parameter error at U in {1e3, 1e4}
    synthetic observations (the jax Adam path, plus the numpy
    finite-difference fallback at the small size), and the multi-zone
    (S, zone) batched sweep vs a per-zone python loop over the same
    archive.  Writes ``BENCH_calibration.json`` (path override:
    ``CARINA_BENCH_CALIBRATION_JSON``)."""
    import shutil
    import tempfile

    from repro.core import (Campaign, MachineProfile, constant_schedule,
                            load_carbon_archive, model,
                            write_synthetic_archive)
    from repro.core.calibrate import Observations, fit_calibration
    from repro.core.engine_jax import clear_plan_cache
    from repro.core.workload import OEMWorkload

    fast = bool(os.environ.get("CARINA_BENCH_FAST"))
    truth = {"rate_at_full": 3.4, "gamma": 0.65, "idle_w": 95.0,
             "dyn_w": 260.0, "overhead_w_frac": 0.45}
    rng = np.random.RandomState(0)

    def synth(n):
        """n synthetic operating points at the truth physics + 0.5%
        measurement noise (the U-scaling benches need logs far larger
        than any simulated campaign writes)."""
        u = 0.3 + 0.7 * rng.rand(n)
        batch = rng.choice([8.0, 16.0, 32.0, 64.0], size=n)
        bg = rng.choice([0.02, 0.15, 0.50, 0.65], size=n)
        r = model.rates(u, batch, bg,
                        rate_at_full=truth["rate_at_full"],
                        batch_overhead_s=2.0, idle_w=truth["idle_w"],
                        dyn_w=truth["dyn_w"], alpha=1.7,
                        gamma=truth["gamma"],
                        overhead_w_frac=truth["overhead_w_frac"], xp=np)
        return Observations(
            u=u, batch=batch, background=bg,
            scen_per_s=r.scen_per_s * (1.0 + 0.005 * rng.randn(n)),
            p_avg_w=r.p_avg_w * (1.0 + 0.005 * rng.randn(n)),
            weight=np.full(n, 1.0 / n))

    wl0 = OEMWorkload("bench", 1, rate_at_full=3.0, batch_overhead_s=2.0)
    m0 = MachineProfile()
    sizes = (1000,) if fast else (1000, 10_000)
    steps = 300 if fast else 500
    fits = {}
    for n in sizes:
        obs = synth(n)
        backends = ("jax", "numpy") if n == sizes[0] else ("jax",)
        for backend in backends:
            t0 = time.perf_counter()
            cm = fit_calibration(obs, wl0, m0, steps=steps,
                                 backend=backend)
            dt = time.perf_counter() - t0
            err = max(cm.rel_error(truth).values())
            emit(f"calibration_sweep/fit_U{n}_{backend}", dt * 1e6,
                 f"max_rel_err={err:.4f}_loss={cm.loss:.2e}")
            fits[f"U{n}_{backend}"] = {"dt_s": dt, "max_rel_err": err,
                                       "loss": cm.loss}

    # multi-zone batched sweep vs a per-zone python loop
    n_zones = 4 if fast else 8
    S = 8 if fast else 12
    d = tempfile.mkdtemp(prefix="carina-calib-bench-")
    try:
        arch = load_carbon_archive(write_synthetic_archive(
            os.path.join(d, "bench.csv"),
            zones=tuple(f"Z{i}" for i in range(n_zones)), days=7, seed=2))
        wl = OEMWorkload("zsweep", 40_000, rate_at_full=2.3,
                         batch_overhead_s=2.0)
        scheds = [constant_schedule(0.35 + 0.6 * i / max(S - 1, 1))
                  for i in range(S)]
        c = Campaign(wl)
        clear_plan_cache()
        t0 = time.perf_counter()
        rows = c.sweep(scheds, zones=arch)
        dt_batched = time.perf_counter() - t0
        clear_plan_cache()
        t0 = time.perf_counter()
        loop_rows = []
        for z in arch.zones:
            loop_rows.extend(c.sweep(scheds,
                                     carbon_trace=arch[z].to_trace()))
        dt_loop = time.perf_counter() - t0
    finally:
        shutil.rmtree(d, ignore_errors=True)
    scen = wl.n_scenarios * len(rows)
    bitwise = all(
        (a.runtime_h, a.energy_kwh, a.co2_kg)
        == (b.runtime_h, b.energy_kwh, b.co2_kg)
        for a, b in zip(rows, loop_rows))
    emit(f"calibration_sweep/zones_batched_S{S}_Z{n_zones}",
         dt_batched * 1e6, f"scen_per_s={scen / dt_batched:.0f}")
    emit(f"calibration_sweep/zones_loop_S{S}_Z{n_zones}", dt_loop * 1e6,
         f"scen_per_s={scen / dt_loop:.0f}")
    emit(f"calibration_sweep/zones_batched_vs_loop_S{S}_Z{n_zones}", 0.0,
         f"x{dt_loop / max(dt_batched, 1e-9):.1f}_bitwise={bitwise}")

    out_path = os.environ.get("CARINA_BENCH_CALIBRATION_JSON",
                              "BENCH_calibration.json")
    with open(out_path, "w") as f:
        json.dump({"bench": "calibration_sweep", "fits": fits,
                   "zones": {"S": S, "n_zones": n_zones,
                             "dt_batched_s": dt_batched,
                             "dt_loop_s": dt_loop,
                             "speedup": dt_loop / max(dt_batched, 1e-9),
                             "bitwise": bitwise}},
                  f, indent=2)
    emit("calibration_sweep/json", 0.0, f"wrote_{out_path}")


BENCHES = {
    "fig1_policy_frontier": fig1_policy_frontier,
    "frontier_sweep": frontier_sweep,
    "trace_sweep": trace_sweep,
    "ensemble_sweep": ensemble_sweep,
    "optimize_sweep": optimize_sweep,
    "fleet_sweep": fleet_sweep,
    "serving_sweep": serving_sweep,
    "scaleout_sweep": scaleout_sweep,
    "recurrence_sweep": recurrence_sweep,
    "calibration_sweep": calibration_sweep,
    "mpc_sweep": mpc_sweep,
    "oem_case_studies": oem_case_studies,
    "campaign_projection": campaign_projection,
    "roofline_table": roofline_table,
    "kernel_micro": kernel_micro,
}


def main(argv=None) -> None:
    """Run the named benchmarks (all of them with no arguments)."""
    names = argv if argv else list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        raise SystemExit(f"unknown benchmark(s) {unknown}; "
                         f"choose from {list(BENCHES)}")
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()


if __name__ == "__main__":
    main(sys.argv[1:])
