"""Chunked resumable trace engine + carbon-trace ensemble tests (the
PR-4 multi_layer_refactor acceptance bar):

* the chunked executor matches the monolithic scan to 1e-9 on the
  existing trace-engine case families, across chunk sizes, on both
  backends, with the straggler re-scan gone (slot-work counters);
* `SignalEnsemble` semantics: (E, T) sampling, window slicing, E=1
  parity with the plain trace sweep, per-member parity with individual
  sweeps, carbon-dependent schedules expanded per member;
* robust objectives: mean/CVaR/worst reductions, constant-ensemble
  equivalence with the deterministic optimum, `Campaign.optimize(
  robust="cvar")` over E>=32 members under both jit and NumPy;
* satellites: early stall detection, per-plan signal sampling (grids
  extended, never re-sampled), plan-cache hits on repeated sweeps.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (BASELINE, Campaign, MachineProfile,
                        PEAK_AWARE_BOOSTED, POLICIES, SignalEnsemble,
                        SweepCase, TimeBands, TraceSignal, as_ensemble,
                        calibrate_workload, constant_schedule,
                        deadline_schedule, hourly_schedule,
                        progress_ramp_schedule, sweep, trace_sweep,
                        trace_windows)
from repro.core.engine_jax import (TraceObjective, compile_plan,
                                   execute_plan, reset_scan_stats,
                                   scan_stats, summarize_plan)
from repro.core.optimize import (Objective, optimize_schedule,
                                 reduce_ensemble)
from repro.core.schedule import FunctionSchedule, parametric_schedule
from repro.core.workload import OEM_CASE_1, OEMWorkload


@pytest.fixture(scope="module")
def calibrated():
    return calibrate_workload(OEM_CASE_1, MachineProfile())


def _week_trace(scale: float = 0.448, seed: int = 7) -> TraceSignal:
    rng = np.random.RandomState(seed)
    h = np.arange(168)
    vals = scale * (1.0 + 0.30 * np.sin(2 * np.pi * h / 24.0)
                    + 0.08 * np.sin(2 * np.pi * h / 168.0)
                    + 0.05 * rng.randn(168))
    return TraceSignal(tuple(float(v) for v in vals), name=f"week{seed}")


def _ensemble(E: int = 4, scale: float = 0.448) -> SignalEnsemble:
    return SignalEnsemble(tuple(_week_trace(scale * (1.0 + 0.06 * e),
                                            seed=11 + e)
                                for e in range(E)), name=f"ens{E}")


# ---------------------------------------------------------------------------
# Tentpole: chunked == monolithic, across chunk sizes and backends
# ---------------------------------------------------------------------------
def test_chunked_matches_monolithic_across_case_families(calibrated):
    """Every trace-engine case family — periodic policies, progress
    ramps, deadline pace-keepers, week-long traces, heterogeneous
    machines — produces identical metrics whether the horizon is scanned
    in one monolithic pass or resumable chunks."""
    wl, m = calibrated
    m2 = MachineProfile(idle_w=120.0, dyn_w=300.0, alpha=1.5, gamma=0.5)
    trace = _week_trace()
    cases = ([SweepCase(p, wl, m) for p in POLICIES.values()]
             + [SweepCase(progress_ramp_schedule(0.4, 0.9), wl, m),
                SweepCase(deadline_schedule(200.0), wl, m, carbon=trace),
                SweepCase(BASELINE, wl, m2, carbon=trace, start_hour=17.0)])
    mono = trace_sweep(cases, mode="monolithic")
    chunked = trace_sweep(cases)
    for a, b in zip(mono, chunked):
        assert abs(b.runtime_h / a.runtime_h - 1) < 1e-9, a.policy
        assert abs(b.energy_kwh / a.energy_kwh - 1) < 1e-9, a.policy
        assert abs(b.co2_kg / a.co2_kg - 1) < 1e-9, a.policy


def test_chunked_identical_across_chunk_sizes(calibrated):
    """Chunk boundaries only split the accumulation; they must never
    move it: results are identical for 1-, 3- and 5-day chunks."""
    wl, m = calibrated
    cases = [SweepCase(PEAK_AWARE_BOOSTED, wl, m),
             SweepCase(deadline_schedule(210.0), wl, m,
                       carbon=_week_trace())]
    ref = trace_sweep(cases, chunk_days=4)
    for days in (1, 3, 5):
        res = trace_sweep(cases, chunk_days=days)
        for a, b in zip(ref, res):
            assert abs(b.energy_kwh / a.energy_kwh - 1) < 1e-12, days
            assert abs(b.runtime_h / a.runtime_h - 1) < 1e-12, days
            assert abs(b.co2_kg / a.co2_kg - 1) < 1e-12, days


def test_chunked_numpy_backend_matches_jax(calibrated):
    wl, m = calibrated
    cases = [SweepCase(BASELINE, wl, m),
             SweepCase(progress_ramp_schedule(0.4, 0.9), wl, m)]
    np_res = trace_sweep(cases, backend="numpy")
    jax_res = trace_sweep(cases, backend="jax")
    for a, b in zip(np_res, jax_res):
        assert abs(b.runtime_h / a.runtime_h - 1) < 1e-12, a.policy
        assert abs(b.energy_kwh / a.energy_kwh - 1) < 1e-12, a.policy


def test_straggler_rescan_is_gone(calibrated):
    """A mixed-finish batch: the monolithic engine scans everyone to the
    straggler's horizon (and re-scans on undershoot); the chunked engine
    compacts finished cases out, so its slot-work is a fraction —
    the benchmark bar is >= 3x at S=1000, pinned here at a smaller S."""
    wl, m = calibrated
    scheds = [hourly_schedule(f"fast{i}",
                              [0.8 + 0.15 * ((i + h) % 24) / 23
                               for h in range(24)]) for i in range(40)]
    scheds += [hourly_schedule(f"slow{i}", [0.12] * 24) for i in range(2)]
    cases = [SweepCase(s, wl, m) for s in scheds]
    reset_scan_stats()
    chunked = trace_sweep(cases)
    work_chunked = scan_stats().slot_work
    reset_scan_stats()
    mono = trace_sweep(cases, mode="monolithic")
    work_mono = scan_stats().slot_work
    for a, b in zip(mono, chunked):
        assert abs(b.energy_kwh / a.energy_kwh - 1) < 1e-9
    assert work_mono >= 3 * work_chunked, (work_mono, work_chunked)


def test_compile_execute_summarize_stages_are_public(calibrated):
    """The staged API composes: a plan compiled once can be executed and
    summarized directly, matching trace_sweep."""
    wl, m = calibrated
    cases = [SweepCase(BASELINE, wl, m, carbon=_week_trace())]
    plan = compile_plan(cases)
    state = execute_plan(plan)
    res = summarize_plan(plan, state)[0]
    ref = trace_sweep(cases)[0]
    assert res.co2_kg == pytest.approx(ref.co2_kg, rel=1e-12)
    assert plan.n_lanes == 1 and plan.E == 1


def test_plan_cache_hits_on_repeated_sweeps(calibrated):
    """Re-sweeping the same (value-fingerprintable) cases must not
    re-probe or rebuild tables: the per-case compile cache reports hits —
    including for the default carbon=None configuration."""
    wl, m = calibrated
    for carbon in (_week_trace(), None):
        cases = [SweepCase(PEAK_AWARE_BOOSTED, wl, m, carbon=carbon)]
        trace_sweep(cases)                # populate
        reset_scan_stats()
        trace_sweep(cases)
        st = scan_stats()
        assert st.plan_hits >= 1, carbon
        assert st.plan_misses == 0, carbon


def test_custom_decide_grid_schedule_keeps_exact_per_slot_tables(calibrated):
    """A decide_grid schedule that does NOT declare `periodic_decisions`
    must keep exact chunk-built per-slot tables — the probe lattice alone
    cannot prove hour-of-day periodicity for arbitrary vectorized
    schedules.  ParametricSchedule declares the contract and lowers to
    one day-periodic table."""
    wl, m = calibrated

    class SneakyGrid:
        """Hour-of-day wave until day 3, then throttled — invisible to a
        probe lattice that samples days 0/1/2 and the horizon end."""
        name = "sneaky"
        batch_size = 50

        def _u(self, hod, elapsed):
            u = 0.5 + 0.4 * np.sin(2 * np.pi * np.asarray(hod) / 24.0) ** 2
            # thresholds off the hourly sample grid so slot-start and
            # just-inside-segment sampling see the same decisions
            return np.where((np.asarray(elapsed) > 71.5)
                            & (np.asarray(elapsed) < 999.5), 0.25, u)

        def decide(self, ctx):
            from repro.core.schedule import Decision
            return Decision(float(self._u(ctx.hour_of_day, ctx.elapsed_h)),
                            self.batch_size)

        def decide_grid(self, ctx):
            u = self._u(ctx.hour_of_day, ctx.elapsed_h)
            return u, np.broadcast_to(50.0, np.shape(u))

    sneaky = SneakyGrid()
    plan = compile_plan([SweepCase(sneaky, wl, m)])
    assert not plan.lane_periodic[0]      # chunk-built, exact per slot
    from repro.core import simulate_campaign
    r = trace_sweep([SweepCase(sneaky, wl, m)])[0]
    seq = simulate_campaign(wl, sneaky, m)
    assert abs(r.energy_kwh / seq.energy_kwh - 1) < 1e-9
    assert abs(r.runtime_h / seq.runtime_h - 1) < 1e-9
    # the optimizer's family declares hour-of-day-only decisions and
    # keeps the compact periodic lowering
    plan_p = compile_plan([SweepCase(parametric_schedule(24), wl, m)])
    assert plan_p.lane_periodic[0]


def test_decide_grid_progress_window_keeps_full_bucket_axis(calibrated):
    """A decide_grid schedule whose progress dependence lives entirely
    between the probe's lattice points must still get the full progress
    bucket axis (the old engine's exactness contract for vectorized
    schedules) — within the documented <0.5% bucket-interpolation bar of
    the per-segment oracle."""
    wl, m = calibrated

    class ProgressWindowGrid:
        """Boost only while progress is in (0.72, 0.94) — invisible at
        the probe's progress samples {0, 1/3, 1/2, 2/3, 0.999}."""
        name = "pwindow"
        batch_size = 50

        def _u(self, progress):
            p = np.asarray(progress)
            return np.where((p > 0.72) & (p < 0.94), 0.95, 0.4)

        def decide(self, ctx):
            from repro.core.schedule import Decision
            return Decision(float(self._u(ctx.progress)), self.batch_size)

        def decide_grid(self, ctx):
            u = np.broadcast_to(self._u(ctx.progress),
                                np.broadcast_shapes(
                                    np.shape(ctx.hour_of_day),
                                    np.shape(ctx.progress)))
            return u, np.broadcast_to(50.0, np.shape(u))

    sched = ProgressWindowGrid()
    from repro.core import simulate_campaign
    seq = simulate_campaign(wl, sched, m)
    # bang-bang progress thresholds are the documented worst case for
    # bucket interpolation (docs/API.md carves them out of the 0.5% bar;
    # error ~1/buckets at the discontinuities) — 1% here, vs ~19% when
    # the probe used to flatten the progress axis away entirely
    r = trace_sweep([SweepCase(sched, wl, m)], progress_buckets=64)[0]
    assert abs(r.runtime_h / seq.runtime_h - 1) < 0.01
    assert abs(r.energy_kwh / seq.energy_kwh - 1) < 0.01
    r32 = trace_sweep([SweepCase(sched, wl, m)])[0]
    assert abs(r32.energy_kwh / seq.energy_kwh - 1) < 0.02


def test_chunk_days_validated(calibrated):
    wl, m = calibrated
    cases = [SweepCase(BASELINE, wl, m)]
    with pytest.raises(ValueError, match="chunk_days"):
        trace_sweep(cases, chunk_days=-1)
    with pytest.raises(ValueError, match="mode"):
        trace_sweep(cases, mode="streamed")


# ---------------------------------------------------------------------------
# Satellite: early stall detection
# ---------------------------------------------------------------------------
def test_stall_raises_immediately_not_at_max_days(calibrated):
    """A zero-intensity schedule used to scan all the way to max_days
    before raising; now the first fully-scanned day with no progress
    raises the diagnostic (in both executors)."""
    wl, m = calibrated
    cases = [SweepCase(constant_schedule(0.0), wl, m)]
    for mode in ("chunked", "monolithic"):
        reset_scan_stats()
        with pytest.raises(RuntimeError, match="stalled at zero intensity"):
            trace_sweep(cases, mode=mode)
        # far less work than a 120-day scan of 2880 slots
        assert scan_stats().slot_work < 1500, mode


def test_slow_but_progressing_case_is_not_flagged_as_stalled():
    """A genuinely slow (but nonzero) schedule must finish, not trip the
    stall detector."""
    m = MachineProfile(gamma=0.0)
    wl = OEMWorkload("slow", 86_400, rate_at_full=10.0, batch_overhead_s=0.0)
    r = trace_sweep([SweepCase(constant_schedule(0.02), wl, m,
                               carbon=_week_trace())])[0]
    assert r.runtime_h == pytest.approx(120.0, rel=1e-6)


# ---------------------------------------------------------------------------
# Satellite: signals sampled once per plan, extended incrementally
# ---------------------------------------------------------------------------
def test_signal_grids_sampled_once_per_plan(calibrated):
    """Each (signal, offset) grid slot is sampled exactly once per plan:
    a counting signal sees every absolute hour at most once, even though
    the straggler forces several appended chunks."""
    wl, m = calibrated

    class CountingTrace:
        name = "counting"
        period_h = None

        def __init__(self):
            self.seen = []

        def at(self, hour):
            self.seen.append(float(hour))
            return 0.448

    fast_sig, slow_sig = CountingTrace(), CountingTrace()
    fast = hourly_schedule("fastc", [0.9] * 24)
    slow = hourly_schedule("slowc", [0.15] * 24)
    trace_sweep([SweepCase(fast, wl, m, carbon=fast_sig),
                 SweepCase(slow, wl, m, carbon=slow_sig)])
    for sig in (fast_sig, slow_sig):
        hours = np.asarray(sig.seen)
        uniq = np.unique(np.round(hours, 6))
        assert len(uniq) == len(hours)    # no hour sampled twice
    # the straggler extended further than the fast case, incrementally
    assert len(slow_sig.seen) > len(fast_sig.seen)


# ---------------------------------------------------------------------------
# SignalEnsemble semantics
# ---------------------------------------------------------------------------
def test_signal_ensemble_sampling_and_coercion():
    ens = _ensemble(3)
    assert len(ens) == 3 and ens.period_h is None
    block = ens.sample(np.arange(10.0))
    assert block.shape == (3, 10)
    for e in range(3):
        assert block[e, 4] == ens.member(e).at(4.0)
    # at() is the member mean (sequential-simulator view)
    assert ens.at(4.0) == pytest.approx(block[:, 4].mean())
    # coercions: passthrough, (E, T) array, list of sequences
    assert as_ensemble(ens) is ens
    arr = np.tile(np.linspace(0.3, 0.6, 48), (4, 1))
    e2 = as_ensemble(arr)
    assert len(e2) == 4 and isinstance(e2.member(0), TraceSignal)
    e3 = as_ensemble([[0.4] * 24, [0.5] * 24])
    assert len(e3) == 2
    with pytest.raises(ValueError):
        SignalEnsemble(())
    # a flat hourly series is one trace, not an ensemble of scalars
    with pytest.raises(TypeError, match="carbon_trace"):
        as_ensemble([0.4, 0.5, 0.6])


def test_trace_windows_slices_a_history():
    series = np.arange(24 * 10, dtype=float)
    ens = trace_windows(series, window_h=24 * 7, stride_h=24)
    assert len(ens) == 4                  # offsets 0, 24, 48, 72
    assert ens.member(1).values[0] == 24.0
    assert len(ens.member(0).values) == 24 * 7
    with pytest.raises(ValueError, match="shorter"):
        trace_windows(series[:100], window_h=168)


def test_ensemble_with_one_member_matches_plain_trace(calibrated):
    """E=1 is the degenerate ensemble: identical numbers to sweeping the
    single trace directly, plus the stats fields."""
    wl, m = calibrated
    trace = _week_trace()
    ens = SignalEnsemble((trace,))
    for sched in (BASELINE, deadline_schedule(210.0)):
        plain = sweep([SweepCase(sched, wl, m, carbon=trace)])[0]
        wrapped = sweep([SweepCase(sched, wl, m, carbon=ens)])[0]
        assert abs(wrapped.co2_kg / plain.co2_kg - 1) < 1e-9, sched.name
        assert abs(wrapped.energy_kwh / plain.energy_kwh - 1) < 1e-9
        assert abs(wrapped.runtime_h / plain.runtime_h - 1) < 1e-9
        assert wrapped.co2_ensemble is not None
        assert wrapped.co2_ensemble.n_members == 1
        assert plain.co2_ensemble is None


def test_ensemble_members_match_individual_sweeps(calibrated):
    """The (S, E) scan's per-member CO2 equals E independent sweeps."""
    wl, m = calibrated
    ens = _ensemble(4)
    for sched in (PEAK_AWARE_BOOSTED, progress_ramp_schedule(0.4, 0.9)):
        r = sweep([SweepCase(sched, wl, m, carbon=ens)])[0]
        singles = [sweep([SweepCase(sched, wl, m,
                                    carbon=ens.member(e))])[0].co2_kg
                   for e in range(4)]
        assert np.allclose(r.co2_ensemble.samples, singles, rtol=1e-9)
        assert r.co2_kg == pytest.approx(np.mean(singles), rel=1e-9)
        assert r.co2_ensemble.hi >= r.co2_ensemble.q95 >= r.co2_ensemble.q05
        # carbon-blind schedule: dynamics identical across members
        assert r.energy_ensemble is None


def test_carbon_dependent_schedule_expands_per_member(calibrated):
    """A schedule that consults ctx.carbon_factor decides differently
    under each member, so the scan expands it into E lanes and even
    energy/runtime get per-member spread."""
    wl, m = calibrated

    def carbon_follower(ctx):
        return 0.9 if ctx.carbon_factor < 0.45 else 0.3

    sched = FunctionSchedule("follower", carbon_follower)
    ens = _ensemble(3)
    r = sweep([SweepCase(sched, wl, m, carbon=ens)])[0]
    assert r.energy_ensemble is not None and r.runtime_ensemble is not None
    singles = [sweep([SweepCase(sched, wl, m,
                                carbon=ens.member(e))])[0]
               for e in range(3)]
    assert np.allclose(r.co2_ensemble.samples,
                       [s.co2_kg for s in singles], rtol=1e-9)
    assert np.allclose(r.runtime_ensemble.samples,
                       [s.runtime_h for s in singles], rtol=1e-9)
    assert r.runtime_ensemble.std > 0.0


def test_mismatched_ensemble_sizes_rejected(calibrated):
    wl, m = calibrated
    with pytest.raises(ValueError, match="same member count"):
        trace_sweep([SweepCase(BASELINE, wl, m, carbon=_ensemble(2)),
                     SweepCase(BASELINE, wl, m, carbon=_ensemble(3))])


def test_campaign_sweep_carbon_ensemble(calibrated):
    c = Campaign(OEM_CASE_1)
    ens = _ensemble(3)
    res = c.sweep([BASELINE, PEAK_AWARE_BOOSTED], carbon_ensemble=ens)
    assert len(res) == 2
    assert all(r.co2_ensemble is not None
               and r.co2_ensemble.n_members == 3 for r in res)
    with pytest.raises(ValueError, match="carbon_ensemble"):
        c.sweep([BASELINE], carbon_trace=[0.4] * 48, carbon_ensemble=ens)


# ---------------------------------------------------------------------------
# Robust objectives
# ---------------------------------------------------------------------------
def test_reduce_ensemble_modes():
    vals = np.array([[1.0, 3.0, 2.0, 10.0]])
    assert reduce_ensemble(vals, "mean")[0] == pytest.approx(4.0)
    assert reduce_ensemble(vals, "worst")[0] == pytest.approx(10.0)
    # alpha=0.5 on 4 members -> mean of worst 2
    assert reduce_ensemble(vals, "cvar", alpha=0.5)[0] == pytest.approx(6.5)
    # cvar interpolates between mean (alpha->0) and worst (alpha->1)
    cv = reduce_ensemble(vals, "cvar", alpha=0.9)[0]
    assert 4.0 <= cv <= 10.0
    with pytest.raises(ValueError, match="robust"):
        reduce_ensemble(vals, "median")
    with pytest.raises(ValueError, match="robust"):
        Objective(weights={"co2": 1.0}, robust="median")
    with pytest.raises(ValueError, match="cvar_alpha"):
        Objective(weights={"co2": 1.0}, cvar_alpha=1.5)


def test_trace_objective_ensemble_axis(calibrated):
    """TraceObjective grows the trailing (E,) CO2 axis; per-member
    values match E single-trace objectives."""
    wl, m = calibrated
    ens = _ensemble(3)
    case = SweepCase(parametric_schedule(24), wl, m, carbon=ens,
                     deadline_h=220.0)
    to = TraceObjective(case, horizon_h=260.0)
    U = np.full((2, 24), 0.6)
    mets = to.evaluate_batch(U)
    assert mets.co2_kg.shape == (2, 3)
    assert mets.energy_kwh.shape == (2,)
    for e in range(3):
        single = TraceObjective(dataclasses.replace(case,
                                                    carbon=ens.member(e)),
                                horizon_h=260.0).evaluate_batch(U)
        assert np.allclose(mets.co2_kg[:, e], single.co2_kg, rtol=1e-12)
        assert np.allclose(mets.energy_kwh, single.energy_kwh, rtol=1e-12)


def test_robust_optimize_constant_ensemble_matches_deterministic():
    """With E identical members every robust mode degenerates to the
    deterministic objective: same search trajectory, same optimum."""
    trace = _week_trace()
    ens = SignalEnsemble(tuple(trace for _ in range(4)), name="const")
    c = Campaign(OEM_CASE_1)
    det = c.optimize("co2", deadline_h=215.0, carbon_trace=trace,
                     method="cem", candidates=48, iterations=6, seed=9)
    for robust in ("mean", "cvar", "worst"):
        rob = c.optimize("co2", deadline_h=215.0, carbon_ensemble=ens,
                         robust=robust, method="cem", candidates=48,
                         iterations=6, seed=9)
        assert abs(rob.metrics.co2_kg / det.metrics.co2_kg - 1) < 1e-9, robust
        assert abs(rob.result.energy_kwh / det.result.energy_kwh - 1) < 1e-9
        assert np.allclose(rob.co2_ensemble, rob.metrics.co2_kg, rtol=1e-9)


def test_campaign_optimize_cvar_e32_numpy_backend():
    """Acceptance: robust CVaR optimization over E>=32 members on the
    NumPy fallback."""
    ens = _ensemble(32)
    c = Campaign(OEM_CASE_1)
    res = c.optimize("co2", deadline_h=220.0, carbon_ensemble=ens,
                     robust="cvar", method="cem", candidates=24,
                     iterations=4, backend="numpy", seed=2)
    assert res.method == "cem"
    assert res.objective.robust == "cvar"
    assert res.co2_ensemble is not None and len(res.co2_ensemble) == 32
    assert res.metrics.unfinished < 1e-9
    # CVaR at the optimum sits in the member tail, above the mean
    assert res.metrics.co2_kg >= np.mean(res.co2_ensemble) - 1e-12
    assert res.result.co2_ensemble is not None
    assert res.result.co2_ensemble.n_members == 32


def test_campaign_optimize_cvar_e32_jit_backend():
    """Acceptance: the same robust search through the jitted scan —
    including gradients through the CVaR sort."""
    ens = _ensemble(32)
    c = Campaign(OEM_CASE_1)
    res = c.optimize("co2", deadline_h=220.0, carbon_ensemble=ens,
                     robust="cvar", method="cem+grad", candidates=32,
                     iterations=4, steps=40, seed=2)
    assert res.method == "cem+grad"
    assert res.metrics.unfinished < 1e-9
    assert res.metrics.runtime_h <= 220.0 * 1.01
    assert len(res.co2_ensemble) == 32
    # robust ranking at one schedule: worst >= cvar >= mean
    mets = np.asarray(res.co2_ensemble)
    assert mets.max() + 1e-12 >= res.metrics.co2_kg >= mets.mean() - 1e-12
