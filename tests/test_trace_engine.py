"""Trace-grid engine + trace-signal tests (the multi_layer_refactor
acceptance bar):

* the trace-grid scan agrees with the periodic 24-slot engine to float
  precision on all six Figure-1 policies, on both backends;
* it agrees with the per-batch oracle to <0.5% on the two case families
  the PR-1 engine rejected with ValueError: progress-aware deadline
  schedules and multi-day non-periodic carbon traces;
* sweep() dispatches mixed case sets to the right path, order preserved;
* satellites: HourlySignal floor fix, bounded engine memo caches,
  periodic-engine boundary cases (day-boundary residual, fractional
  start_hour, price=None) pinned against simulate_campaign_exact.
"""
import math

import numpy as np
import pytest

from repro.core import (BASELINE, GridCarbonModel, HourlySignal,
                        MachineProfile, MIDWEST_HOURLY, PEAK_AWARE_BOOSTED,
                        POLICIES, SweepCase, TimeBands, TraceSignal,
                        as_trace, calibrate_workload, constant_schedule,
                        deadline_schedule, default_signals, hourly_schedule,
                        progress_ramp_schedule, simulate_campaign,
                        simulate_campaign_exact, sweep, trace_sweep)
from repro.core import Campaign
from repro.core.engine import _band_table, _carbon_table
from repro.core.policy import HourlyPolicy
from repro.core.workload import OEM_CASE_1, OEMWorkload


@pytest.fixture(scope="module")
def calibrated():
    return calibrate_workload(OEM_CASE_1, MachineProfile())


def _week_trace(scale: float = 0.448) -> TraceSignal:
    """A 7-day non-periodic carbon trace: diurnal swing + weekday drift +
    deterministic noise (nothing repeats with period 24)."""
    rng = np.random.RandomState(7)
    h = np.arange(168)
    vals = scale * (1.0 + 0.30 * np.sin(2 * np.pi * h / 24.0)
                    + 0.08 * np.sin(2 * np.pi * h / 168.0)
                    + 0.05 * rng.randn(168))
    return TraceSignal(tuple(float(v) for v in vals), name="week")


# ---------------------------------------------------------------------------
# Acceptance: parity with the periodic engine on periodic cases
# ---------------------------------------------------------------------------
def test_trace_engine_matches_periodic_engine_all_six_policies(calibrated):
    """Float-precision agreement on every Figure-1 policy: both engines
    integrate the same piecewise-hourly model, one by day-jump arithmetic,
    one by scanning every hour."""
    wl, m = calibrated
    cases = [SweepCase(p, wl, m) for p in POLICIES.values()]
    periodic = sweep(cases)
    traced = trace_sweep(cases)
    for a, b in zip(periodic, traced):
        assert abs(b.runtime_h / a.runtime_h - 1) < 1e-9, a.policy
        assert abs(b.energy_kwh / a.energy_kwh - 1) < 1e-9, a.policy
        assert abs(b.co2_kg / a.co2_kg - 1) < 1e-9, a.policy


def test_trace_engine_numpy_backend_matches_jax(calibrated):
    """The NumPy fallback runs the identical scan; with JAX present the
    two backends must agree to float64 precision."""
    wl, m = calibrated
    cases = [SweepCase(p, wl, m) for p in (BASELINE, PEAK_AWARE_BOOSTED)]
    cases += [SweepCase(progress_ramp_schedule(0.4, 0.9), wl, m)]
    np_res = trace_sweep(cases, backend="numpy")
    jax_res = trace_sweep(cases, backend="jax")
    for a, b in zip(np_res, jax_res):
        assert abs(b.runtime_h / a.runtime_h - 1) < 1e-12, a.policy
        assert abs(b.energy_kwh / a.energy_kwh - 1) < 1e-12, a.policy


# ---------------------------------------------------------------------------
# Acceptance: the two PR-1 ValueError walls, now first-class cases
# ---------------------------------------------------------------------------
def test_deadline_schedule_sweeps_and_matches_exact_oracle(calibrated):
    """(a) a progress-aware deadline schedule — the periodic engine's
    probe rejects it, sweep() routes it to the trace grid, and the result
    stays within 0.5% of the per-batch oracle."""
    wl, m = calibrated
    sched = deadline_schedule(200.0)
    vec = sweep([SweepCase(sched, wl, m)])[0]
    exact = simulate_campaign_exact(wl, sched, m)
    assert abs(vec.runtime_h / exact.runtime_h - 1) < 0.005
    assert abs(vec.energy_kwh / exact.energy_kwh - 1) < 0.005
    assert abs(vec.co2_kg / exact.co2_kg - 1) < 0.005
    # and the pace-keeper meets its deadline with a small margin
    assert 180.0 < vec.runtime_h < 201.0


def test_week_long_trace_sweeps_and_matches_exact_oracle(calibrated):
    """(b) a 7-day non-periodic carbon trace — unrepresentable on the
    periodic 24-slot grid, exact on the trace grid."""
    wl, m = calibrated
    trace = _week_trace()
    for sched in (BASELINE, PEAK_AWARE_BOOSTED):
        vec = sweep([SweepCase(sched, wl, m, carbon=trace)])[0]
        exact = simulate_campaign_exact(wl, sched, m, carbon=trace)
        assert abs(vec.runtime_h / exact.runtime_h - 1) < 0.005
        assert abs(vec.energy_kwh / exact.energy_kwh - 1) < 0.005
        assert abs(vec.co2_kg / exact.co2_kg - 1) < 0.005
        # the sequential segment simulator handles traces too, and the
        # trace grid matches it to float precision (same hourly model)
        seq = simulate_campaign(wl, sched, m, carbon=trace)
        assert abs(vec.co2_kg / seq.co2_kg - 1) < 1e-9


def test_progress_and_trace_combined(calibrated):
    """Deadline pace-keeping under a week-long carbon trace: both
    previously-impossible features at once."""
    wl, m = calibrated
    sched = deadline_schedule(220.0)
    trace = _week_trace()
    vec = sweep([SweepCase(sched, wl, m, carbon=trace)])[0]
    exact = simulate_campaign_exact(wl, sched, m, carbon=trace)
    assert abs(vec.runtime_h / exact.runtime_h - 1) < 0.005
    assert abs(vec.co2_kg / exact.co2_kg - 1) < 0.005


def test_sweep_dispatch_preserves_order_and_periodic_results(calibrated):
    """A mixed case list: periodic cases keep the fast path's
    float-identical numbers, trace cases slot back in original order."""
    wl, m = calibrated
    ramp = progress_ramp_schedule(0.4, 0.9)
    mixed = [SweepCase(BASELINE, wl, m), SweepCase(ramp, wl, m),
             SweepCase(PEAK_AWARE_BOOSTED, wl, m)]
    res = sweep(mixed)
    assert [r.policy for r in res] == [BASELINE.name, ramp.name,
                                       PEAK_AWARE_BOOSTED.name]
    pure = sweep([mixed[0], mixed[2]])
    assert res[0].energy_kwh == pure[0].energy_kwh
    assert res[2].energy_kwh == pure[1].energy_kwh


def test_campaign_sweep_carbon_trace_and_deadline(calibrated):
    """Campaign.sweep grows carbon_trace= / deadline_h=: an hourly list
    becomes a TraceSignal, and the deadline reaches schedules through
    ctx.deadline_h."""
    trace_vals = list(_week_trace().values)
    c = Campaign(OEM_CASE_1)
    sched = deadline_schedule()          # no own deadline: reads ctx
    res = c.sweep([sched], carbon_trace=trace_vals, deadline_h=200.0)
    assert len(res) == 1
    wl, m = c.calibrated()
    exact = simulate_campaign_exact(wl, sched, m, carbon=_week_trace(),
                                    deadline_h=200.0)
    assert abs(res[0].runtime_h / exact.runtime_h - 1) < 0.005
    assert abs(res[0].co2_kg / exact.co2_kg - 1) < 0.005
    with pytest.raises(ValueError, match="carbon_trace"):
        c.sweep([sched], carbons=[GridCarbonModel()],
                carbon_trace=trace_vals)


def test_heterogeneous_start_hours_and_machines(calibrated):
    """The scan batches a heterogeneous fleet: per-case start_hour and
    machine profiles, each agreeing with its own sequential run."""
    wl, m = calibrated
    m2 = MachineProfile(idle_w=120.0, dyn_w=300.0, alpha=1.5, gamma=0.5)
    trace = _week_trace()
    cases = [SweepCase(BASELINE, wl, m, carbon=trace, start_hour=3.0),
             SweepCase(BASELINE, wl, m2, carbon=trace, start_hour=17.0)]
    res = trace_sweep(cases)
    for case, r in zip(cases, res):
        seq = simulate_campaign(wl, BASELINE, case.machine, carbon=trace,
                                start_hour=case.start_hour)
        assert abs(r.runtime_h / seq.runtime_h - 1) < 1e-9
        assert abs(r.co2_kg / seq.co2_kg - 1) < 1e-9


# ---------------------------------------------------------------------------
# Chunked resumable executor (PR-4): the default engine path scans in
# fixed-shape chunks with state carried across them — it must reproduce
# the monolithic single-scan numbers on this file's own case families.
# Deeper chunking/ensemble coverage lives in tests/test_ensemble.py.
# ---------------------------------------------------------------------------
def test_chunked_executor_matches_monolithic_on_this_files_cases(calibrated):
    wl, m = calibrated
    trace = _week_trace()
    cases = ([SweepCase(p, wl, m) for p in POLICIES.values()]
             + [SweepCase(deadline_schedule(200.0), wl, m, carbon=trace),
                SweepCase(progress_ramp_schedule(0.4, 0.9), wl, m,
                          carbon=trace, start_hour=3.0)])
    for chunk_days in (2, 4):
        chunked = trace_sweep(cases, chunk_days=chunk_days)
        mono = trace_sweep(cases, mode="monolithic")
        for a, b in zip(mono, chunked):
            assert abs(b.runtime_h / a.runtime_h - 1) < 1e-9, a.policy
            assert abs(b.energy_kwh / a.energy_kwh - 1) < 1e-9, a.policy
            assert abs(b.co2_kg / a.co2_kg - 1) < 1e-9, a.policy


def test_sweep_dispatches_ensemble_to_trace_path(calibrated):
    """A SignalEnsemble carbon is never representable on the periodic
    grid: sweep() must route it to the trace engine and attach per-member
    stats, order preserved in a mixed batch."""
    from repro.core import SignalEnsemble
    wl, m = calibrated
    ens = SignalEnsemble((_week_trace(), _week_trace(0.5)))
    mixed = [SweepCase(BASELINE, wl, m),
             SweepCase(BASELINE, wl, m, carbon=ens)]
    res = sweep(mixed)
    assert res[0].co2_ensemble is None
    assert res[1].co2_ensemble is not None
    assert res[1].co2_ensemble.n_members == 2
    assert res[1].co2_kg == pytest.approx(
        np.mean(res[1].co2_ensemble.samples))


# ---------------------------------------------------------------------------
# TraceSignal semantics
# ---------------------------------------------------------------------------
def test_trace_signal_clamps_and_samples():
    t = TraceSignal((1.0, 2.0, 3.0), name="t3")
    assert t.period_h is None
    assert t.at(-5.0) == 1.0             # clamp before range
    assert t.at(0.5) == 1.0
    assert t.at(2.9) == 3.0
    assert t.at(10.0) == 3.0             # hold-last beyond range
    assert list(t.sample([-1.0, 1.5, 99.0])) == [1.0, 2.0, 3.0]
    with pytest.raises(ValueError):
        TraceSignal(())


def test_custom_at_only_signal_routes_to_trace_path(calibrated):
    """A live-feed-style signal implementing only at(hour) — no period_h
    declaration — must not be collapsed onto one repeated day by the
    periodic engine: unknown periodicity routes to the trace grid."""
    wl, m = calibrated

    class DriftingFeed:                  # drifts 0.4 -> 0.7 over a week
        name = "drifting-feed"

        def at(self, hour):
            return 0.4 + 0.3 * min(max(hour / 168.0, 0.0), 1.0)

    feed = DriftingFeed()
    vec = sweep([SweepCase(BASELINE, wl, m, carbon=feed)])[0]
    seq = simulate_campaign(wl, BASELINE, m, carbon=feed)
    assert abs(vec.co2_kg / seq.co2_kg - 1) < 1e-9
    # a signal declaring 24 h periodicity still takes the periodic path
    class DeclaredPeriodic(DriftingFeed):
        period_h = 24.0
    from repro.core import is_periodic_24h
    assert is_periodic_24h(DeclaredPeriodic())
    assert not is_periodic_24h(feed)


def test_as_trace_coerces_sequences():
    t = as_trace([0.4] * 48, name="two-day")
    assert isinstance(t, TraceSignal) and len(t.values) == 48
    assert as_trace(t) is t
    # arrays exposing a non-callable `.at` indexer (jnp, pandas) are
    # sequences, not Signals — they must be converted, not passed through
    import jax.numpy as jnp
    tj = as_trace(jnp.linspace(0.4, 0.7, 48))
    assert isinstance(tj, TraceSignal) and len(tj.values) == 48
    # SignalSet.sample carries traces next to periodic signals
    sigs = default_signals(TimeBands(), GridCarbonModel())
    sigs = type(sigs)(background=sigs.background, carbon=_week_trace())
    assert not sigs.is_periodic()
    bg, cf, pr = sigs.sample([0.0, 30.0, 200.0])
    assert cf[0] == _week_trace().values[0]
    assert cf[2] == _week_trace().values[-1]    # clamped past the trace
    assert pr.tolist() == [0.0, 0.0, 0.0]


# ---------------------------------------------------------------------------
# Satellite: HourlySignal floor fix (and the same bug class elsewhere)
# ---------------------------------------------------------------------------
def test_hourly_signal_negative_and_large_hours():
    vals = tuple(float(h) for h in range(24))
    s = HourlySignal(vals)
    assert s.at(-0.5) == 23.0            # int() used to truncate to slot 0
    assert s.at(-24.5) == 23.0
    assert s.at(-1e-9) == 23.0
    assert s.at(24.5) == 0.0
    assert s.at(47.99) == 23.0
    curve = tuple(1.0 + 0.01 * h for h in range(24))
    g = GridCarbonModel(hourly_curve=curve)
    assert g.factor_at(-0.5) == pytest.approx(0.448 * curve[23])
    p = HourlyPolicy("h", {b: 0.5 for b in ("peak", "load_sensitive",
                                            "shoulder", "night")},
                     50, False, vals)
    assert p.intensity_at_hour(-0.5) == 23.0


# ---------------------------------------------------------------------------
# Satellite: bounded engine memo caches
# ---------------------------------------------------------------------------
def test_engine_caches_are_bounded(calibrated):
    wl, m = calibrated
    maxsize = _band_table.cache_info().maxsize
    assert maxsize is not None and maxsize <= 1024
    variants = [TimeBands(peak=((a, b),))
                for a in range(0, 23) for b in range(a + 1, 24)][:maxsize + 20]
    for bands in variants:
        sweep([SweepCase(BASELINE, wl, m, bands=bands)])
    assert _band_table.cache_info().currsize <= maxsize
    # unhashable hourly curves still work (uncached path)
    curvy = GridCarbonModel(hourly_curve=list(MIDWEST_HOURLY))
    assert _carbon_table(curvy).shape == (24,)


# ---------------------------------------------------------------------------
# Satellite: periodic-engine boundary cases vs the per-batch oracle
# ---------------------------------------------------------------------------
def test_residual_landing_exactly_on_day_boundary():
    """n_scenarios an exact multiple of one day's throughput: zero
    residual, runtime an exact whole number of days."""
    m = MachineProfile(gamma=0.0)        # contention off => exact rates
    wl = OEMWorkload("exact-days", 864_000, rate_at_full=10.0,
                     batch_overhead_s=0.0)
    sched = constant_schedule(0.5)       # 5 scen/s -> 432000/day -> 2 days
    r = sweep([SweepCase(sched, wl, m)])[0]
    assert r.runtime_h == pytest.approx(48.0, abs=1e-9)
    exact = simulate_campaign_exact(wl, sched, m)
    assert abs(r.runtime_h / exact.runtime_h - 1) < 0.005
    assert abs(r.energy_kwh / exact.energy_kwh - 1) < 0.005


def test_fractional_start_hour_partial_leading_slot(calibrated):
    """start_hour=9.5 splits the leading hour across lens[:,0]/lens[:,24];
    pinned against the oracle and float-identical to the sequential path."""
    wl, m = calibrated
    for sched in (PEAK_AWARE_BOOSTED,
                  hourly_schedule("hr", [0.3 + 0.02 * h for h in range(24)])):
        r = sweep([SweepCase(sched, wl, m, start_hour=9.5)])[0]
        exact = simulate_campaign_exact(wl, sched, m, start_hour=9.5)
        seq = simulate_campaign(wl, sched, m, start_hour=9.5)
        assert abs(r.runtime_h / exact.runtime_h - 1) < 0.005, sched.name
        assert abs(r.energy_kwh / exact.energy_kwh - 1) < 0.005, sched.name
        assert abs(r.energy_kwh / seq.energy_kwh - 1) < 1e-9, sched.name


def test_price_none_leaves_cost_none(calibrated):
    """No price signal => cost_usd stays None (not 0.0) on every path."""
    wl, m = calibrated
    assert sweep([SweepCase(BASELINE, wl, m)])[0].cost_usd is None
    assert trace_sweep([SweepCase(BASELINE, wl, m,
                                  carbon=_week_trace())])[0].cost_usd is None
    assert simulate_campaign_exact(wl, BASELINE, m).cost_usd is None


# ---------------------------------------------------------------------------
# Satellite: mixed-resolution sweeps in one process (PR-2 memo-cache audit).
# The sph-keyed caches (_bg_table) were fine, but the closed-form profile
# path crashed on sub-hour band edges (periodic_decision_profile sampled
# through the hourly-only _band_table) and mixed-resolution batches used
# max() instead of lcm() to pick the shared grid.
# ---------------------------------------------------------------------------
def test_sub_hour_band_edges_on_trace_path(calibrated):
    """Band policies with sub-hour edges route to the trace grid and match
    the sequential simulator (used to raise the periodic engine's
    'cannot represent sub-hour band edges' ValueError)."""
    wl, m = calibrated
    bands = TimeBands(peak=((14.5, 19),),
                      load_sensitive=((11, 14.5), (19, 21)))
    r = sweep([SweepCase(PEAK_AWARE_BOOSTED, wl, m, bands=bands)])[0]
    seq = simulate_campaign(wl, PEAK_AWARE_BOOSTED, m, bands=bands)
    assert abs(r.energy_kwh / seq.energy_kwh - 1) < 1e-9
    assert abs(r.runtime_h / seq.runtime_h - 1) < 1e-9


def test_hourly_profile_still_rejects_sub_hour_bands(calibrated):
    """The periodic-only helper keeps its guard: sampling sub-hour band
    edges on an incompatible grid raises instead of silently aliasing
    the edge onto the previous band (docs/API.md migration note)."""
    from repro.core import hourly_profile
    bands = TimeBands(peak=((14.5, 19),),
                      load_sensitive=((11, 14.5), (19, 21)))
    with pytest.raises(ValueError, match="alias|band edges"):
        hourly_profile(PEAK_AWARE_BOOSTED, bands, GridCarbonModel())


def test_mixed_resolution_sweeps_in_one_process(calibrated):
    """Alternating grid resolutions through the same memoization caches:
    hourly, half-hour, hourly again, quarter-hour — every sweep must
    match its own sequential run (a cache key ignoring slots_per_hour
    would replay the wrong resolution's tables)."""
    wl, m = calibrated
    half = TimeBands(peak=((14.5, 19),),
                     load_sensitive=((11, 14.5), (19, 21)))
    quarter = TimeBands(peak=((14.25, 19),),
                        load_sensitive=((11, 14.25), (19, 21)))
    for bands in (TimeBands(), half, TimeBands(), quarter, half):
        r = sweep([SweepCase(PEAK_AWARE_BOOSTED, wl, m, bands=bands)])[0]
        seq = simulate_campaign(wl, PEAK_AWARE_BOOSTED, m, bands=bands)
        assert abs(r.energy_kwh / seq.energy_kwh - 1) < 1e-9, bands.peak


def test_mixed_resolutions_in_one_batch_use_lcm_grid(calibrated):
    """One sweep() call mixing a half-hour case and a third-hour case:
    the shared trace grid must refine to lcm (6 slots/hour), not max."""
    wl, m = calibrated
    half = TimeBands(peak=((14.5, 19),),
                     load_sensitive=((11, 14.5), (19, 21)))
    third = TimeBands(peak=((43.0 / 3.0, 19),),
                      load_sensitive=((11, 43.0 / 3.0), (19, 21)))
    cases = [SweepCase(PEAK_AWARE_BOOSTED, wl, m, bands=half),
             SweepCase(PEAK_AWARE_BOOSTED, wl, m, bands=third)]
    res = sweep(cases)
    for case, r in zip(cases, res):
        seq = simulate_campaign(wl, PEAK_AWARE_BOOSTED, m, bands=case.bands)
        assert abs(r.energy_kwh / seq.energy_kwh - 1) < 1e-9


def test_sub_hour_parametric_schedule_forces_trace_dispatch(calibrated):
    """The dispatcher hook: a 48-slot ParametricSchedule advertises
    half-hour change hours, so its case needs slots_per_hour=2 and the
    trace path — sampling it hourly would alias away every second slot."""
    from repro.core.engine import case_slots_per_hour
    from repro.core.schedule import ParametricSchedule
    wl, m = calibrated
    ps = ParametricSchedule.from_intensities(
        [0.3 + 0.5 * math.sin(2 * math.pi * i / 48) ** 2 for i in range(48)],
        name="p48")
    case = SweepCase(ps, wl, m)
    assert case_slots_per_hour(case) == 2
    r = sweep([case])[0]
    seq = simulate_campaign(wl, ps, m)
    assert abs(r.energy_kwh / seq.energy_kwh - 1) < 1e-9
    assert abs(r.runtime_h / seq.runtime_h - 1) < 1e-9


# ---------------------------------------------------------------------------
# deadline_schedule behaviour
# ---------------------------------------------------------------------------
def test_deadline_schedule_paces_toward_deadline(calibrated):
    """A generous deadline is met near-exactly (the keeper slows down to
    it); an infeasible one degrades gracefully to ~flat-out runtime."""
    wl, m = calibrated
    generous = simulate_campaign(wl, deadline_schedule(260.0), m)
    assert 230.0 < generous.runtime_h < 261.0
    flat_out = simulate_campaign(wl, constant_schedule(0.95), m)
    tight = simulate_campaign(wl, deadline_schedule(100.0), m)
    assert tight.runtime_h < flat_out.runtime_h * 1.1
    # pacing draws far less average power than flat-out (total kWh still
    # grows with runtime here: whole-machine energy includes idle draw)
    assert (generous.energy_kwh / generous.runtime_h
            < 0.8 * flat_out.energy_kwh / flat_out.runtime_h)
    # no deadline anywhere -> flat out at u_high
    free = simulate_campaign(wl, deadline_schedule(), m)
    assert math.isclose(
        free.runtime_h,
        simulate_campaign(wl, constant_schedule(0.95), m).runtime_h,
        rel_tol=1e-9)
