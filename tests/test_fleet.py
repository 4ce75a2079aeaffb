"""Fleet: site-level joint scheduling (the PR-5 api_redesign bar).

* uncoupled parity: `Fleet.sweep` with no site cap is bitwise-identical
  to M independent `Campaign.sweep` calls, pinned on the chunked trace
  path, and grouping alone never changes results;
* coupled correctness: the grouped-lane kernel matches the sequential
  per-slot oracle (`simulate_fleet`) to <0.5 % under an active cap,
  across allocation families and backends, and site peaks agree;
* joint optimization: `Fleet.optimize` under a shared cap + per-campaign
  deadlines produces site CO2 <= the independently-optimized
  per-campaign schedules evaluated under the same cap (two-OEM example);
* satellites: `scan_stats(reset=True)` + plan-cache hits across two
  identical fleet sweeps, grouped-lane counting, duplicate-name dedupe /
  empty-sequence errors in Campaign and Fleet sweeps, `trace_windows`
  edge cases, and the dashboard's ensemble + site-rollup rendering.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core import (BASELINE, Campaign, Fleet, GridCarbonModel,
                        MachineProfile, PEAK_AWARE_BOOSTED, Site, SweepCase,
                        TraceSignal, calibrate_workload, carbon_gated_cap,
                        constant_schedule, deadline_weighted_split,
                        proportional_split, site_throttle, trace_windows)
from repro.core.engine_jax import (compile_plan, execute_plan,
                                   reset_scan_stats, scan_stats)
from repro.core.fleet import fleet_sweep, simulate_fleet
from repro.core.schedule import dedupe_names
from repro.core.workload import OEM_CASE_1, OEM_CASE_2


@pytest.fixture(scope="module")
def calibrated():
    wl1, m = calibrate_workload(OEM_CASE_1, MachineProfile())
    wl2 = dataclasses.replace(OEM_CASE_2, rate_at_full=wl1.rate_at_full)
    return wl1, wl2, m


@pytest.fixture(scope="module")
def campaigns():
    return [Campaign(OEM_CASE_1), Campaign(OEM_CASE_2)]


def _week_trace(scale: float = 0.448, seed: int = 7) -> TraceSignal:
    rng = np.random.RandomState(seed)
    h = np.arange(168)
    vals = scale * (1.0 + 0.30 * np.sin(2 * np.pi * h / 24.0)
                    + 0.05 * rng.randn(168))
    return TraceSignal(tuple(float(v) for v in vals), name=f"week{seed}")


# ---------------------------------------------------------------------------
# The coupling model
# ---------------------------------------------------------------------------
def test_site_throttle_step_semantics():
    """One fixed-point step: free headroom keeps f=1; a binding cap
    scales the sheddable component; an unreachable cap pins the floor;
    an uncapped site is inert.  With base_kw=0 the step degenerates to
    plain demand-proportional curtailment."""
    assert site_throttle(2.0, 0.0, 3.0) == 1.0     # headroom free: no cut
    assert abs(site_throttle(4.0, 0.0, 3.0) - 0.75) < 1e-12
    # sheddable-aware: base 2 kW is not sheddable, so meeting headroom 3
    # of a 4 kW draw needs the sheddable 2 kW cut in half
    assert abs(site_throttle(4.0, 2.0, 3.0) - 0.5) < 1e-12
    assert site_throttle(100.0, 0.0, 0.5) == 0.05  # floor: never deadlock
    assert site_throttle(5.0, 4.0, 2.0) == 0.05    # unreachable cap
    assert site_throttle(1.0, 0.5, math.inf) == 1.0   # uncapped site
    out = site_throttle(np.array([2.0, 4.0, 100.0]), 0.0, 3.0, xp=np)
    assert np.allclose(out, [1.0, 0.75, 0.05])
    # damped: the factor compounds across steps through f
    assert abs(site_throttle(4.0, 2.0, 3.0, f=0.5) - 0.25) < 1e-12


# ---------------------------------------------------------------------------
# Uncoupled parity (acceptance: bitwise on the chunked path)
# ---------------------------------------------------------------------------
def test_uncoupled_fleet_bitwise_matches_independent_sweeps(campaigns):
    """Fleet([c1, c2]).sweep with no site cap must equal two independent
    Campaign.sweep calls bit for bit — pinned on the chunked trace path
    (a week-long carbon trace forces every case onto it)."""
    c1, c2 = campaigns
    trace = _week_trace()
    scheds = [BASELINE, PEAK_AWARE_BOOSTED]
    fleet = Fleet([c1, c2], Site(carbon=trace))
    fres = fleet.sweep(scheds)
    ind = [c.sweep(scheds, carbon_trace=trace) for c in (c1, c2)]
    for i, fr in enumerate(fres):
        for m, r in enumerate(fr.campaigns):
            assert r.runtime_h == ind[m][i].runtime_h
            assert r.energy_kwh == ind[m][i].energy_kwh
            assert r.co2_kg == ind[m][i].co2_kg
        assert fr.site.runtime_h == max(r.runtime_h for r in fr.campaigns)
        assert fr.site.energy_kwh == sum(r.energy_kwh for r in fr.campaigns)


def test_uncapped_grouping_is_bitwise_inert(calibrated):
    """group_sizes with an infinite cap must not perturb the scan: the
    grouped plan runs the exact ungrouped kernels."""
    wl1, wl2, m = calibrated
    trace = _week_trace()
    cases = [SweepCase(BASELINE, wl1, m, carbon=trace),
             SweepCase(PEAK_AWARE_BOOSTED, wl2, m, carbon=trace)]
    from repro.core.engine_jax import trace_sweep
    ref = trace_sweep(cases)
    grp = trace_sweep(cases, group_sizes=[2], group_caps_kw=[None])
    for a, b in zip(ref, grp):
        assert a.runtime_h == b.runtime_h
        assert a.energy_kwh == b.energy_kwh
        assert a.co2_kg == b.co2_kg


def test_campaign_as_fleet_is_the_m1_special_case(campaigns):
    c1, _ = campaigns
    scheds = [BASELINE, PEAK_AWARE_BOOSTED]
    solo = c1.sweep(scheds)
    f = c1.as_fleet().sweep(scheds)
    for a, fr in zip(solo, f):
        assert len(fr.campaigns) == 1
        assert fr.campaigns[0].runtime_h == a.runtime_h
        assert fr.campaigns[0].energy_kwh == a.energy_kwh


# ---------------------------------------------------------------------------
# Coupled correctness (acceptance: <0.5 % vs the per-slot oracle)
# ---------------------------------------------------------------------------
SITE = Site(power_cap_kw=0.40, office_kw=0.12)


def _fleet_cases(calibrated, schedules, deadlines=(0.0, 0.0), carbon=None):
    wl1, wl2, m = calibrated
    return [SweepCase(s, wl, m, SITE.bands, carbon or GridCarbonModel(),
                      9.0, deadline_h=d)
            for s, wl, d in zip(schedules, (wl1, wl2), deadlines)]


@pytest.mark.parametrize("backend", ["jax", "numpy"])
def test_grouped_engine_matches_oracle_under_cap(calibrated, backend):
    """Every bundled allocation family, coupled under an active cap:
    the grouped-lane scan agrees with the python per-slot oracle to
    <0.5 % on runtime/energy/CO2, and site peaks to <1 %."""
    dls = (300.0, 480.0)
    families = [
        proportional_split(0.8).for_fleet(2),
        carbon_gated_cap(0.45).for_fleet(2),
        deadline_weighted_split(dls).for_fleet(2),
        (PEAK_AWARE_BOOSTED, PEAK_AWARE_BOOSTED),
    ]
    for scheds in families:
        cases = _fleet_cases(calibrated, scheds, dls)
        eng = fleet_sweep([cases], SITE, backend=backend)[0]
        orc = simulate_fleet(cases, SITE)
        for e, o in zip(eng.campaigns, orc.campaigns):
            assert abs(e.runtime_h / o.runtime_h - 1) < 5e-3, e.policy
            assert abs(e.energy_kwh / o.energy_kwh - 1) < 5e-3, e.policy
            assert abs(e.co2_kg / o.co2_kg - 1) < 5e-3, e.policy
        assert abs(eng.site.peak_kw / orc.site.peak_kw - 1) < 1e-2


def test_cap_actually_bites_and_slows_the_fleet(calibrated):
    """A tight cap must curtail: coupled runtimes strictly exceed the
    uncoupled ones, and the site peak sits near the cap instead of at
    the free-running draw."""
    scheds = (BASELINE, BASELINE)
    cases = _fleet_cases(calibrated, scheds)
    free = fleet_sweep([cases], Site())[0]
    capped = fleet_sweep([cases], SITE)[0]
    for f, c in zip(free.campaigns, capped.campaigns):
        assert c.runtime_h > f.runtime_h * 1.05
    assert capped.site.peak_kw < 0.52   # demand would be well above


def test_finished_campaign_releases_headroom(calibrated):
    """When the small campaign finishes, the big one must speed up: its
    coupled runtime is shorter than if the small one ran forever (pinned
    by comparing against a doubled-workload small campaign)."""
    wl1, wl2, m = calibrated
    scheds = (BASELINE, BASELINE)
    base = fleet_sweep([_fleet_cases((wl1, wl2, m), scheds)], SITE)[0]
    wl1_big = dataclasses.replace(wl1, n_scenarios=wl1.n_scenarios * 4)
    longer = fleet_sweep([_fleet_cases((wl1_big, wl2, m), scheds)], SITE)[0]
    assert base.campaigns[1].runtime_h < longer.campaigns[1].runtime_h - 5.0


def test_coupled_groups_reject_mixed_start_hours(calibrated):
    wl1, wl2, m = calibrated
    cases = [SweepCase(BASELINE, wl1, m, start_hour=9.0),
             SweepCase(BASELINE, wl2, m, start_hour=17.0)]
    with pytest.raises(ValueError, match="start_hour"):
        compile_plan(cases, group_sizes=[2], group_caps_kw=[0.4])


# ---------------------------------------------------------------------------
# Joint optimization (acceptance: joint site CO2 <= independent optima)
# ---------------------------------------------------------------------------
def test_fleet_optimize_beats_independent_under_shared_cap(campaigns):
    """The two-OEM example: joint optimization under a shared cap and
    per-campaign deadlines must find site CO2 <= the independently-
    optimized per-campaign schedules evaluated under the same cap (the
    joint search warm-starts from them and keeps the best seen)."""
    c1, c2 = campaigns
    site = Site(power_cap_kw=0.40, office_kw=0.12)
    fleet = Fleet([c1, c2], site)
    dls = [300.0, 480.0]
    res = fleet.optimize("co2", deadlines=dls, candidates=32, iterations=4,
                         steps=40)
    assert len(res.schedules) == 2 and len(res.independent) == 2
    # evaluate the independent optima as a fleet under the same cap
    wl1, m1 = c1.calibrated()
    wl2, m2 = c2.calibrated()
    cases = [SweepCase(r.schedule, wl, mach, site.bands, GridCarbonModel(),
                       9.0, label=r.schedule.name, deadline_h=d)
             for r, (wl, mach), d in zip(res.independent,
                                         ((wl1, m1), (wl2, m2)), dls)]
    ind = fleet_sweep([cases], site, names=["independent"])[0]
    assert res.site.co2_kg <= ind.site.co2_kg + 1e-9
    # joint result is feasible and engine-reported
    for r, d in zip(res.results, dls):
        assert r.runtime_h <= d * 1.02
    assert res.site.peak_kw is not None
    assert float(np.max(res.metrics.unfinished)) < 1e-6


def test_fleet_objective_peak_constraint_plans_around_budget(calibrated):
    """Planning mode: no physical cap, but a site_peak_kw constraint —
    the optimizer must return a schedule whose (uncoupled) peak draw
    respects the budget that free-running baselines exceed."""
    from repro.core.optimize import optimize_fleet
    wl1, wl2, m = calibrated
    cases = [SweepCase(BASELINE, wl1, m, deadline_h=320.0),
             SweepCase(BASELINE, wl2, m, deadline_h=500.0)]
    budget = 0.52
    free = fleet_sweep([_fleet_cases((wl1, wl2, m), (BASELINE, BASELINE))],
                       Site(power_cap_kw=5.0))[0]
    assert free.site.peak_kw > budget    # baselines bust the budget
    res = optimize_fleet(cases, Site(), objective="co2",
                         constraints={"site_peak_kw": budget},
                         init=0.6, candidates=32, iterations=4, steps=60)
    assert float(res.metrics.site_peak_kw) <= budget * 1.02
    assert float(np.max(res.metrics.unfinished)) < 1e-6


# ---------------------------------------------------------------------------
# Satellites
# ---------------------------------------------------------------------------
def test_scan_stats_reset_and_plan_cache_hits_on_repeated_fleet_sweep(
        calibrated):
    """Two identical fleet sweeps: the second must hit the per-case
    compile cache for every case, and `scan_stats(reset=True)` must
    hand back the pre-reset snapshot while zeroing the live counters."""
    cases = _fleet_cases(calibrated, (BASELINE, PEAK_AWARE_BOOSTED))
    fleet_sweep([cases], SITE)               # warm the plan cache
    reset_scan_stats()
    fleet_sweep([cases], SITE)
    snap = scan_stats(reset=True)
    assert snap.plan_hits >= len(cases) and snap.plan_misses == 0
    assert snap.grouped_lanes > 0            # coupled kernel ran
    assert snap.chunks > 0
    after = scan_stats()
    assert after.slot_work == 0 and after.chunks == 0
    assert after.grouped_lanes == 0 and after.plan_hits == 0
    assert after.jit_compiles == 0


def test_grouped_lanes_counter_zero_for_plain_sweeps(calibrated):
    wl1, _, m = calibrated
    reset_scan_stats()
    from repro.core.engine_jax import trace_sweep
    trace_sweep([SweepCase(BASELINE, wl1, m, carbon=_week_trace())])
    assert scan_stats(reset=True).grouped_lanes == 0


def test_sweep_dedupes_duplicate_names_and_rejects_empty(campaigns):
    c1, _ = campaigns
    dup = [constant_schedule(0.5, name="same"),
           constant_schedule(0.9, name="same"),
           constant_schedule(0.7, name="same")]
    rows = c1.sweep(dup)
    assert [r.policy for r in rows] == ["same", "same#1", "same#2"]
    assert len({r.policy for r in rows}) == 3
    with pytest.raises(ValueError, match="at least one schedule"):
        c1.sweep([])
    with pytest.raises(ValueError, match="at least one schedule"):
        c1.frontier([])
    front = c1.frontier(dup)
    assert [r.policy for r in front] == ["same", "same#1", "same#2"]
    fleet = Fleet([c1])
    with pytest.raises(ValueError, match="at least one assignment"):
        fleet.sweep([])
    frows = fleet.sweep([constant_schedule(0.5, name="dup"),
                         constant_schedule(0.9, name="dup")])
    assert [fr.policy for fr in frows] == ["dup", "dup#1"]


def test_dedupe_names_helper():
    assert dedupe_names(["a", "b", "a", "a"]) == ["a", "b", "a#1", "a#2"]
    assert dedupe_names([]) == []


def test_trace_windows_edge_cases():
    series = np.arange(48.0)
    # window exactly the archive: one member
    ens = trace_windows(series, window_h=48)
    assert len(ens) == 1
    assert ens.member(0).values == tuple(series)
    # window longer than the archive: a clear error
    with pytest.raises(ValueError, match="shorter than one"):
        trace_windows(series, window_h=49)
    # stride > window: gaps are legal, members skip data between windows
    ens = trace_windows(series, window_h=12, stride_h=24)
    assert len(ens) == 2
    assert ens.member(1).values[0] == 24.0
    # non-integer-hour archive lengths (not a whole number of days)
    ens = trace_windows(np.arange(31.0), window_h=10, stride_h=7)
    assert len(ens) == 4
    assert ens.member(3).values == tuple(np.arange(21.0, 31.0))
    # invalid strides fail loudly
    with pytest.raises(ValueError, match="positive"):
        trace_windows(series, window_h=0)
    with pytest.raises(ValueError, match="positive"):
        trace_windows(series, window_h=12, stride_h=0)


def test_fleet_sweep_with_carbon_ensemble_rolls_up_site_stats(campaigns):
    """Ensemble + fleet: per-campaign rows carry EnsembleStats, and the
    site rollup sums per-member CO2 across campaigns (same member
    alignment), uncapped so the lanes stay independent."""
    c1, c2 = campaigns
    ens = trace_windows(np.asarray(_week_trace().values) * 1.0,
                        window_h=24 * 5, stride_h=24)
    fleet = Fleet([c1, c2])
    fr = fleet.sweep([BASELINE], carbon_ensemble=ens)[0]
    assert all(r.co2_ensemble is not None for r in fr.campaigns)
    assert fr.site.co2_ensemble is not None
    total = np.sum([r.co2_ensemble.samples for r in fr.campaigns], axis=0)
    assert abs(fr.site.co2_ensemble.mean - total.mean()) < 1e-12
    assert abs(fr.site.co2_kg
               - sum(r.co2_kg for r in fr.campaigns)) < 1e-9


def test_coupled_fleet_rejects_carbon_dependent_ensemble(calibrated):
    wl1, wl2, m = calibrated
    ens = trace_windows(np.asarray(_week_trace().values), window_h=24 * 5,
                        stride_h=48)
    scheds = carbon_gated_cap(0.45).for_fleet(2)
    cases = [SweepCase(s, wl, m, carbon=ens)
             for s, wl in zip(scheds, (wl1, wl2))]
    with pytest.raises(ValueError, match="cannot share a site cap"):
        compile_plan(cases, group_sizes=[2], group_caps_kw=[0.4])


def test_dashboard_renders_ensemble_whiskers_and_site_rollup(
        campaigns, tmp_path):
    from repro.core.dashboard import render_frontier_dashboard
    c1, c2 = campaigns
    ens = trace_windows(np.asarray(_week_trace().values), window_h=24 * 5,
                        stride_h=24)
    fleet = Fleet([c1, c2])
    frs = fleet.sweep([BASELINE, PEAK_AWARE_BOOSTED], carbon_ensemble=ens)
    rows = [r for fr in frs for r in fr.campaigns]
    md = render_frontier_dashboard(
        rows, str(tmp_path), title="fleet test",
        site_rollups=[(fr.policy, fr.site) for fr in frs])
    assert "±" in md and "…" in md          # mean ±std [q05…q95]
    assert "Site rollup" in md
    assert "makespan" in md
    assert (tmp_path / "frontier.md").exists()
    assert (tmp_path / "frontier.json").exists()
    # plain (no-ensemble) rows still render the point-value column
    md2 = render_frontier_dashboard(
        [dataclasses.replace(rows[0], co2_ensemble=None, summary=None)],
        str(tmp_path), title="plain")
    assert "±" not in md2


def test_site_validation():
    with pytest.raises(ValueError, match="power_cap_kw"):
        Site(power_cap_kw=-1.0)
    with pytest.raises(ValueError, match="office_kw"):
        Site(office_kw=-0.1)
    s = Site(power_cap_kw=0.5, office_kw=0.2)
    assert s.headroom_kw(3.0) > s.headroom_kw(15.0)   # office peaks midday
    assert Site().headroom_kw(12.0) == math.inf


def test_allocation_schedule_contract():
    from repro.core.schedule import (AllocationSchedule, SchedulingContext)
    a = deadline_weighted_split([100.0, 200.0])
    assert a.n_members() == 2
    with pytest.raises(ValueError, match="campaigns"):
        a.for_fleet(3)
    ctx = SchedulingContext(10.0, "shoulder", 0.15, 0.4, elapsed_h=50.0,
                            progress=0.1)
    d = a.decide_joint([ctx, ctx])
    assert len(d) == 2
    assert d[0].intensity >= d[1].intensity   # tighter deadline -> more urgent
    with pytest.raises(ValueError, match="at least one"):
        AllocationSchedule(())
    b = proportional_split(0.8)
    assert [s.name for s in b.for_fleet(3)].count("const_0.80") == 3
    assert b.decide(ctx).intensity == 0.8


def test_allocation_schedule_degenerate_contexts():
    """Edge contexts never yield NaN or out-of-range demands: zero
    active campaigns mid-horizon, a fully spent cap (site_headroom=0),
    and an office draw already past the cap (negative headroom)."""
    from repro.core.schedule import SchedulingContext
    allocs = (proportional_split(0.8),
              deadline_weighted_split([100.0, 200.0]),
              carbon_gated_cap(0.4))
    ctxs = (
        SchedulingContext(12.0, "shoulder", 0.5, 0.6, n_active=0,
                          site_power_kw=0.0),
        SchedulingContext(12.0, "shoulder", 0.5, 0.6, elapsed_h=10.0,
                          progress=0.5, site_power_kw=5.0,
                          site_headroom=0.0, n_active=2),
        SchedulingContext(12.0, "shoulder", 0.5, 0.6, site_power_kw=9.0,
                          site_headroom=-0.25, n_active=2),
    )
    for a in allocs:
        for ctx in ctxs:
            for d in a.decide_joint([ctx] * a.n_members()):
                assert math.isfinite(d.intensity)
                assert 0.0 <= d.intensity <= 1.0


def test_site_throttle_all_members_finished():
    """With every campaign finished the fleet draw collapses to the
    non-sheddable base: the RATE_EPS guard keeps the step at f=1 (no
    0/0), and a headroom below even the base pins the floor instead of
    dividing by zero — for negative headroom too (office past cap)."""
    assert site_throttle(2.0, 2.0, 3.0) == 1.0
    assert site_throttle(0.0, 0.0, 3.0) == 1.0
    assert site_throttle(2.0, 2.0, 1.0) == 0.05
    assert site_throttle(4.0, 1.0, -0.5) == 0.05
    out = site_throttle(np.array([0.0, 2.0]), np.array([0.0, 2.0]), 3.0,
                        xp=np)
    assert np.allclose(out, 1.0)


def test_fleet_all_campaigns_finish_mid_horizon(calibrated):
    """Shrink both workloads so the whole fleet completes well inside
    the horizon under an active cap: results stay finite, runtimes are
    real, and the site peak still honours the cap after the fleet goes
    idle (office-only draw)."""
    wl1, wl2, m = calibrated
    tiny = (dataclasses.replace(wl1, n_scenarios=wl1.n_scenarios // 60),
            dataclasses.replace(wl2, n_scenarios=wl2.n_scenarios // 60))
    cases = _fleet_cases((tiny[0], tiny[1], m), (BASELINE, BASELINE))
    res = fleet_sweep([cases], SITE)[0]
    for c in res.campaigns:
        assert math.isfinite(c.runtime_h) and 0.0 < c.runtime_h < 24.0
        assert math.isfinite(c.co2_kg) and c.co2_kg > 0
    assert res.site.peak_kw <= SITE.power_cap_kw * 1.05
    assert res.site.peak_kw >= SITE.office_kw
