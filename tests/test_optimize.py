"""Schedule-optimizer tests (the PR-3 acceptance bar):

* gradient search recovers the closed-form optimum of a two-band toy
  case (convex power, no contention, no overhead) to <1%;
* the vmapped population/CEM search matches gradient search on the same
  smooth family;
* `Campaign.optimize` finds a schedule for the OEM case-1 workload under
  a week-long carbon trace whose energy beats every fixed Figure-1
  policy at an equal deadline;
* the ParametricSchedule family, the pure `TraceObjective`/
  `evaluate_params` path (grad/vmap-compatible, engine-consistent), and
  Pareto-frontier extraction.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core import (Campaign, MachineProfile, POLICIES, SweepCase,
                        TimeBands, TraceSignal, HourlySignal, trace_sweep)
from repro.core.engine_jax import TraceObjective, evaluate_params
from repro.core.optimize import (Objective, canonical_metric,
                                 optimize_schedule, pareto_front)
from repro.core.schedule import ParametricSchedule, parametric_schedule
from repro.core.workload import OEM_CASE_1, OEMWorkload


class QuietBands(TimeBands):
    """Background load off: the analytic toy needs u to be the only load."""

    def background(self, band: str) -> float:
        return 0.0


def _toy_case():
    """Two-band toy with a closed-form optimum.

    idle=0, alpha=2, gamma=0, no batch overhead, zero background; carbon
    is c1=1.0 for hours 0-11 and c2=0.2 for 12-23; deadline one day.
    Minimizing CO2 = dyn * sum_i c_i u_i^2 tau_i subject to
    R * sum_i u_i tau_i = W gives u_i ∝ 1/c_i, so
    CO2* = dyn W^2 / (R^2 sum_i tau_i / c_i).
    """
    m = MachineProfile(idle_w=0.0, dyn_w=200.0, alpha=2.0, gamma=0.0)
    wl = OEMWorkload("toy", 388_800, rate_at_full=10.0, batch_overhead_s=0.0)
    carbon = HourlySignal(tuple([1.0] * 12 + [0.2] * 12), name="two-band")
    case = SweepCase(parametric_schedule(24), wl, m, QuietBands(), carbon,
                     start_hour=0.0, deadline_h=24.0)
    tau = 12 * 3600.0
    co2_star = (m.dyn_w * wl.n_scenarios ** 2
                / (wl.rate_at_full ** 2 * tau * (1 / 1.0 + 1 / 0.2))) / 3.6e6
    return case, co2_star


@pytest.fixture(scope="module")
def toy():
    return _toy_case()


@pytest.fixture(scope="module")
def calibrated_oem():
    from repro.core import calibrate_workload
    return calibrate_workload(OEM_CASE_1, MachineProfile())


@pytest.fixture(scope="module")
def week_trace():
    rng = np.random.RandomState(7)
    h = np.arange(168)
    vals = 0.448 * (1.0 + 0.30 * np.sin(2 * np.pi * h / 24.0)
                    + 0.08 * np.sin(2 * np.pi * h / 168.0)
                    + 0.05 * rng.randn(168))
    return TraceSignal(tuple(float(v) for v in vals), name="week")


# ---------------------------------------------------------------------------
# Acceptance: analytic optimum, grad vs population, beats the Figure-1 set
# ---------------------------------------------------------------------------
def test_grad_recovers_analytic_two_band_optimum(toy):
    case, co2_star = toy
    res = optimize_schedule(case, "co2", {"runtime_h": 24.0}, method="grad",
                            u_min=0.02, u_max=1.0, steps=800, lr=0.1,
                            horizon_h=30.0)
    assert res.metrics.unfinished < 1e-9
    assert res.metrics.runtime_h <= 24.0 * 1.005
    assert abs(res.metrics.co2_kg / co2_star - 1) < 0.01
    # and the found structure is the analytic one: u ∝ 1/c per band
    u = res.schedule.intensity_table()
    assert u[:12].mean() < 0.5 * u[12:].mean()


def test_population_matches_grad_on_smooth_family(toy):
    """CEM needs no gradients but must land on the same optimum for the
    smooth per-slot family (within a percent of the analytic value)."""
    case, co2_star = toy
    res = optimize_schedule(case, "co2", {"runtime_h": 24.0}, method="cem",
                            u_min=0.02, u_max=1.0, candidates=256,
                            iterations=60, horizon_h=30.0, seed=1)
    assert res.evaluations >= 256 * 60
    assert res.metrics.runtime_h <= 24.0 * 1.005
    assert abs(res.metrics.co2_kg / co2_star - 1) < 0.01


def test_cem_runs_on_numpy_backend(toy):
    """The population search must not require jax (NumPy scan fallback)."""
    case, _ = toy
    res = optimize_schedule(case, "co2", {"runtime_h": 24.0}, method="cem",
                            u_min=0.02, u_max=1.0, candidates=64,
                            iterations=8, horizon_h=30.0, seed=2,
                            backend="numpy")
    assert res.method == "cem"
    assert res.metrics.unfinished < 1e-9
    # 8 cheap iterations already beat the flat seed
    flat = TraceObjective(case, slots_per_hour=1, horizon_h=30.0,
                          backend="numpy").evaluate_batch(
        np.full((1, 24), 0.6))
    assert res.metrics.co2_kg < float(flat.co2_kg[0])


def test_optimized_beats_six_policies_oem_case1(week_trace):
    """The headline claim: on the OEM case-1 workload under a week-long
    carbon trace, the synthesized schedule's energy is <= the best of the
    six fixed Figure-1 policies given the same deadline."""
    c = Campaign(OEM_CASE_1)
    six = c.sweep(list(POLICIES.values()), carbon_trace=week_trace)
    deadline = max(r.runtime_h for r in six)
    best_six = min(r.energy_kwh for r in six)
    res = c.optimize("energy", deadline_h=deadline, carbon_trace=week_trace,
                     method="auto", candidates=256, iterations=30, steps=400)
    assert res.result.runtime_h <= deadline * 1.005
    assert res.result.energy_kwh <= best_six
    # the optimizer's own metrics agree with the engine's SimResult
    assert abs(res.metrics.energy_kwh / res.result.energy_kwh - 1) < 1e-9
    assert abs(res.metrics.runtime_h / res.result.runtime_h - 1) < 1e-9


# ---------------------------------------------------------------------------
# Objective semantics
# ---------------------------------------------------------------------------
def test_objective_coercion_and_aliases():
    obj = Objective.coerce("co2", {"runtime": 100.0})
    assert obj.weights == {"co2_kg": 1.0}
    assert obj.constraints == {"runtime_h": 100.0}
    obj2 = Objective.coerce({"energy": 1.0, "runtime_h": 0.2})
    assert set(obj2.weights) == {"energy_kwh", "runtime_h"}
    assert canonical_metric("carbon") == "co2_kg"
    with pytest.raises(ValueError, match="unknown metric"):
        Objective.coerce("joules")
    with pytest.raises(ValueError, match="at least one"):
        Objective(weights={})
    with pytest.raises(ValueError, match="positive"):
        Objective(weights={"co2": 1.0}, constraints={"runtime": -5.0})


def test_cost_objective_requires_price(toy):
    case, _ = toy
    with pytest.raises(ValueError, match="price"):
        optimize_schedule(case, "cost", horizon_h=30.0)


def test_runtime_cap_is_respected_as_epsilon_constraint(toy):
    """min energy s.t. a *tight* runtime cap: the cap binds (the
    unconstrained optimum runs slower) and is met within tolerance."""
    case, _ = toy
    res = optimize_schedule(case, "energy", {"runtime_h": 14.0},
                            method="cem", u_min=0.02, u_max=1.0,
                            candidates=128, iterations=40, horizon_h=30.0,
                            seed=3)
    assert res.metrics.runtime_h <= 14.0 * 1.01
    assert res.metrics.unfinished < 1e-9


# ---------------------------------------------------------------------------
# The pure objective path
# ---------------------------------------------------------------------------
def test_trace_objective_is_engine_consistent(toy):
    """TraceObjective.evaluate must reproduce the trace engine's numbers
    exactly for the equivalent ParametricSchedule (same grid + physics)."""
    case, _ = toy
    sched = ParametricSchedule.from_intensities(
        0.3 + 0.4 * np.sin(np.arange(24) / 24 * 2 * np.pi) ** 2,
        u_min=0.02, u_max=1.0, name="wavy")
    to = TraceObjective(case, slots_per_hour=1, horizon_h=60.0)
    mets = to.evaluate_batch(sched.intensity_table()[None, :])
    eng = trace_sweep([dataclasses.replace(case, schedule=sched)])[0]
    assert abs(float(mets.energy_kwh[0]) / eng.energy_kwh - 1) < 1e-9
    assert abs(float(mets.co2_kg[0]) / eng.co2_kg - 1) < 1e-9
    assert abs(float(mets.runtime_h[0]) / eng.runtime_h - 1) < 1e-9
    assert abs(float(mets.unfinished[0])) < 1e-12


def test_evaluate_params_grad_and_vmap_compatible(toy):
    import jax
    import jax.numpy as jnp

    from repro.compat import enable_x64

    case, _ = toy
    with enable_x64():
        g = jax.grad(lambda p: evaluate_params(p, case,
                                               horizon_h=30.0).co2_kg)(
            jnp.zeros(24))
        assert g.shape == (24,)
        assert bool(jnp.isfinite(g).all())
        assert float(jnp.abs(g).max()) > 0.0
        to = TraceObjective(case, slots_per_hour=1, horizon_h=30.0)
        U = jnp.asarray(np.linspace(0.3, 0.9, 5)[:, None]
                        * np.ones((5, 24)))
        mets = jax.vmap(lambda u: to.evaluate(u))(U)
        assert mets.energy_kwh.shape == (5,)
        # more intensity, faster finish
        rts = np.asarray(mets.runtime_h)
        assert (np.diff(rts) < 0).all()


def test_unfinished_is_reported_not_grown(toy):
    """A schedule that cannot finish inside the horizon reports
    unfinished > 0 instead of growing the grid (no retry inside the
    objective)."""
    case, _ = toy
    to = TraceObjective(case, slots_per_hour=1, horizon_h=6.0)
    mets = to.evaluate_batch(np.full((1, 24), 0.1))
    assert float(mets.unfinished[0]) > 0.5
    assert float(mets.runtime_h[0]) == pytest.approx(6.0)


# ---------------------------------------------------------------------------
# Pareto extraction
# ---------------------------------------------------------------------------
def test_pareto_front_mask():
    pts = np.array([[1.0, 5.0], [2.0, 3.0], [3.0, 4.0], [4.0, 1.0],
                    [2.5, 3.0]])
    mask = pareto_front(pts)
    assert mask.tolist() == [True, True, False, True, False]
    # K>2 fallback agrees on the same points (third objective constant)
    pts3 = np.hstack([pts, np.ones((5, 1))])
    assert pareto_front(pts3).tolist() == mask.tolist()


def test_cem_pareto_frontier_attached(toy):
    case, _ = toy
    res = optimize_schedule(case, "co2", {"runtime_h": 24.0}, method="cem",
                            u_min=0.02, u_max=1.0, candidates=96,
                            iterations=12, horizon_h=30.0, seed=4,
                            pareto=True)
    assert len(res.frontier) >= 2
    rts = [r.runtime_h for r in res.frontier]
    co2 = [r.co2_kg for r in res.frontier]
    assert rts == sorted(rts)                  # sorted by runtime …
    assert co2 == sorted(co2, reverse=True)    # … and non-dominated


# ---------------------------------------------------------------------------
# ParametricSchedule family
# ---------------------------------------------------------------------------
def test_parametric_schedule_round_trip_and_protocol():
    u_in = np.linspace(0.1, 0.9, 24)
    s = ParametricSchedule.from_intensities(u_in, name="rt")
    assert np.allclose(s.intensity_table(), u_in, atol=1e-6)
    # decide() and decide_grid() agree on the same grid
    from repro.core.schedule import SchedulingContext
    hod = np.arange(24, dtype=float)
    ctx = SchedulingContext(hour_of_day=hod[:, None], band="",
                            background=0.0, carbon_factor=0.0)
    u_grid, b_grid = s.decide_grid(ctx)
    for h in range(24):
        d = s.decide(SchedulingContext(hour_of_day=float(h), band="",
                                       background=0.0, carbon_factor=0.0))
        assert d.intensity == pytest.approx(float(u_grid[h, 0]))
        assert d.batch_size == 50
    # sub-hour slots advertise sub-hour change hours
    s48 = parametric_schedule(48)
    assert 0.5 in s48.change_hours(TimeBands())
    assert math.isclose(max(s48.change_hours(TimeBands())), 24.0)
    with pytest.raises(ValueError, match="divide the day"):
        ParametricSchedule(tuple(np.zeros(7)))
    with pytest.raises(ValueError, match="u_min"):
        ParametricSchedule(tuple(np.zeros(24)), u_min=0.9, u_max=0.5)


def test_optimizer_quantizes_to_levels(toy):
    """Snapped tables are *exact* members of the level set, including
    levels at the range endpoints (a logit round trip cannot represent
    those bit-exactly — regression for the from_intensities clip)."""
    case, _ = toy
    levels = (0.1, 0.3, 0.5, 0.7, 1.0)
    res = optimize_schedule(case, "co2", {"runtime_h": 24.0}, method="cem",
                            u_min=0.02, u_max=1.0, candidates=64,
                            iterations=10, horizon_h=30.0, seed=5,
                            levels=levels)
    u = res.schedule.intensity_table()
    assert all(any(v == l for l in levels) for v in u)
    # candidates are snapped BEFORE evaluation, so the search optimized
    # the quantized objective and its constraints hold for the result
    assert res.metrics.runtime_h <= 24.0 * 1.01
    assert res.metrics.unfinished < 1e-9
    # the engine-reported result reflects the snapped table
    eng = trace_sweep([dataclasses.replace(case, schedule=res.schedule)])[0]
    assert abs(eng.energy_kwh / res.result.energy_kwh - 1) < 1e-12


def test_parametric_slot_lookup_with_non_binary_slot_width(calibrated_oem):
    """n_slots=120 (12-minute slots, width 0.2 h — not binary-
    representable): slot-edge grid hours must not truncate one slot low;
    engine vs sequential stays at the 1e-9 contract."""
    wl, m = calibrated_oem
    rng = np.random.RandomState(3)
    ps = ParametricSchedule.from_intensities(
        0.25 + 0.7 * rng.rand(120), name="p120")
    from repro.core import simulate_campaign, sweep
    r = sweep([SweepCase(ps, wl, m)])[0]
    seq = simulate_campaign(wl, ps, m)
    assert abs(r.energy_kwh / seq.energy_kwh - 1) < 1e-9
    assert abs(r.runtime_h / seq.runtime_h - 1) < 1e-9


def test_cem_candidates_validated(toy):
    case, _ = toy
    with pytest.raises(ValueError, match="candidates"):
        optimize_schedule(case, "co2", method="cem", candidates=1,
                          horizon_h=30.0)
    # levels need the quantized (population) search: snapping a smooth
    # gradient optimum afterwards could silently violate constraints
    with pytest.raises(ValueError, match="population"):
        optimize_schedule(case, "co2", method="grad", levels=(0.2, 0.9),
                          horizon_h=30.0)


def test_campaign_optimize_warm_starts_from_parametric_incumbent():
    """Re-optimizing a campaign whose schedule is already a
    ParametricSchedule must refine the incumbent, not restart flat: even
    a tiny budget returns a result no worse than the incumbent."""
    c0 = Campaign(OEM_CASE_1)
    first = c0.optimize("energy", deadline_h=210.0, method="cem",
                        candidates=64, iterations=10)
    c1 = Campaign(OEM_CASE_1, first.schedule)
    again = c1.optimize("energy", deadline_h=210.0, method="cem",
                        candidates=16, iterations=2, init_std=0.05)
    assert again.result.energy_kwh <= first.result.energy_kwh * 1.0001


def test_campaign_optimize_canonicalizes_constraint_aliases():
    """An aliased runtime cap ('runtime'/'deadline') must win over the
    deadline_h shorthand instead of being silently overridden."""
    c = Campaign(OEM_CASE_1)
    res = c.optimize("co2", constraints={"runtime": 150.0}, deadline_h=200.0,
                     method="cem", candidates=32, iterations=4)
    assert res.objective.constraints == {"runtime_h": 150.0}
    res2 = c.optimize("co2", constraints={"deadline": 150.0}, method="cem",
                      candidates=32, iterations=4)
    assert res2.objective.constraints == {"runtime_h": 150.0}


def test_campaign_optimize_smoke_and_deltas():
    """Session surface: constraints shorthand, warm start from the
    campaign schedule, delta columns vs the calibrated baseline."""
    c = Campaign(OEM_CASE_1)
    res = c.optimize("energy", deadline_h=200.0, method="cem",
                     candidates=48, iterations=6, deltas=True)
    assert res.result.policy.startswith("optimized[")
    assert res.objective.constraints == {"runtime_h": 200.0}
    assert res.result.energy_delta_pct != 0.0
    # the result schedule is a drop-in Schedule for any sweep
    again = c.sweep([res.schedule])[0]
    assert abs(again.energy_kwh / res.result.energy_kwh - 1) < 1e-9
