"""Persistent plan cache + incremental delta sweeps (the perf_opt
acceptance bar):

* a fresh-process re-sweep of an identical fleet batch hits the disk
  cache with zero classification/lowering work (`plan_misses == 0`,
  `disk_hits >= n_cases`), results bitwise vs the cold compile;
* `delta_sweep` with 1 changed schedule of S=100 recomputes <= 2% of
  the full sweep's `slot_work`, spliced results bitwise-equal to a
  full re-sweep, coupled groups re-scan whole;
* satellites: true-LRU in-memory memo (hit refreshes recency),
  opaque-fingerprint schedules bypass both layers without poisoning
  the store, corrupted entries and schema-version drift recompile
  instead of crashing, `plan_cache_info`/`clear_plan_cache` reset the
  new counters, and the disk store's size-bounded LRU eviction;
* carbon-blind keys: a schedule whose type declares `carbon_blind`
  hits the memo under a new forecast, bitwise equal to a cold compile;
  a carbon-consulting schedule (a subclass of a declared family
  included: the flag is not inherited) misses however its probe read
  the first forecast; a schedule that declares the flag but reads
  carbon raises.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (MachineProfile, SweepCase, TraceSignal,
                        as_ensemble, calibrate_workload, constant_schedule,
                        trace_sweep)
from repro.core import engine_jax as ej
from repro.core import plancache
from repro.core.policy import BANDS, Policy, hourly_schedule
from repro.core.schedule import (CarbonGateSchedule, Decision,
                                 FunctionSchedule, ParametricSchedule,
                                 deadline_schedule)
from repro.core.workload import OEM_CASE_1

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def calibrated():
    return calibrate_workload(OEM_CASE_1, MachineProfile())


@pytest.fixture(autouse=True)
def _no_env_cache(monkeypatch):
    """Keep ambient CARINA_PLAN_CACHE* out of every test: plan caching
    is exercised only through explicit cache_dir= arguments here."""
    monkeypatch.delenv("CARINA_PLAN_CACHE", raising=False)
    monkeypatch.delenv("CARINA_PLAN_CACHE_MB", raising=False)


def _res_key(r):
    return (r.runtime_h, r.energy_kwh, r.co2_kg, r.cost_usd)


def _week_trace(seed: int = 3) -> TraceSignal:
    rng = np.random.RandomState(seed)
    h = np.arange(96)
    vals = 0.45 * (1.0 + 0.3 * np.sin(2 * np.pi * h / 24.0)
                   + 0.05 * rng.rand(96))
    return TraceSignal(tuple(float(v) for v in vals), name=f"trace{seed}")


def _cases(calibrated, n, scenarios=600.0):
    """n distinct small cases (distinct constant schedules, one shared
    non-periodic trace)."""
    wl, m = calibrated
    wl = dataclasses.replace(wl, n_scenarios=float(scenarios))
    trace = _week_trace()
    us = np.linspace(0.35, 1.0, n)
    return [SweepCase(constant_schedule(float(u)), wl, m, carbon=trace,
                      label=f"u{j}")
            for j, u in enumerate(us)]


# ---------------------------------------------------------------------------
# Acceptance: disk warm start does zero classification/lowering work
# ---------------------------------------------------------------------------
def test_disk_cache_warm_start_zero_work_bitwise(calibrated, tmp_path):
    cases = _cases(calibrated, 5)
    d = str(tmp_path / "store")
    ej.clear_plan_cache()
    cold = trace_sweep(cases, cache_dir=d, backend="numpy")
    s = ej.scan_stats()
    assert s.plan_misses == len(cases)
    assert s.disk_misses == len(cases)
    # simulate a fresh process: the in-memory memo is gone, disk stays
    ej.clear_plan_cache()
    warm = trace_sweep(cases, cache_dir=d, backend="numpy")
    s = ej.scan_stats()
    assert s.plan_misses == 0, "warm start must not compile anything"
    assert s.disk_hits >= len(cases)
    for a, b in zip(cold, warm):
        assert _res_key(a) == _res_key(b)


def test_fleet_warm_start_across_processes(calibrated, tmp_path):
    """The roadmap pin, for real: a second identical coupled fleet
    sweep in a *fresh python process* does zero classification/lowering
    work and reproduces the cold results bitwise."""
    d = str(tmp_path / "store")
    script = textwrap.dedent("""
        import dataclasses, json, sys
        import numpy as np
        from repro.core import (MachineProfile, Site, SweepCase,
                                TraceSignal, calibrate_workload,
                                constant_schedule, fleet_sweep)
        from repro.core import engine_jax as ej
        from repro.core.workload import OEM_CASE_1

        wl, m = calibrate_workload(OEM_CASE_1, MachineProfile())
        wl = dataclasses.replace(wl, n_scenarios=600.0)
        rng = np.random.RandomState(3)
        h = np.arange(96)
        vals = 0.45 * (1.0 + 0.3 * np.sin(2 * np.pi * h / 24.0)
                       + 0.05 * rng.rand(96))
        trace = TraceSignal(tuple(float(v) for v in vals), name="trace3")
        groups = [[SweepCase(constant_schedule(u), wl, m, carbon=trace,
                             label=f"u{j}")
                   for j, u in enumerate((0.5, 0.8, 1.0))]]
        site = Site(power_cap_kw=2.0)
        res = fleet_sweep(groups, site, backend="numpy",
                          cache_dir=sys.argv[1])
        s = ej.scan_stats()
        print(json.dumps({
            "co2": [r.co2_kg for r in res[0].campaigns],
            "runtime": [r.runtime_h for r in res[0].campaigns],
            "plan_misses": s.plan_misses, "disk_hits": s.disk_hits}))
    """)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu")
    env.pop("CARINA_PLAN_CACHE", None)
    runs = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", script, d], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    assert cold["plan_misses"] == 3 and cold["disk_hits"] == 0
    assert warm["plan_misses"] == 0, "fresh process must warm-start"
    assert warm["disk_hits"] >= 3
    assert warm["co2"] == cold["co2"]
    assert warm["runtime"] == cold["runtime"]


def test_xla_compilation_cache_warm_across_processes(tmp_path):
    """The persistent *XLA* compilation cache goes where
    ``JAX_COMPILATION_CACHE_DIR`` says (wired by compile_plan through
    `repro.compat.enable_persistent_compilation_cache`).  The plan
    store skips re-*lowering*; this skips re-*compiling* the jitted
    scan itself.  A fresh process re-running the same sweep must load
    its executable from disk: cold = compilation-cache misses + files
    written, warm = hits with zero misses, results bitwise.  The
    children stay on the CPU."""
    d = str(tmp_path / "store")
    script = textwrap.dedent("""
        import dataclasses, glob, json, os, sys
        from jax._src import monitoring

        counts = {"misses": 0, "hits": 0}

        def _listen(event, *a, **kw):
            if event.endswith("cache_misses"):
                counts["misses"] += 1
            elif event.endswith("cache_hits"):
                counts["hits"] += 1

        monitoring.register_event_listener(_listen)

        from repro.core import (MachineProfile, SweepCase, TraceSignal,
                                calibrate_workload, constant_schedule,
                                trace_sweep)
        from repro.core.workload import OEM_CASE_1

        wl, m = calibrate_workload(OEM_CASE_1, MachineProfile())
        wl = dataclasses.replace(wl, n_scenarios=40_000.0)
        trace = TraceSignal(tuple([0.4] * 72), name="flat")
        res = trace_sweep([SweepCase(constant_schedule(0.8), wl, m,
                                     carbon=trace)],
                          cache_dir=sys.argv[1])
        xla = os.environ["JAX_COMPILATION_CACHE_DIR"]
        files = [p for p in glob.glob(os.path.join(xla, "**", "*"),
                                      recursive=True) if os.path.isfile(p)]
        print(json.dumps({"misses": counts["misses"],
                          "hits": counts["hits"], "files": len(files),
                          "co2": res[0].co2_kg}))
    """)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    env.pop("CARINA_PLAN_CACHE", None)
    runs = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", script, d], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    assert cold["misses"] > 0 and cold["hits"] == 0
    assert cold["files"] > 0, "the cold run must persist its executable"
    assert warm["misses"] == 0, "a fresh process must not recompile"
    assert warm["hits"] > 0
    assert warm["co2"] == cold["co2"]


@pytest.mark.parametrize("env_set", [True, False],
                         ids=["env", "default"])
def test_env_var_jax_cache_override(calibrated, tmp_path, monkeypatch,
                                    env_set):
    """After a sweep, jax's persistent compilation cache sits where
    ``JAX_COMPILATION_CACHE_DIR`` says; without it, at the fixed path
    inside the checkout, which git ignores."""
    import jax

    from repro import compat
    if env_set:
        want = str(tmp_path / "elsewhere")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        assert compat.DEFAULT_COMPILATION_CACHE_DIR == want
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    before = jax.config.jax_compilation_cache_dir
    try:
        ej.trace_sweep(_cases(calibrated, 1), backend="numpy")
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_corrupted_entries_recompile_never_crash(calibrated, tmp_path):
    cases = _cases(calibrated, 3)
    d = str(tmp_path / "store")
    ej.clear_plan_cache()
    cold = trace_sweep(cases, cache_dir=d, backend="numpy")
    cache = plancache.get_cache(d)
    entries = cache._entries()
    assert entries, "the store should hold entries after a cold sweep"
    for e in entries:
        with open(e.path, "wb") as f:
            f.write(b"not an npz archive")
    ej.clear_plan_cache()
    again = trace_sweep(cases, cache_dir=d, backend="numpy")
    s = ej.scan_stats()
    assert s.plan_misses == len(cases), "corrupt entries must recompile"
    for a, b in zip(cold, again):
        assert _res_key(a) == _res_key(b)
    # the corrupt files were dropped and replaced by fresh writes
    for e in cache._entries():
        with open(e.path, "rb") as f:
            assert f.read(2) == b"PK"


def test_schema_version_salt_invalidates(calibrated, tmp_path, monkeypatch):
    cases = _cases(calibrated, 2)
    d = str(tmp_path / "store")
    ej.clear_plan_cache()
    trace_sweep(cases, cache_dir=d, backend="numpy")
    monkeypatch.setattr(plancache, "SCHEMA_VERSION",
                        plancache.SCHEMA_VERSION + 1)
    ej.clear_plan_cache()
    trace_sweep(cases, cache_dir=d, backend="numpy")
    s = ej.scan_stats()
    assert s.disk_hits == 0, "a version bump must orphan old entries"
    assert s.plan_misses == len(cases)


def test_opaque_schedule_bypasses_both_layers(calibrated, tmp_path):
    """A closure-bearing schedule has no value identity: it must
    compile fresh every time (no memo hit, no disk entry — the store
    cannot be poisoned by an object that can change behind its key)."""
    wl, m = calibrated
    wl = dataclasses.replace(wl, n_scenarios=400.0)
    knob = {"u": 0.7}
    sched = FunctionSchedule("closure", lambda ctx: knob["u"])
    case = SweepCase(sched, wl, m, carbon=_week_trace())
    d = str(tmp_path / "store")
    ej.clear_plan_cache()
    r1 = trace_sweep([case], cache_dir=d, backend="numpy")
    r2 = trace_sweep([case], cache_dir=d, backend="numpy")
    s = ej.scan_stats()
    assert s.plan_hits == 0 and s.disk_hits == 0
    assert s.plan_misses == 2, "opaque cases compile fresh every sweep"
    assert plancache.get_cache(d).info() == (0, 0), "no entry stored"
    assert _res_key(r1[0]) == _res_key(r2[0])
    # the closure really is live: mutating it changes the next sweep
    knob["u"] = 0.4
    r3 = trace_sweep([case], cache_dir=d, backend="numpy")
    assert r3[0].runtime_h > r1[0].runtime_h


def test_memo_true_lru_hit_refreshes_recency(calibrated, monkeypatch):
    """Regression for the insertion-order eviction bug: an entry hit
    recently must survive the eviction sweep even if it was compiled
    first."""
    monkeypatch.setattr(ej, "_PLAN_CACHE_SIZE", 4)
    cases = _cases(calibrated, 5)
    ej.clear_plan_cache()
    trace_sweep([cases[0]], backend="numpy")     # oldest by insertion
    for c in cases[1:4]:
        trace_sweep([c], backend="numpy")        # memo now full (4)
    trace_sweep([cases[0]], backend="numpy")     # hit -> young end
    assert ej.scan_stats().plan_hits == 1
    trace_sweep([cases[4]], backend="numpy")     # evicts oldest quarter
    ej._STATS.plan_hits = 0
    ej._STATS.plan_misses = 0
    trace_sweep([cases[0]], backend="numpy")
    s = ej.scan_stats()
    assert s.plan_hits == 1 and s.plan_misses == 0, \
        "the recently-hit entry must have survived eviction"
    # and the insertion-order victim is really gone
    trace_sweep([cases[1]], backend="numpy")
    assert ej.scan_stats().plan_misses == 1


def test_disk_lru_eviction_bounds_store(calibrated, tmp_path):
    cases = _cases(calibrated, 12)
    d = str(tmp_path / "store")
    ej.clear_plan_cache()
    cold = trace_sweep(cases, cache_dir=d, backend="numpy")
    cache = plancache.get_cache(d)
    n0, bytes0 = cache.info()
    assert n0 > 0
    # shrink the bound below the current footprint and trigger a sweep
    small = plancache.PlanCache(d, max_bytes=max(bytes0 // 2, 1))
    small._evict()
    n1, bytes1 = small.info()
    assert bytes1 <= small.max_bytes
    assert n1 < n0, "the oldest entries must have been swept"
    # a sweep against the thinned store still works (partial hits +
    # recompiles) and stays bitwise
    ej.clear_plan_cache()
    warm = trace_sweep(cases, cache_dir=d, backend="numpy")
    for a, b in zip(cold, warm):
        assert _res_key(a) == _res_key(b)


def test_plan_cache_info_and_clear(calibrated, tmp_path):
    cases = _cases(calibrated, 4)
    d = str(tmp_path / "store")
    ej.clear_plan_cache()
    trace_sweep(cases, cache_dir=d, backend="numpy")
    ej.clear_plan_cache()                        # memo gone, disk stays
    trace_sweep(cases, cache_dir=d, backend="numpy")
    info = ej.plan_cache_info(cache_dir=d)
    assert info.mem_entries == len(cases) and info.mem_bytes > 0
    assert info.disk_entries > 0 and info.disk_bytes > 0
    assert info.hits >= len(cases) and info.misses == 0
    assert info.hit_rate == 1.0
    ej.clear_plan_cache()
    s = ej.scan_stats()
    assert (s.plan_hits, s.plan_misses, s.disk_hits, s.disk_misses,
            s.lanes_recomputed, s.lanes_spliced) == (0, 0, 0, 0, 0, 0)
    info = ej.plan_cache_info(cache_dir=d)
    assert info.mem_entries == 0 and info.hit_rate == 0.0
    assert info.disk_entries > 0, "clear_plan_cache leaves disk alone"


# ---------------------------------------------------------------------------
# Acceptance: delta_sweep recomputes ~K/S of the slot work, bitwise
# ---------------------------------------------------------------------------
def test_delta_sweep_1_of_100_slot_work_and_bitwise(calibrated):
    S = 100
    cases = _cases(calibrated, S)
    plan = ej.compile_plan(cases)
    ej.reset_scan_stats()
    state = ej.execute_plan(plan, backend="numpy")
    base_work = ej.scan_stats().slot_work
    prev = ej.summarize_plan(plan, state)

    new_sched = constant_schedule(0.42)
    ej.reset_scan_stats()
    delta = ej.delta_sweep(plan, prev, schedules={7: new_sched},
                           backend="numpy")
    s = ej.scan_stats()
    assert s.lanes_recomputed == 1 and s.lanes_spliced == S - 1
    assert s.slot_work <= 0.02 * base_work, (
        f"1-of-{S} delta re-scanned {s.slot_work}/{base_work} slot units")
    assert delta.recomputed == (7,)
    assert len(delta.spliced) == S - 1

    full_cases = list(cases)
    full_cases[7] = dataclasses.replace(cases[7], schedule=new_sched)
    ref = trace_sweep(full_cases, backend="numpy")
    for a, b in zip(delta.results, ref):
        assert _res_key(a) == _res_key(b)
    # the returned plan is the delta base for the *next* cycle
    assert delta.plan.cases[7].schedule is new_sched


def test_delta_sweep_noop_delta_splices_everything(calibrated):
    cases = _cases(calibrated, 6)
    plan = ej.compile_plan(cases)
    prev = ej.summarize_plan(plan, ej.execute_plan(plan, backend="numpy"))
    ej.reset_scan_stats()
    # an "update" that fingerprints identically to the incumbent —
    # e.g. the orchestrator re-sends every schedule each cycle
    delta = ej.delta_sweep(plan, prev,
                           schedules=[c.schedule for c in cases],
                           backend="numpy")
    s = ej.scan_stats()
    assert delta.recomputed == ()
    assert s.lanes_recomputed == 0 and s.lanes_spliced == plan.n_lanes
    assert s.slot_work == 0, "a value-identical delta must scan nothing"
    assert [_res_key(r) for r in delta.results] == \
        [_res_key(r) for r in prev]


def test_delta_sweep_carbon_delta_rescans_its_cases(calibrated):
    cases = _cases(calibrated, 4)
    plan = ej.compile_plan(cases)
    prev = ej.summarize_plan(plan, ej.execute_plan(plan, backend="numpy"))
    new_trace = _week_trace(seed=11)
    ej.reset_scan_stats()
    delta = ej.delta_sweep(plan, prev, carbon={2: new_trace},
                           backend="numpy")
    assert delta.recomputed == (2,)
    full_cases = list(cases)
    full_cases[2] = dataclasses.replace(cases[2], carbon=new_trace)
    ref = trace_sweep(full_cases, backend="numpy")
    for a, b in zip(delta.results, ref):
        assert _res_key(a) == _res_key(b)


def test_delta_sweep_coupled_group_rescans_whole(calibrated):
    """A changed member of a site-capped group drags the whole group
    into the re-scan (lanes interact through the cap every slot);
    uncapped cases in the same plan still splice."""
    cases = _cases(calibrated, 5)
    plan = ej.compile_plan(cases, group_sizes=[3, 2],
                           group_caps_kw=[2.0, None])
    prev = ej.summarize_plan(plan, ej.execute_plan(plan, backend="numpy"))
    new_sched = constant_schedule(0.55)
    ej.reset_scan_stats()
    delta = ej.delta_sweep(plan, prev, schedules={0: new_sched},
                           backend="numpy")
    s = ej.scan_stats()
    assert delta.recomputed == (0, 1, 2), "the capped group goes whole"
    assert delta.spliced == (3, 4)
    assert s.lanes_recomputed == 3 and s.lanes_spliced == 2
    full_cases = list(cases)
    full_cases[0] = dataclasses.replace(cases[0], schedule=new_sched)
    full_plan = ej.compile_plan(full_cases, group_sizes=[3, 2],
                                group_caps_kw=[2.0, None])
    ref = ej.summarize_plan(full_plan,
                            ej.execute_plan(full_plan, backend="numpy"))
    for a, b in zip(delta.results, ref):
        assert _res_key(a) == _res_key(b)


def test_delta_sweep_revalidates_ensemble_width(calibrated):
    wl, m = calibrated
    wl = dataclasses.replace(wl, n_scenarios=400.0)
    ens = as_ensemble([_week_trace(1), _week_trace(2)], name="e2")
    cases = [SweepCase(constant_schedule(0.8), wl, m, carbon=ens)]
    plan = ej.compile_plan(cases)
    prev = ej.summarize_plan(plan, ej.execute_plan(plan, backend="numpy"))
    with pytest.raises(ValueError, match="ensemble width"):
        ej.delta_sweep(plan, prev, carbon={0: _week_trace(9)},
                       backend="numpy")


def test_delta_sweep_rejects_mismatched_results(calibrated):
    cases = _cases(calibrated, 3)
    plan = ej.compile_plan(cases)
    prev = ej.summarize_plan(plan, ej.execute_plan(plan, backend="numpy"))
    with pytest.raises(ValueError, match="full result list"):
        ej.delta_sweep(plan, prev[:-1], schedules={0: constant_schedule(0.5)})


def test_subset_plan_refuses_split_coupled_group(calibrated):
    cases = _cases(calibrated, 3)
    plan = ej.compile_plan(cases, group_sizes=[3], group_caps_kw=[2.0])
    with pytest.raises(ValueError, match="whole"):
        ej._subset_plan(plan, [1])


# ---------------------------------------------------------------------------
# Carbon-blind keys: the recurring refresh re-scores the same candidates
# against each new forecast
# ---------------------------------------------------------------------------
def _forecast(seed: int, members: int = 3, hours: int = 96,
              level: float = 0.45, swing: float = 0.3):
    """A `members`-window carbon ensemble around `level`."""
    rng = np.random.RandomState(seed)
    h = np.arange(hours)
    rows = [level * (1.0 + swing * np.sin(2 * np.pi * (h + 5 * e) / 24.0)
                     + 0.05 * rng.rand(hours)) for e in range(members)]
    return as_ensemble(np.asarray(rows), name=f"forecast{seed}")


def _candidates(calibrated, carbon):
    """Three parametric and three deadline-paced candidates, as the
    refresh re-scores them, each a campaign of one to three days."""
    wl, m = calibrated
    wl = dataclasses.replace(wl, n_scenarios=300_000.0)
    rng = np.random.RandomState(11)
    scheds = [ParametricSchedule(tuple(float(v) for v in rng.randn(24)),
                                 u_min=0.45, name=f"parametric-{j}")
              for j in range(3)]
    scheds += [deadline_schedule(d, name=f"deadline-{d:g}")
               for d in (30.0, 45.0, 70.0)]
    return [SweepCase(s, wl, m, carbon=carbon, label=s.name)
            for s in scheds]


def _answers(results):
    return [_res_key(r) + tuple(r.co2_ensemble.samples) for r in results]


def test_carbon_blind_candidates_hit_across_forecasts_bitwise(calibrated):
    a, b = _forecast(1), _forecast(2)
    ej.clear_plan_cache()
    under_a = trace_sweep(_candidates(calibrated, a))
    ej.reset_scan_stats()
    warm = trace_sweep(_candidates(calibrated, b))
    s = ej.scan_stats()
    assert (s.plan_hits, s.plan_misses) == (6, 0), \
        "a new forecast must not re-classify carbon-blind candidates"
    ej.clear_plan_cache()
    cold = trace_sweep(_candidates(calibrated, b))
    assert ej.scan_stats().plan_misses == 6
    assert _answers(warm) == _answers(cold)
    # the forecast really moves the answers: none came from the memo
    assert all(x[2] != y[2] for x, y in zip(_answers(warm),
                                            _answers(under_a)))


@dataclasses.dataclass(frozen=True)
class _DirtyHourPolicy(Policy):
    """A `Policy` subclass whose decide() throttles to `u_dirty` while the
    grid is dirtier than `threshold`: it reads carbon and declares
    nothing, so `Policy`'s own `carbon_blind` must not reach it."""
    threshold: float = 0.6
    u_dirty: float = 0.2

    def decide(self, ctx):
        if ctx.carbon_factor > self.threshold:
            return Decision(self.u_dirty, self.batch_size)
        return super().decide(ctx)


@dataclasses.dataclass(frozen=True)
class _SubParametric(ParametricSchedule):
    """Overrides nothing; the flag is read from the class itself."""


@pytest.mark.parametrize("sched", [
    CarbonGateSchedule(threshold=0.6, u_low=0.2, u_high=0.95),
    _DirtyHourPolicy("dirty_hour", {b: 0.95 for b in BANDS}),
], ids=["carbon_gate", "policy_subclass"])
def test_carbon_reading_schedule_misses_although_its_probe_read_no_carbon(
        calibrated, sched):
    """Forecast A never brings the threshold within the probe's
    perturbation (`carbon_dep` false); forecast B crosses it.  Neither
    schedule's own class declares `carbon_blind`, so B misses the memo
    and answers as a cold compile, throttled in B's dirty hours."""
    wl, m = calibrated
    wl = dataclasses.replace(wl, n_scenarios=300_000.0)
    a = _forecast(1, level=0.28, swing=0.1)      # perturbed at most 0.52
    b = _forecast(2, level=0.6, swing=0.5)       # 0.3 to 0.93
    plan = ej.compile_plan([SweepCase(sched, wl, m, carbon=a)])
    assert plan.case_expanded == [False], "the probe read no carbon in A"
    under_a = trace_sweep([SweepCase(sched, wl, m, carbon=a)])
    ej.reset_scan_stats()
    warm = trace_sweep([SweepCase(sched, wl, m, carbon=b)])
    s = ej.scan_stats()
    assert (s.plan_hits, s.plan_misses) == (0, 1)
    ej.clear_plan_cache()
    cold = trace_sweep([SweepCase(sched, wl, m, carbon=b)])
    assert _answers(warm) == _answers(cold)
    assert warm[0].runtime_h > under_a[0].runtime_h


@pytest.mark.parametrize("sched, blind", [
    (ParametricSchedule((0.3,) * 24), True),
    (deadline_schedule(12.0), True),
    (constant_schedule(0.7), True),
    (hourly_schedule("hourly", [0.5] * 12 + [0.9] * 12), True),
    (CarbonGateSchedule(threshold=0.5), False),
    (_DirtyHourPolicy("dirty_hour", {b: 0.7 for b in BANDS}), False),
    (_SubParametric((0.3,) * 24), False),
], ids=["parametric", "deadline", "policy", "hourly", "carbon_gate",
        "policy_subclass", "parametric_subclass"])
def test_fingerprint_leaves_carbon_out_for_declared_families(
        calibrated, sched, blind):
    wl, m = calibrated
    keys = {ej._fingerprint(SweepCase(sched, wl, m, carbon=c), None, 1, 32,
                            120)
            for c in (_forecast(1), _forecast(2), _week_trace(), None)}
    assert None not in keys
    assert len(keys) == (1 if blind else 4)


@dataclasses.dataclass(frozen=True)
class _MislabelledGate(CarbonGateSchedule):
    carbon_blind = True          # wrong: the gate reads ctx.carbon_factor


def test_carbon_blind_schedule_that_reads_carbon_raises(calibrated):
    wl, m = calibrated
    wl = dataclasses.replace(wl, n_scenarios=600.0)
    case = SweepCase(_MislabelledGate(threshold=0.45, name="mislabelled"),
                     wl, m, carbon=_week_trace())
    ej.clear_plan_cache()
    with pytest.raises(ValueError, match="mislabelled.*carbon_blind"):
        trace_sweep([case])
    assert ej.plan_cache_info().mem_entries == 0, "nothing may be cached"


def test_replace_tables_carbon_delta_hits_for_blind_schedules(calibrated):
    """The re-plan path keys through `_fingerprint` too: new carbon on a
    carbon-blind case is a memo hit, and the re-planned sweep answers as
    a cold compile under the new carbon."""
    a, b = _forecast(1), _forecast(2)
    ej.clear_plan_cache()
    plan = ej.compile_plan(_candidates(calibrated, a))
    ej.reset_scan_stats()
    new = ej.replace_tables(plan, carbon=b)
    s = ej.scan_stats()
    assert (s.plan_hits, s.plan_misses) == (6, 0)
    replanned = ej.summarize_plan(new, ej.execute_plan(new))
    ej.clear_plan_cache()
    cold = trace_sweep(_candidates(calibrated, b))
    assert _answers(replanned) == _answers(cold)


# ---------------------------------------------------------------------------
# The operator's rescore cycle: a few candidates revised per cycle, each
# delta chained on the previous one's plan and results
# ---------------------------------------------------------------------------
def _revision(rng, i: int, n_par: int):
    """A fresh candidate for slot i, of the family the slot holds."""
    if i < n_par:
        return ParametricSchedule(tuple(float(v) for v in 1.5 * rng.randn(24)),
                                  u_min=0.45, name=f"parametric-{i}")
    return deadline_schedule(float(rng.uniform(30.0, 90.0)),
                             name=f"deadline-{i}")


@pytest.mark.parametrize("backend, tol", [("numpy", 0.0), ("jax", 1e-12)],
                         ids=["numpy-bitwise", "jax-1e-12"])
def test_chained_rescores_equal_full_sweeps(calibrated, backend, tol):
    """16 candidates (8 day schedules, 8 pace keepers) under a 4-member
    forecast; three cycles each revise 3 of them through `delta_sweep`,
    chained on the previous cycle.  Every cycle answers as a full sweep
    of its candidate set, re-scans the 3 revised lanes only, splices the
    other 13, and re-lowers the stacked table rows of the 3 alone."""
    wl, m = calibrated
    wl = dataclasses.replace(wl, n_scenarios=300_000.0)
    rng = np.random.RandomState(2024)
    n_par, S = 8, 16
    forecast = _forecast(5, members=4, hours=24 * 8)
    cases = [SweepCase(_revision(rng, i, n_par), wl, m, carbon=forecast)
             for i in range(S)]
    plan = ej.compile_plan(cases)
    results = ej.summarize_plan(plan, ej.execute_plan(
        plan, backend=backend, chunk_days=1))
    # both families, day schedules alone, pace keepers alone
    for cycle, revised in enumerate(([1, 6, 12], [0, 2, 5], [9, 13, 15])):
        new = {i: _revision(rng, i, n_par) for i in revised}
        ej.reset_scan_stats()
        delta = ej.delta_sweep(plan, results, schedules=new,
                               backend=backend, chunk_days=1)
        s = ej.scan_stats()
        assert delta.recomputed == tuple(revised)
        assert (s.lanes_recomputed, s.lanes_spliced, s.lanes_relowered) \
            == (3, S - 3, 3)
        cases = [dataclasses.replace(c, schedule=new[i]) if i in new else c
                 for i, c in enumerate(cases)]
        full = trace_sweep(cases, backend=backend, chunk_days=1)
        got, want = (np.array([(r.runtime_h, r.energy_kwh, r.co2_kg,
                                *r.co2_ensemble.samples) for r in res])
                     for res in (delta.results, full))
        assert np.all(np.abs(got - want) <= tol * np.abs(want)), cycle
        plan, results = delta.plan, delta.results


def test_replace_tables_restacks_whole_when_the_bucket_width_changes(
        calibrated):
    """A progress-aware replacement widens the stacked tables' bucket
    axis, and replacing it back narrows them again: both restack every
    lane, and the stack is the one a cold compile of the cases builds."""
    cases = _cases(calibrated, 3)
    plan = ej.compile_plan(cases)
    ramp = FunctionSchedule("ramp", lambda ctx: 0.3 + 0.6 * ctx.progress)
    ej.reset_scan_stats()
    wide = ej.replace_tables(plan, schedules={1: ramp})
    narrow = ej.replace_tables(wide, schedules={1: cases[1].schedule})
    assert (plan.tab_buckets, wide.tab_buckets, narrow.tab_buckets) == \
        (1, 32, 1)
    assert ej.scan_stats().lanes_relowered == 6
    for p in (wide, narrow):
        cold = ej.compile_plan(p.cases)
        assert np.array_equal(p.tab_u, cold.tab_u)
        assert np.array_equal(p.tab_b, cold.tab_b)
    assert plan.tab_u.shape[2] == 1, "the incumbent plan is left as it was"
