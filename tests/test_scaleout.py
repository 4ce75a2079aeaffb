"""Scale-out engine tests: device-sharded execute_plan, per-plan
precision policy, XLA flag profiles, and the reentrant `enable_x64`
context manager.

Multi-device cases run in one amortized subprocess (the virtual CPU
device count is an XLA_FLAGS setting locked at first jax init); the
subprocess pins sharded-vs-single results bitwise (fp64) and to the
documented 1e-6 tolerance (mixed), including a coupled fleet sweep.
Everything else — precision accuracy bounds, scan_stats counters —
runs in-process on one device.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (BASELINE, GridCarbonModel, MachineProfile,
                        PEAK_AWARE_BOOSTED, Site, SweepCase,
                        calibrate_workload, constant_schedule)
from repro.core.engine_jax import (_group_cuts, _pad_lanes, _pad_pow2,
                                   compile_plan, execute_plan,
                                   reset_scan_stats, scan_stats,
                                   summarize_plan)
from repro.core.workload import OEM_CASE_1, OEM_CASE_2

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
SITE = Site(power_cap_kw=0.40, office_kw=0.12)


@pytest.fixture(scope="module")
def calibrated():
    wl1, m = calibrate_workload(OEM_CASE_1, MachineProfile())
    wl2 = dataclasses.replace(OEM_CASE_2, rate_at_full=wl1.rate_at_full)
    return wl1, wl2, m


def _uncoupled_cases(calibrated, n=6):
    wl1, wl2, m = calibrated
    scheds = [BASELINE, PEAK_AWARE_BOOSTED, constant_schedule(0.6),
              constant_schedule(0.8), constant_schedule(0.95),
              constant_schedule(0.7)]
    return [SweepCase(s, w, m, carbon=GridCarbonModel())
            for s, w in zip(scheds[:n], ([wl1, wl2] * 3)[:n])]


def _coupled_plan(calibrated, precision="fp64"):
    wl1, wl2, m = calibrated
    cases = [SweepCase(s, w, m, SITE.bands, GridCarbonModel(), 9.0)
             for s, w in zip((BASELINE, PEAK_AWARE_BOOSTED,
                              constant_schedule(0.8), BASELINE),
                             (wl1, wl2, wl1, wl2))]
    return compile_plan(cases, group_sizes=[2, 2],
                        group_caps_kw=[SITE.power_cap_kw] * 2,
                        group_office_kw=[SITE.office_kw] * 2,
                        precision=precision)


# ---------------------------------------------------------------------------
# Multi-device subprocess (bitwise fp64, documented-tolerance mixed)
# ---------------------------------------------------------------------------
def run_subprocess(code: str, devices: int = 8) -> str:
    from repro.core.xla_profiles import fanout_env
    env = fanout_env(devices)
    env["PYTHONPATH"] = SRC
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout


def test_sharded_execute_plan_8_virtual_devices():
    """One amortized 8-virtual-device subprocess: (a) uncoupled sharded
    fp64 is bitwise-identical to single-device; (b) mixed precision stays
    within the documented 1e-6 relative tolerance on kWh/CO2, sharded or
    not; (c) a coupled fleet sweep shards bitwise at group granularity;
    (d) the devices_used counter reports the fan-out."""
    code = """
    import dataclasses, json
    import jax
    from repro.core import (BASELINE, GridCarbonModel, MachineProfile,
                            PEAK_AWARE_BOOSTED, Site, SweepCase,
                            calibrate_workload, constant_schedule)
    from repro.core.engine_jax import (compile_plan, execute_plan,
                                       reset_scan_stats, scan_stats,
                                       summarize_plan)
    from repro.core.workload import OEM_CASE_1, OEM_CASE_2

    wl1, m = calibrate_workload(OEM_CASE_1, MachineProfile())
    wl2 = dataclasses.replace(OEM_CASE_2, rate_at_full=wl1.rate_at_full)
    scheds = [BASELINE, PEAK_AWARE_BOOSTED, constant_schedule(0.6),
              constant_schedule(0.8), constant_schedule(0.95),
              constant_schedule(0.7), BASELINE, PEAK_AWARE_BOOSTED]
    cases = [SweepCase(s, w, m, carbon=GridCarbonModel())
             for s, w in zip(scheds, [wl1, wl2] * 4)]
    out = {"n_devices": len(jax.devices())}

    plan = compile_plan(cases)
    r1 = summarize_plan(plan, execute_plan(plan, devices=1))
    reset_scan_stats()
    r8 = summarize_plan(plan, execute_plan(plan, devices=8))
    out["devices_used"] = scan_stats().devices_used
    out["uncoupled_bitwise"] = all(
        a.runtime_h == b.runtime_h and a.energy_kwh == b.energy_kwh
        and a.co2_kg == b.co2_kg for a, b in zip(r1, r8))

    pm = compile_plan(cases, precision="mixed")
    rm8 = summarize_plan(pm, execute_plan(pm, devices=8))
    out["mixed_rel"] = max(
        max(abs(a.energy_kwh - b.energy_kwh) / abs(a.energy_kwh),
            abs(a.co2_kg - b.co2_kg) / abs(a.co2_kg))
        for a, b in zip(r1, rm8))

    SITE = Site(power_cap_kw=0.40, office_kw=0.12)
    fc = [SweepCase(s, w, m, SITE.bands, GridCarbonModel(), 9.0)
          for s, w in zip((BASELINE, PEAK_AWARE_BOOSTED,
                           constant_schedule(0.8), BASELINE),
                          (wl1, wl2, wl1, wl2))]
    cp = compile_plan(fc, group_sizes=[2, 2],
                      group_caps_kw=[SITE.power_cap_kw] * 2,
                      group_office_kw=[SITE.office_kw] * 2)
    c1 = summarize_plan(cp, execute_plan(cp, devices=1))
    reset_scan_stats()
    c2 = summarize_plan(cp, execute_plan(cp, devices=2))
    out["coupled_devices_used"] = scan_stats().devices_used
    out["coupled_bitwise"] = all(
        a.runtime_h == b.runtime_h and a.energy_kwh == b.energy_kwh
        and a.co2_kg == b.co2_kg for a, b in zip(c1, c2))
    print(json.dumps(out))
    """
    out = json.loads(run_subprocess(code, devices=8).strip().splitlines()[-1])
    assert out["n_devices"] == 8
    assert out["uncoupled_bitwise"] is True
    assert out["devices_used"] == 8
    assert out["mixed_rel"] < 1e-6, out["mixed_rel"]
    assert out["coupled_bitwise"] is True
    assert out["coupled_devices_used"] == 2


# ---------------------------------------------------------------------------
# Precision policy (single device)
# ---------------------------------------------------------------------------
def test_compile_plan_rejects_unknown_precision(calibrated):
    with pytest.raises(ValueError):
        compile_plan(_uncoupled_cases(calibrated, 2), precision="fp16")


def test_mixed_precision_within_documented_tolerance(calibrated):
    """The per-plan mixed policy (fp32 per-slot physics, fp64 carried
    state + accumulators) keeps kWh/CO2 within 1e-6 relative of the
    exact-fp64 default, and the stats counter reports the mode."""
    cases = _uncoupled_cases(calibrated)
    plan = compile_plan(cases)
    ref = summarize_plan(plan, execute_plan(plan))
    pm = compile_plan(cases, precision="mixed")
    reset_scan_stats()
    got = summarize_plan(pm, execute_plan(pm))
    assert scan_stats().precision_mode == "mixed"
    for a, b in zip(ref, got):
        assert abs(a.energy_kwh - b.energy_kwh) / abs(a.energy_kwh) < 1e-6
        assert abs(a.co2_kg - b.co2_kg) / abs(a.co2_kg) < 1e-6


def test_fp64_default_reports_precision_mode(calibrated):
    plan = compile_plan(_uncoupled_cases(calibrated, 2))
    reset_scan_stats()
    execute_plan(plan, devices=1)
    st = scan_stats()
    assert st.precision_mode == "fp64"
    assert st.devices_used == 1


@pytest.mark.parametrize("coupled", [False, True],
                         ids=["uncoupled", "coupled"])
def test_bytes_uploaded_counts_device_launches(calibrated, coupled):
    """`bytes_uploaded` counts the host -> device bytes of chunk
    launches: none on the NumPy backend, and fewer under the mixed
    policy, whose per-slot inputs are fp32."""
    def run(precision, backend):
        plan = (_coupled_plan(calibrated, precision=precision) if coupled
                else compile_plan(_uncoupled_cases(calibrated, 2),
                                  precision=precision))
        reset_scan_stats()
        execute_plan(plan, backend=backend, devices=1)
        return scan_stats()

    assert run("fp64", "numpy").bytes_uploaded == 0
    fp64, mixed = run("fp64", "jax"), run("mixed", "jax")
    assert fp64.bytes_uploaded > 0 and fp64.chunks == mixed.chunks
    assert mixed.bytes_uploaded < fp64.bytes_uploaded


def test_coupled_mixed_precision_tolerance(calibrated):
    ref_plan = _coupled_plan(calibrated)
    ref = summarize_plan(ref_plan, execute_plan(ref_plan))
    pm = _coupled_plan(calibrated, precision="mixed")
    got = summarize_plan(pm, execute_plan(pm))
    for a, b in zip(ref, got):
        assert abs(a.energy_kwh - b.energy_kwh) / abs(a.energy_kwh) < 1e-6
        assert abs(a.co2_kg - b.co2_kg) / abs(a.co2_kg) < 1e-6


# ---------------------------------------------------------------------------
# enable_x64 reentrancy
# ---------------------------------------------------------------------------
def test_enable_x64_nested_contexts_restore_correctly():
    import jax
    from repro.compat import enable_x64
    base = bool(jax.config.jax_enable_x64)
    with enable_x64(True):
        assert jax.config.jax_enable_x64 is True
        with enable_x64(False):
            assert jax.config.jax_enable_x64 is False
            with enable_x64(True):
                assert jax.config.jax_enable_x64 is True
            assert jax.config.jax_enable_x64 is False
        assert jax.config.jax_enable_x64 is True
    assert bool(jax.config.jax_enable_x64) == base


def test_enable_x64_out_of_order_exit():
    """A frame closed while a newer frame is still active (e.g. a
    generator finalized mid-context) must not clobber the live value,
    and the surviving frame must restore the elder's saved value."""
    import jax
    from repro.compat import enable_x64
    base = bool(jax.config.jax_enable_x64)
    outer = enable_x64(True)
    outer.__enter__()
    inner = enable_x64(False)
    inner.__enter__()
    outer.__exit__(None, None, None)      # out of order: outer dies first
    assert jax.config.jax_enable_x64 is False   # inner still governs
    inner.__exit__(None, None, None)
    assert bool(jax.config.jax_enable_x64) == base


def test_enable_x64_generator_finalization():
    import jax
    from repro.compat import enable_x64
    base = bool(jax.config.jax_enable_x64)

    def gen():
        with enable_x64(True):
            yield 1
            yield 2

    g = gen()
    next(g)
    with enable_x64(True):
        g.close()                         # finalize inside a newer frame
        assert jax.config.jax_enable_x64 is True
    assert bool(jax.config.jax_enable_x64) == base


# ---------------------------------------------------------------------------
# XLA flag profiles
# ---------------------------------------------------------------------------
def test_xla_profiles_render_and_env():
    from repro.core.xla_profiles import (fanout_env, fanout_flags,
                                         flags_string)
    s = flags_string("cpu_scan", base="")
    assert "--xla_cpu_enable_fast_math=false" in s
    assert flags_string("default", base="--keep=1") == "--keep=1"
    with pytest.raises(KeyError):
        flags_string("nope")
    with pytest.raises(ValueError):
        fanout_flags(0)
    env = fanout_env(8, base_env={})
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    # later flags win in XLA's parser: the fan-out override comes last
    env2 = fanout_env(4, base_env={"XLA_FLAGS": "--xla_cpu_enable_fast_math=true"})
    assert env2["XLA_FLAGS"].index("fast_math=true") \
        < env2["XLA_FLAGS"].index("fast_math=false")


def test_apply_profile_warns_after_jax_init():
    import jax
    from repro.core.xla_profiles import apply_profile
    jax.devices()                         # force backend init
    before = os.environ.get("XLA_FLAGS")
    try:
        with pytest.warns(RuntimeWarning):
            apply_profile("cpu_scan")
    finally:
        if before is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = before


# ---------------------------------------------------------------------------
# Lane/group partition helpers
# ---------------------------------------------------------------------------
def test_pad_lanes_matches_single_device_bucketing():
    for n in (1, 2, 5, 8, 13, 64, 100):
        assert _pad_lanes(n, 1) == _pad_pow2(n, minimum=8)
        for n_dev in (2, 4, 8):
            p = _pad_lanes(n, n_dev)
            assert p % n_dev == 0 and p >= n


def test_group_cuts_cover_and_balance():
    cnt = np.array([5, 1, 3, 7, 2, 2, 4, 1])
    for n_dev in (1, 2, 3, 4, 8):
        bounds = _group_cuts(cnt, n_dev)
        assert bounds[0] == 0 and bounds[-1] == len(cnt)
        parts = np.diff(bounds)
        assert (parts >= 1).all()         # every device owns >=1 group
        assert parts.sum() == len(cnt)


def test_execute_plan_rejects_bad_devices(calibrated):
    plan = compile_plan(_uncoupled_cases(calibrated, 2))
    with pytest.raises(ValueError):
        execute_plan(plan, devices=0)
