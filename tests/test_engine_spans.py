"""The engine's profiler spans and its live-lane slot-work counter.

A profiler trace recorded on the CPU around a plain sweep, a capped fleet
sweep, and (in a child process with four virtual devices) a lane-sharded
sweep and a sharded capped fleet sweep must hold every `carina.*` span,
each inside its parent and every one inside its `carina.sweep`, at most
8 + 8 x chunks of them per sweep whatever the number of cases.
`live_slot_work` counts the unpadded lanes of each launch, never more
than `slot_work`, which counts them padded to their shape bucket.
"""
import dataclasses
import glob
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core import (BASELINE, GridCarbonModel, MachineProfile,
                        PEAK_AWARE_BOOSTED, Site, SweepCase,
                        calibrate_workload, constant_schedule)
from repro.core.engine_jax import (SPAN_PREFIX, reset_scan_stats,
                                   scan_stats, trace_sweep)
from repro.core.fleet import fleet_sweep
from repro.core.workload import OEM_CASE_1, OEM_CASE_2

#: Each span's parent; `sweep` has none.
PARENT = {"plan": "sweep", "plan.keys": "plan", "plan.lookup": "plan",
          "plan.classify": "plan", "plan.lanes": "plan", "execute": "sweep",
          "chunk.inputs": "execute", "chunk": "execute",
          "chunk.upload": "chunk", "chunk.launch": "chunk",
          "chunk.fetch": "chunk", "summarize": "sweep"}
SITE = Site(power_cap_kw=0.40, office_kw=0.12)


def record(fn, tmp: str) -> list:
    """Runs `fn()` under a profiler trace written to `tmp`; the program's
    spans as [start ns, end ns, name without prefix, thread, stats]."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(tmp + "/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append([ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name[len(SPAN_PREFIX):],
                                plane.name + "/" + line.name,
                                {k: int(v) for k, v in ev.stats}])
    return out


@pytest.fixture(scope="module")
def calibrated():
    wl1, m = calibrate_workload(OEM_CASE_1, MachineProfile())
    wl2 = dataclasses.replace(OEM_CASE_2, rate_at_full=wl1.rate_at_full)
    return wl1, wl2, m


def plain_cases(calibrated, n):
    wl1, wl2, m = calibrated
    scheds = [BASELINE, PEAK_AWARE_BOOSTED, constant_schedule(0.6),
              constant_schedule(0.8), constant_schedule(0.95)]
    return [SweepCase(scheds[i % 5], (wl1, wl2)[i % 2], m,
                      carbon=GridCarbonModel()) for i in range(n)]


def fleet_groups(calibrated, groups):
    wl1, wl2, m = calibrated
    return [[SweepCase(s, w, m, SITE.bands, GridCarbonModel(), 9.0)
             for s, w in ((BASELINE, wl1), (constant_schedule(0.8), wl2))]
            for _ in range(groups)]


def check_spans(spans):
    """The structural checks; returns {sweep seq: (spans, chunks)}."""
    assert {s[2] for s in spans} == set(PARENT) | {"sweep"}
    for s, e, name, thread, _ in spans:
        if name == "sweep":
            continue
        assert any(p[2] == PARENT[name] and p[3] == thread
                   and p[0] <= s and e <= p[1] for p in spans), name
    sweeps = [sp for sp in spans if sp[2] == "sweep"]
    per_sweep = {}
    for s, e, _, thread, stats in sweeps:
        inside = [sp for sp in spans
                  if sp[3] == thread and s <= sp[0] and sp[1] <= e]
        chunks = sum(sp[2] == "chunk" for sp in inside)
        assert chunks >= 1
        assert len(inside) <= 8 + 8 * chunks
        assert stats["cases"] >= 1
        per_sweep[stats["seq"]] = (len(inside), chunks)
    # no span outside a sweep
    assert all(any(w[3] == sp[3] and w[0] <= sp[0] and sp[1] <= w[1]
                   for w in sweeps) for sp in spans)
    assert len(per_sweep) == len(sweeps)      # one seq per sweep
    return per_sweep


def test_spans_of_a_plain_and_a_capped_fleet_sweep(calibrated, tmp_path):
    def sweeps():
        trace_sweep(plain_cases(calibrated, 5))
        trace_sweep(plain_cases(calibrated, 40))
        fleet_sweep(fleet_groups(calibrated, 3), SITE, devices=1)

    spans = record(sweeps, str(tmp_path))
    per_sweep = check_spans(spans)
    assert len(per_sweep) == 3
    (n5, c5), (n40, c40), _ = (per_sweep[k] for k in sorted(per_sweep))
    # eight times the cases, no more spans than one more chunk brings
    assert n40 - n5 <= 8 * max(c40 - c5, 0)


def test_live_slot_work_counts_unpadded_lanes(calibrated):
    wl1, _, m = calibrated
    same = [SweepCase(BASELINE, wl1, m, carbon=GridCarbonModel())] * 8
    reset_scan_stats()
    trace_sweep(same)              # 8 lanes finish together: no padding
    st = scan_stats()
    assert st.chunks >= 1 and st.live_slot_work == st.slot_work
    reset_scan_stats()
    trace_sweep(same[:5])          # 5 lanes pad to 8
    st = scan_stats()
    assert st.live_slot_work * 8 == st.slot_work * 5
    reset_scan_stats()
    trace_sweep(plain_cases(calibrated, 5), backend="numpy")
    st = scan_stats(reset=True)
    assert 0 < st.live_slot_work == st.slot_work
    assert scan_stats().live_slot_work == 0


def test_spans_of_sharded_sweeps_on_four_devices(tmp_path):
    from repro.core.xla_profiles import fanout_env

    code = textwrap.dedent("""
    import dataclasses, json, sys
    from repro.core import (BASELINE, GridCarbonModel, MachineProfile,
                            PEAK_AWARE_BOOSTED, Site, SweepCase,
                            calibrate_workload, constant_schedule)
    from repro.core.engine_jax import (reset_scan_stats, scan_stats,
                                       trace_sweep)
    from repro.core.fleet import fleet_sweep
    from repro.core.workload import OEM_CASE_1, OEM_CASE_2
    from test_engine_spans import record

    wl1, m = calibrate_workload(OEM_CASE_1, MachineProfile())
    wl2 = dataclasses.replace(OEM_CASE_2, rate_at_full=wl1.rate_at_full)
    scheds = [BASELINE, PEAK_AWARE_BOOSTED, constant_schedule(0.6),
              constant_schedule(0.8)]
    cases = [SweepCase(scheds[i % 4], (wl1, wl2)[i % 2], m,
                       carbon=GridCarbonModel()) for i in range(12)]
    site = Site(power_cap_kw=0.40, office_kw=0.12)
    groups = [[SweepCase(s, w, m, site.bands, GridCarbonModel(), 9.0)
               for s, w in ((BASELINE, wl1), (constant_schedule(0.8), wl2))]
              for _ in range(4)]
    reset_scan_stats()

    def sweeps():
        trace_sweep(cases, devices=4)
        fleet_sweep(groups, site, devices=4)

    spans = record(sweeps, sys.argv[1])
    st = scan_stats()
    print(json.dumps({"spans": spans, "devices_used": st.devices_used,
                      "live": st.live_slot_work, "slot": st.slot_work}))
    """)
    here = os.path.dirname(os.path.abspath(__file__))
    env = fanout_env(4)
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(here, "..", "src"),
                                         here])
    p = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                       capture_output=True, text=True, timeout=600, env=env,
                       check=False)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices_used"] == 4
    assert 0 < out["live"] <= out["slot"]
    assert len(check_spans(out["spans"])) == 2
