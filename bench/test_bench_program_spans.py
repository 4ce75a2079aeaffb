"""The program's spans in a trace (`program_spans.py`), the readers of the
metrics they feed, and `program_trace.py`.

A trace that holds the program's `carina.*` spans must read, through
`xplane.Trace` and `ProgramTrace` alike, every number it reads without
them: synthetic events, and a trace recorded on the CPU around a sweep.
"""
import glob
import importlib.util
import os
import time
import types

import pytest

import program_spans
import program_trace
import xplane
from program_spans import PROGRAM_PREFIX, ProgramTrace
from shrink import small_cell

BENCH = os.path.dirname(os.path.abspath(__file__))


def reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_prefix_is_the_programs():
    from repro.core import engine_jax
    assert PROGRAM_PREFIX == engine_jax.SPAN_PREFIX


# ---------------------------------------------------------------------------
# Synthetic profile data, shaped as `jax.profiler.ProfileData`
# ---------------------------------------------------------------------------
def ev(start, end, name, **stats):
    return types.SimpleNamespace(start_ns=start, duration_ns=end - start,
                                 name=name, stats=list(stats.items()))


def line(name, *events):
    return types.SimpleNamespace(name=name, events=list(events))


def profile(program: bool):
    """A TPU plane with two kernel launches, a host plane with the
    benchmark's spans and, when `program`, the program's spans."""
    P = PROGRAM_PREFIX
    ops = line("XLA Ops",
               ev(1500, 1600, "%fusion.1 = f64[8] fusion(x)",
                  hlo_module="jit__scan_chunk_jax_impl"),
               ev(1800, 1850, "%copy.2 = f64[8] copy(y)",
                  hlo_module="jit__scan_chunk_jax_impl"))
    mods = line("XLA Modules", ev(1500, 1600, "jit__scan_chunk_jax_impl"),
                ev(1800, 1850, "jit__scan_chunk_jax_impl"))
    host = [ev(1000, 2000, xplane.WINDOW),
            ev(1000, 1400, xplane.SPAN_PREFIX + "plan"),
            ev(1450, 1900, xplane.SPAN_PREFIX + "chunk")]
    if program:
        host += [ev(1000, 1950, P + "sweep", cases=4, seq=1),
                 ev(1010, 1400, P + "plan"),
                 ev(1100, 1300, P + "plan.classify"),
                 ev(1400, 1950, P + "execute"),
                 ev(1450, 1900, P + "chunk"),
                 ev(1460, 1480, P + "chunk.upload"),
                 ev(1480, 1500, P + "chunk.launch"),
                 ev(1500, 1700, P + "chunk.fetch")]
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/device:TPU:0", lines=[ops, mods]),
        types.SimpleNamespace(name="/host:CPU",
                              lines=[line("python", *host)])])


def readings(tr):
    """Everything the benchmark's trace readers and breakdown read."""
    run = types.SimpleNamespace(trace=tr)
    return {"window": tr.window, "spans": tr.spans,
            "window_s": tr.window_s(), "busy_s": tr.busy_s(),
            "kernel_s": tr.module_time_s(xplane.KERNEL_MODULES),
            "plan_s": tr.span_s("plan"), "top_ops": tr.top_ops(10),
            "idle_by_span": tr.idle_by_span(10),
            "plan_share": reader("plan_share.sweep")(run),
            "device_idle": reader("device_idle.sweep")(run)}


def test_program_spans_leave_every_reading_alone_synthetic():
    without = readings(xplane.Trace.from_profile(profile(False)))
    assert without == readings(xplane.Trace.from_profile(profile(True)))
    assert without == readings(ProgramTrace.from_profile(profile(True)))
    assert without["plan_share"] == pytest.approx(40.0)


def test_program_span_numbers_synthetic():
    tr = ProgramTrace.from_profile(profile(True))
    assert len(tr.program_spans) == 8
    assert tr.program_span_s("plan.classify") == pytest.approx(200e-9)
    assert tr.program_span_s("chunk.fetch") == pytest.approx(200e-9)
    idle = dict(tr.idle_by_program_span(20))
    # busy [1500, 1600) and [1800, 1850): idle 850 ns of the 1000
    assert sum(idle.values()) == pytest.approx(850e-9)
    assert idle == pytest.approx({
        "sweep": 10e-9, "plan": 190e-9, "plan.classify": 200e-9,
        "execute": 100e-9, "chunk": 160e-9, "chunk.upload": 20e-9,
        "chunk.launch": 20e-9, "chunk.fetch": 100e-9,
        xplane.IDLE_OUTSIDE_SPANS: 50e-9}, abs=1e-15)
    assert ProgramTrace.from_profile(profile(False)).program_spans == []


def test_span_readers_on_synthetic_runs():
    tr = ProgramTrace.from_profile(profile(True))
    run = types.SimpleNamespace(trace=tr)
    assert reader("classify_share.sweep")(run) == pytest.approx(20.0)
    assert reader("upload_share.sweep")(run) == pytest.approx(2.0)
    assert reader("fetch_share.sweep")(run) == pytest.approx(20.0)
    # no program spans: plain `xplane.Trace`, or none recorded
    for tr in (xplane.Trace.from_profile(profile(True)),
               ProgramTrace.from_profile(profile(False))):
        for name in ("classify_share", "upload_share", "fetch_share"):
            assert reader(name + ".sweep")(
                types.SimpleNamespace(trace=tr)) is None
    # no window
    no_window = ProgramTrace({}, [], {}, [(0, 10, PROGRAM_PREFIX + "plan")])
    assert program_spans.window_share(
        types.SimpleNamespace(trace=no_window), "plan") is None


def test_lane_pad_share_reader():
    read = reader("lane_pad_share.sweep")
    stats = types.SimpleNamespace(chunks=3, slot_work=800,
                                  live_slot_work=600)
    assert read(types.SimpleNamespace(stats=stats)) == pytest.approx(25.0)
    stats.live_slot_work = 800
    assert read(types.SimpleNamespace(stats=stats)) == 0.0
    # no chunk, or a program that does not count live lanes
    assert read(types.SimpleNamespace(stats=types.SimpleNamespace(
        chunks=0, slot_work=0, live_slot_work=0))) is None
    assert read(types.SimpleNamespace(stats=types.SimpleNamespace(
        chunks=2, slot_work=800))) is None


# ---------------------------------------------------------------------------
# A trace recorded on the CPU around a program sweep
# ---------------------------------------------------------------------------
def without_program_spans(pd):
    """The same profile with the program's events taken out."""
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=p.name, lines=[
            types.SimpleNamespace(name=ln.name, events=[
                e for e in ln.events
                if not e.name.startswith(PROGRAM_PREFIX)])
            for ln in p.lines])
        for p in pd.planes])


def test_program_spans_leave_every_reading_alone_cpu_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    import repro.carina as carina
    from repro.core.workload import OEM_CASE_1

    wl, m = carina.calibrate_workload(OEM_CASE_1, carina.MachineProfile())
    cases = [carina.SweepCase(s, wl, m) for s in
             (carina.BASELINE, carina.PEAK_AWARE_BOOSTED,
              carina.constant_schedule(0.7))]
    carina.trace_sweep(cases)                    # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW):
            with jax.profiler.TraceAnnotation(xplane.SPAN_PREFIX + "request"):
                carina.trace_sweep(cases)
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    base = readings(xplane.Trace.from_profile(without_program_spans(pd)))
    assert base == readings(xplane.Trace.from_profile(pd))
    assert base == readings(ProgramTrace.from_profile(pd))
    tr = ProgramTrace.from_profile(pd)
    names = {n[len(PROGRAM_PREFIX):] for _, _, n in tr.program_spans}
    assert {"sweep", "plan", "plan.classify", "execute", "chunk",
            "chunk.upload", "chunk.launch", "chunk.fetch",
            "summarize"} <= names
    assert 0.0 < tr.program_span_s("sweep") <= tr.window_s()
    idle = dict(tr.idle_by_program_span(30))
    assert sum(idle.values()) == pytest.approx(
        tr.window_s() - tr.busy_s(), rel=1e-6)
    assert idle[xplane.IDLE_OUTSIDE_SPANS] >= 0.009   # the sleep


# ---------------------------------------------------------------------------
# program_trace.py on cells shrunk to CPU size
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("workload", ["oem1-refresh", "fleet-capped"])
def test_program_trace_reads_the_span_metrics(workload):
    c = small_cell(workload)
    # the CPU has no entry in the roofline's peak table
    c["per_layer"] = [m for m in c["per_layer"]
                      if m["name"] != "kernel_roofline.sweep"]
    result = program_trace.trace_cell(c, 2**31 + 12345, 0.01,
                                      require_chip=False)
    assert result["correct"] is True
    for name in program_trace.SPAN_METRICS + ("lane_pad_share.sweep",
                                              "plan_share.sweep"):
        assert 0.0 <= result["metrics"][name]["value"] <= 100.0, name
    prog = result["program"]
    assert prog["requests"] >= 1 and prog["cases_per_s_traced"] > 0
    assert prog["span_s"]["sweep"] > 0
    assert 0.0 < prog["idle_covered_pct"] <= 100.0
