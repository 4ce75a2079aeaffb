"""Trace reduction: interval arithmetic, gap labelling, and a small trace
recorded on the CPU."""
import glob
import re
import time

import pytest

import xplane
from xplane import Op, Trace


def test_merge_clip_length_complement():
    iv = [(5, 8), (0, 2), (1, 3), (7, 10), (12, 12)]
    assert xplane.merge(iv) == [(0, 3), (5, 10)]
    assert xplane.length(iv) == 8
    assert sorted(xplane.clip(iv, 2, 6)) == [(2, 3), (5, 6)]
    assert xplane.complement(xplane.merge(iv), -1, 11) == \
        [(-1, 0), (3, 5), (10, 11)]
    assert xplane.complement([], 0, 4) == [(0, 4)]


SPANS = [(0, 100, "request"), (10, 40, "plan"), (50, 90, "chunk"),
         (50, 60, "chunk_inputs")]


def test_innermost_partition():
    parts = xplane.innermost(SPANS, 0, 100)
    assert parts == [(0, 10, "request"), (10, 40, "plan"),
                     (40, 50, "request"), (50, 60, "chunk_inputs"),
                     (60, 90, "chunk"), (90, 100, "request")]
    assert xplane.innermost([], 0, 5) == [(0, 5, xplane.IDLE_OUTSIDE_SPANS)]


def test_gap_labelling():
    # device busy [60, 85): idle [0, 60) and [85, 100)
    gaps = xplane.complement([(60, 85)], 0, 100)
    assert gaps == [(0, 60), (85, 100)]
    assert xplane.label_gaps(gaps, SPANS) == {
        "request": 30, "plan": 30, "chunk_inputs": 10, "chunk": 5}


def test_trace_numbers_on_synthetic_events():
    win = (1000.0, 2000.0)
    ops = {"/device:TPU:0": [Op(900, 1100, "fusion.1", "jit_k"),
                             Op(1500, 1700, "while.3", "jit_k"),
                             Op(1550, 1650, "fusion.2", "jit_k"),
                             Op(1800, 1900, "copy.1", "jit_other")],
           "/device:TPU:1": [Op(1000, 1500, "fusion.1", "jit_k")]}
    spans = [(win[0], win[1], xplane.WINDOW),
             (1000, 1400, xplane.SPAN_PREFIX + "plan")]
    tr = Trace(ops, spans)
    assert tr.window == win
    assert tr.window_s() == pytest.approx(1000e-9)
    # device 0 busy 100 + 200 + 100 = 400, device 1 busy 500
    assert tr.busy_s() == pytest.approx(450e-9)
    # module time from op unions: device 0 jit_k 300, device 1 500
    assert tr.module_time_s(re.compile("jit_k")) == pytest.approx(400e-9)
    top = dict(tr.top_ops())
    assert "while.3" not in top                  # containers left out
    assert top["fusion.1"] == pytest.approx((100 + 500) / 2 * 1e-9)
    idle = dict(tr.idle_by_span())
    # device 0 idle in plan [1100, 1400) = 300; device 1 idle in plan 0
    assert idle["plan"] == pytest.approx(150e-9)
    assert sum(idle.values()) == pytest.approx(
        tr.window_s() - tr.busy_s())
    assert tr.span_s("plan") == pytest.approx(400e-9)


def test_reduction_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW):
            with jax.profiler.TraceAnnotation(xplane.SPAN_PREFIX + "plan"):
                time.sleep(0.05)
            for _ in range(3):
                with jax.profiler.TraceAnnotation(
                        xplane.SPAN_PREFIX + "chunk"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    tr = Trace.from_file(path)
    assert tr.window is not None and tr.window_s() >= 0.05
    assert 0.0 < tr.busy_s() < tr.window_s()
    assert tr.module_time_s(re.compile("lambda")) > 0.0
    assert tr.span_s("plan") >= 0.05
    idle = dict(tr.idle_by_span())
    assert idle["plan"] >= 0.045                 # the sleep is idle time
    assert sum(idle.values()) == pytest.approx(
        tr.window_s() - tr.busy_s(), rel=1e-6)
    assert tr.top_ops()
