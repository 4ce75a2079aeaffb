"""plan_share.sweep: share of the window the host spent in
`engine_jax.compile_plan` (the benchmark's "plan" span), in percent.
Planning classifies and lowers every case of a refresh in Python; while
it runs the chip has no chunk to scan."""
from __future__ import annotations


def read(run):
    tr = run.trace
    if tr.window is None or not tr.spans:
        return None
    return 100.0 * tr.span_s("plan") / tr.window_s()
