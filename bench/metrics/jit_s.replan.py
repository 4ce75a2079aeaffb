"""jit_s.replan: seconds per re-plan that JAX spent tracing, lowering and
compiling (or loading a compiled program from the cache) inside the
window, from `jax.monitoring` duration events heard by the harness."""
from __future__ import annotations


def read(run):
    if not run.window["requests"]:
        return None
    return run.window["jit_s"] / run.window["requests"]
