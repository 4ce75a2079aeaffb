"""jax process settings shared by the engine and the model stack.

`enable_x64` is the double-precision context manager, implemented over
the config flag with an explicit frame stack so nested and out-of-order
exits restore the right value.  `enable_persistent_compilation_cache`
decides where compiled programs are cached.
"""
from __future__ import annotations

import contextlib
import os
import threading

import jax


class _X64Frames(threading.local):
    """Per-thread stack of live `enable_x64` frames."""

    def __init__(self):
        self.stack = []  # list of [token, saved_value]


_X64 = _X64Frames()


@contextlib.contextmanager
def enable_x64(new_val: bool = True):
    """Set the `jax_enable_x64` flag for the duration of the context.

    Unlike a naive save/restore over the global config (the old
    fallback), each frame is tracked on a stack so the manager is
    reentrancy-safe: nested contexts restore the value their *own*
    entry observed, and an inner frame closed out of order (e.g. a
    generator finalized while a newer context is active) hands its
    saved value to the frame above it instead of clobbering the live
    setting.  This became load-bearing once the per-plan dtype policy
    made the engine open fp64 contexts inside callers' own contexts.
    """
    token = object()
    stack = _X64.stack
    stack.append([token, bool(jax.config.jax_enable_x64)])
    jax.config.update("jax_enable_x64", bool(new_val))
    try:
        yield
    finally:
        for i in range(len(stack) - 1, -1, -1):
            if stack[i][0] is token:
                saved = stack[i][1]
                del stack[i]
                if i < len(stack):
                    # Out-of-order exit: a newer frame is still active.
                    # Leave the flag as that frame set it, but make the
                    # newer frame restore *our* saved value when it
                    # exits (it captured the value we had installed).
                    stack[i][1] = saved
                else:
                    jax.config.update("jax_enable_x64", saved)
                break


#: The persistent compilation cache's directory when
#: ``JAX_COMPILATION_CACHE_DIR`` is not set: a fixed path inside the
#: checkout (git-ignored), never a temporary one: a cache that moves
#: between runs is never found again.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_persistent_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    The one place that decides where compiled programs are cached; the
    engine calls it before its first compile.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set the cache goes there and to no
    other directory; otherwise it goes to
    `DEFAULT_COMPILATION_CACHE_DIR`.  The min-entry-size and
    min-compile-time floors are dropped so the engine's many small chunk
    kernels are cached too.  The plan cache (core/plancache.py) removes
    re-*staging* across processes; this removes the re-compiles.
    """
    target = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
              or DEFAULT_COMPILATION_CACHE_DIR)
    os.makedirs(target, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", target)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return target
