"""Tuned XLA flag profiles for the scan engine.

XLA reads its flags from the ``XLA_FLAGS`` environment variable once, at
backend initialization — flags changed after the first `jax.devices()`
call are silently ignored.  This module therefore deals only in
*strings and environment dicts* (no jax import at module scope) so that
test harnesses can assemble an environment for a CPU subprocess, and
applications can call `apply_profile` before first use.  `fanout_env`
is for CPU tests only: on an accelerator host a child of a process that
holds the chip cannot reach it, so multi-chip runs stay in one process.

The flag-dictionary pattern (one dict per profile, merged and rendered
as ``--name=value`` tokens) mirrors how production jax codebases ship
tuned flag sets per topology; profiles here are deliberately small and
CPU-focused since that is where the test matrix runs:

- ``cpu_scan``    — conservative CPU profile for the chunked scan: keep
  fast-math off so fp parity pins stay honest, let Eigen use the host
  threads it finds.
- ``cpu_fanout``  — `cpu_scan` plus ``xla_force_host_platform_device_count``
  so one host exposes N virtual CPU devices for `shard_map` lanes.
- ``default``     — empty; inherit whatever the process already has.

Usage::

    from repro.core.xla_profiles import apply_profile, fanout_env
    apply_profile("cpu_scan")           # before any jax.* call
    env = fanout_env(8)                 # env dict for a subprocess
"""
from __future__ import annotations

import os
import sys
from typing import Dict, Mapping, Optional

# One dict per profile; values are strings exactly as XLA parses them.
CPU_SCAN_FLAGS: Dict[str, str] = {
    # Parity pins (bitwise fp64 shard-vs-single) assume IEEE
    # semantics; never trade them for fast-math.
    "xla_cpu_enable_fast_math": "false",
    # The chunk kernels are large fused loops; multi-threaded Eigen
    # helps the single-device path on multi-core hosts.
    "xla_cpu_multi_thread_eigen": "true",
}

PROFILES: Dict[str, Dict[str, str]] = {
    "default": {},
    "cpu_scan": CPU_SCAN_FLAGS,
}


def fanout_flags(devices: int) -> Dict[str, str]:
    """Flags exposing `devices` virtual CPU devices on one host."""
    if devices < 1:
        raise ValueError(f"devices must be >= 1, got {devices}")
    return {"xla_force_host_platform_device_count": str(int(devices))}


def flags_string(profile: str = "default", *,
                 extra: Optional[Mapping[str, str]] = None,
                 base: Optional[str] = None) -> str:
    """Render a profile (plus overrides) as an ``XLA_FLAGS`` string.

    `base` is an existing ``XLA_FLAGS`` value to prepend (defaults to
    the current environment's); profile flags and then `extra` override
    duplicates by coming later in the string — XLA takes the last
    occurrence of a flag.
    """
    if profile not in PROFILES:
        raise KeyError(f"unknown XLA profile {profile!r}; "
                       f"have {sorted(PROFILES)}")
    if base is None:
        base = os.environ.get("XLA_FLAGS", "")
    merged = dict(PROFILES[profile])
    if extra:
        merged.update({str(k): str(v) for k, v in extra.items()})
    tokens = [base.strip()] if base and base.strip() else []
    tokens += [f"--{k}={v}" for k, v in merged.items()]
    return " ".join(tokens)


def fanout_env(devices: int, profile: str = "cpu_scan", *,
               extra: Optional[Mapping[str, str]] = None,
               base_env: Optional[Mapping[str, str]] = None
               ) -> Dict[str, str]:
    """A full environment dict for launching a CPU test subprocess with
    `devices` virtual CPU devices under `profile`.  Pins
    ``JAX_PLATFORMS=cpu`` so the fan-out flag is honored even where other
    backends exist (and so the child never competes for a chip)."""
    env = dict(base_env if base_env is not None else os.environ)
    merged = dict(fanout_flags(devices))
    if extra:
        merged.update(extra)
    env["XLA_FLAGS"] = flags_string(profile, extra=merged,
                                    base=env.get("XLA_FLAGS", ""))
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _jax_initialized() -> bool:
    """Best-effort: has this process already stood up an XLA backend?"""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    try:
        xb = sys.modules.get("jax._src.xla_bridge")
        return bool(xb is not None and getattr(xb, "_backends", None))
    except Exception:  # pragma: no cover - defensive
        return False


def apply_profile(profile: str = "cpu_scan", *,
                  extra: Optional[Mapping[str, str]] = None) -> str:
    """Install a profile into this process's ``XLA_FLAGS``.

    Must run before jax initializes a backend; if one already exists the
    flags are still set (harmless) but a warning is emitted because XLA
    will not re-read them.  Returns the installed string.
    """
    if _jax_initialized():
        import warnings
        warnings.warn("apply_profile called after jax backend "
                      "initialization; XLA_FLAGS changes will not take "
                      "effect in this process", RuntimeWarning,
                      stacklevel=2)
    flags = flags_string(profile, extra=extra)
    os.environ["XLA_FLAGS"] = flags
    return flags
