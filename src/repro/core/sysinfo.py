"""System auto-detection (paper Algorithm 1, line 3: "Detect machine
characteristics and initialize tracker"; §2: "the current implementation
also supports system auto-detection").

Detects host characteristics (cores, memory, accelerator platform/count)
and derives an estimation MachineProfile / ChipProfile.  Pure estimation —
no meters — per the paper's method; every inferred constant is carried in
the profile `meta` so dashboards can show the provenance of the estimate.
"""
from __future__ import annotations

import dataclasses
import os
import platform
from typing import Dict, Optional

from repro.core.energy import ChipProfile, MachineProfile


def _read_meminfo_gb() -> Optional[float]:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) / 1024 / 1024
    except OSError:
        pass
    return None


def detect_host() -> Dict:
    """Raw host characteristics."""
    info: Dict = {
        "hostname": platform.node(),
        "machine": platform.machine(),
        "system": platform.system(),
        "cpus": os.cpu_count() or 1,
        "mem_gb": _read_meminfo_gb(),
    }
    import jax
    info["jax_backend"] = jax.default_backend()
    info["jax_devices"] = len(jax.devices())
    info["jax_device_kind"] = jax.devices()[0].device_kind
    return info


# Workstation-class TDP estimation by core count (estimation-based, as the
# paper's method allows; the calibration pass re-solves dyn_w anyway).
_TDP_BY_CORES = ((4, 65.0), (8, 95.0), (16, 145.0), (32, 220.0), (64, 320.0))


def machine_profile_from_host(info: Optional[Dict] = None) -> MachineProfile:
    info = info or detect_host()
    cores = info.get("cpus", 8)
    dyn = next((w for c, w in _TDP_BY_CORES if cores <= c), 360.0)
    idle = max(30.0, dyn * 0.35)
    return dataclasses.replace(MachineProfile(), name=f"auto-{info.get('hostname', 'host')}",
                               idle_w=idle, dyn_w=dyn)


# Known accelerator energy profiles (per chip), keyed by the exact
# `device_kind` jax reports.  Peak bf16 FLOP/s and HBM bandwidth are the
# published per-chip figures of Google Cloud's TPU documentation ("TPU
# v5e", "TPU v5p", "TPU v4" system-architecture pages); idle/TDP watts
# and ICI bandwidth are estimation constants (see energy.ChipProfile).
_CHIP_TABLE = {
    "TPU v5 lite": ChipProfile(),                 # v5e: 197 TFLOP/s, 819 GB/s
    "TPU v5": ChipProfile(name="tpu-v5p", peak_flops=459e12, hbm_bw=2765e9,
                          ici_bw=90e9, idle_w=90.0, tdp_w=350.0),
    "TPU v4": ChipProfile(name="tpu-v4", peak_flops=275e12, hbm_bw=1228e9,
                          ici_bw=50e9, idle_w=90.0, tdp_w=300.0),
}


def chip_profile_from_host(info: Optional[Dict] = None) -> ChipProfile:
    """The energy profile of the host's accelerator, by exact device
    kind; a kind not in the table (a CPU among them) is an error."""
    info = info or detect_host()
    kind = info.get("jax_device_kind")
    if kind not in _CHIP_TABLE:
        raise ValueError(
            f"no chip profile for device kind {kind!r}; known kinds: "
            f"{sorted(_CHIP_TABLE)}")
    return _CHIP_TABLE[kind]
