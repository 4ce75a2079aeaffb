"""Peak table and the chunk kernels' work function.

The chunk kernels step (lane x slot) state with a handful of
element-wise operations per value read, so on a v5e they are bound by
memory bandwidth, not by arithmetic: their least time is the bytes the
scored work has to read, over the chip's HBM bandwidth.

The bytes are those of the *useful* work: the lane-slots in which a
campaign of the request still had work (padded lanes, and slots after a
lane finished, are not counted), so a change that removes padding or
fuses chunks is read against the same work.  Per useful lane-slot a lane
reads its E carbon factors, the slot's background and length, and the
intensity and batch size it decides at its progress (E + 4 values of the
configuration's dtype) plus a 4-byte int32 decision-row index.  The
site-coupled kernel also reads, per useful group-slot, the site's
office draw (one value).
"""
from __future__ import annotations

from typing import Dict

#: Per-chip peaks, keyed by `device_kind` as JAX reports it.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12, "hbm_bytes": 16e9},
}
PEAKS_SOURCE = ("Google Cloud documentation, 'TPU v5e': per chip 197 "
                "TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s")

DTYPE_BYTES = {"float64": 8, "float32": 4, "bfloat16": 2}
INDEX_BYTES = 4


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak row of a device kind; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; add "
                         "its published figures to PEAKS") from None


def useful_bytes(kernel: str, lane_slots: int, group_slots: int,
                 members: int, dtype: str) -> int:
    """Bytes the scored work has to read: `lane_slots` useful lane-slots
    with `members` carbon members each, plus (site-coupled kernel)
    `group_slots` useful group-slots."""
    item = DTYPE_BYTES[dtype]
    per_lane_slot = (members + 4) * item + INDEX_BYTES
    total = lane_slots * per_lane_slot
    if kernel == "coupled":
        total += group_slots * item
    elif kernel != "plain":
        raise ValueError(f"unknown chunk kernel {kernel!r}")
    return int(total)


def least_time_s(nbytes: int, device_kind: str, chips: int) -> float:
    """Seconds the chips need at least to read `nbytes` (split evenly)."""
    return nbytes / (chips * peaks(device_kind)["hbm_bytes_per_s"])
