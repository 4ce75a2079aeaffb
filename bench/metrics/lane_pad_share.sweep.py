"""lane_pad_share.sweep: share of the slot-work the chunk launches of the
window ran on padding lanes, in percent: 100 x (1 - live / launched), from
the program's `scan_stats()`: `live_slot_work` (the lanes each launch was
given) over `slot_work` (the lane axis padded to its shape bucket).  None
without a chunk launch, or from a program without the live count."""
from __future__ import annotations


def read(run):
    st = run.stats
    live = getattr(st, "live_slot_work", 0)
    if not st.chunks or not live:
        return None
    return 100.0 * (1.0 - live / st.slot_work)
