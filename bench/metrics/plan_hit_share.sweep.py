"""plan_hit_share.sweep: share of the cases planning looked up in the
window that a plan cache served instead of classifying them anew, in
percent: 100 x (plan_hits + disk_hits) / (plan_hits + disk_hits +
plan_misses), from the program's `scan_stats()` (the in-process memo and
the disk store).  None when the window looked up no case."""
from __future__ import annotations


def read(run):
    st = run.stats
    hits = st.plan_hits + st.disk_hits
    looked_up = hits + st.plan_misses
    if not looked_up:
        return None
    return 100.0 * hits / looked_up
