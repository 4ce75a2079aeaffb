"""recompute_share.rescore: share of the window's `delta_sweep` lanes that
were re-scanned rather than spliced from the previous answer, in percent:
100 x lanes_recomputed / (lanes_recomputed + lanes_spliced), from the
program's `scan_stats()`.  A rescore of K of S candidates reads 100 K / S;
more means the value screen or the splice stopped working.  None when the
window ran no delta."""
from __future__ import annotations


def read(run):
    st = run.stats
    lanes = st.lanes_recomputed + st.lanes_spliced
    if not lanes:
        return None
    return 100.0 * st.lanes_recomputed / lanes
