"""relower_share.rescore: lanes whose stacked decision-table rows
`replace_tables` wrote in the window, over the window's `delta_sweep`
lanes, in percent: 100 x lanes_relowered / (lanes_recomputed +
lanes_spliced), from the program's `scan_stats()`.  Rows written for the
changed lanes alone read the recompute share; a restack of every lane
reads 100.  None when the window ran no delta, or from a program without
the `lanes_relowered` counter."""
from __future__ import annotations


def read(run):
    st = run.stats
    lanes = st.lanes_recomputed + st.lanes_spliced
    relowered = getattr(st, "lanes_relowered", None)
    if not lanes or relowered is None:
        return None
    return 100.0 * relowered / lanes
