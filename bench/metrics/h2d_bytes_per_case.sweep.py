"""h2d_bytes_per_case.sweep: host-to-device bytes the executor's chunk
launches uploaded in the window (inputs and carried state, the program's
`scan_stats().bytes_uploaded`), per (schedule x carbon scenario) case
scored in the window."""
from __future__ import annotations


def read(run):
    cases = run.window["units"]
    if not cases or not run.stats.chunks:
        return None
    return run.stats.bytes_uploaded / cases
