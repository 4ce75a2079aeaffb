"""device_idle.sweep: share of the traced window in which no operation
ran on the chip (1 - union of device-op intervals / window, mean over the
cell's chips), in percent."""
from __future__ import annotations


def read(run):
    tr = run.trace
    if tr.window is None or not tr.ops:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s())
