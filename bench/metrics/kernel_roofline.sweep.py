"""kernel_roofline.sweep: the chunk kernels' share of their roofline, in
percent.

The kernels are bound by memory bandwidth (see `roofline.py`), so the
least time of the window's work is the bytes its useful lane-slots have
to read over the chips' HBM bandwidth; the share is that least time over
the device time of the chunk-kernel modules in the trace
(`xplane.KERNEL_MODULES`)."""
from __future__ import annotations

import roofline
from xplane import KERNEL_MODULES


def read(run):
    kernel_s = run.trace.module_time_s(KERNEL_MODULES)
    work = run.client.work()
    if kernel_s <= 0.0 or not work.get("lane_slots"):
        return None
    nbytes = roofline.useful_bytes(run.client.kernel, work["lane_slots"],
                                   work["group_slots"], work["members"],
                                   run.cfg["engine"]["dtype"])
    least = roofline.least_time_s(nbytes, run.device["kind"], run.chips)
    return 100.0 * least / kernel_s
