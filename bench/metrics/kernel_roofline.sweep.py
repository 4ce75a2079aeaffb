"""kernel_roofline.sweep: the chunk kernels' share of their roofline, in
percent.

The kernels are bound by memory bandwidth (see `roofline.py`), so the
least time of the window's work is the bytes its useful lane-slots have
to read over the chips' HBM bandwidth; the share is that least time over
the device time of the chunk-kernel modules in the trace.  The traffic
kind's client gives both the bytes (`useful_bytes`) and the modules
(`kernel_modules`), so a kind with a kernel of its own counts its own."""
from __future__ import annotations

import roofline


def read(run):
    kernel_s = run.trace.module_time_s(run.client.kernel_modules)
    if kernel_s <= 0.0:
        return None
    nbytes = run.client.useful_bytes(run.cfg["engine"]["dtype"])
    if not nbytes:
        return None
    least = roofline.least_time_s(nbytes, run.device["kind"], run.chips)
    return 100.0 * least / kernel_s
