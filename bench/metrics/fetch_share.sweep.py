"""fetch_share.sweep: share of the traced window the host spent in the
program's `carina.chunk.fetch` spans, in percent: waiting for each chunk
kernel and copying its results to the host.  It holds the kernels' device
time; fetch minus device busy is the wait for the launch and the copy.
Read from a trace reduced by `program_spans.ProgramTrace`; None from one
that keeps no program spans."""
from __future__ import annotations

from program_spans import window_share


def read(run):
    return window_share(run, "chunk.fetch")
