"""upload_share.sweep: share of the traced window the host spent putting
chunk inputs and carried state on the device (the program's
`carina.chunk.upload` spans), in percent.  Read from a trace reduced by
`program_spans.ProgramTrace`; None from one that keeps no program spans."""
from __future__ import annotations

from program_spans import window_share


def read(run):
    return window_share(run, "chunk.upload")
