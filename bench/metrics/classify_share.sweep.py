"""classify_share.sweep: share of the traced window the host spent
classifying the cases planning found in no cache (the program's
`carina.plan.classify` span: probe, decision tables, duration estimate,
cache write-through), in percent.  Read from a trace reduced by
`program_spans.ProgramTrace`; None from one that keeps no program spans."""
from __future__ import annotations

from program_spans import window_share


def read(run):
    return window_share(run, "plan.classify")
