"""The program's own profiler spans in a benchmark trace.

    tr = ProgramTrace.from_file(path)       # an `xplane.Trace`, and besides:
    tr.program_spans                        # [(start ns, end ns, name)]
    tr.program_span_s("plan.classify")      # seconds inside the window
    tr.idle_by_program_span(10)             # device idle by program span

The engine writes `jax.profiler.TraceAnnotation`s named with
`PROGRAM_PREFIX` (`repro.core.engine_jax.SPAN_PREFIX`) around the phases
of each sweep; the profiler keeps their keyword metadata (`cases`, `seq`)
as event stats, so the event name is the plain span name.
`xplane.Trace` reads only the benchmark's `bench:` spans and leaves these
out, so every number it reads is the same from a `ProgramTrace`.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional

from xplane import (Trace, clip, complement, label_gaps, length)

PROGRAM_PREFIX = "carina."


class ProgramTrace(Trace):
    """An `xplane.Trace` that also keeps the program's spans."""

    def __init__(self, ops, spans, modules=None, program_spans=()):
        super().__init__(ops, spans, modules)
        self.program_spans = list(program_spans)

    @classmethod
    def from_profile(cls, pd) -> "ProgramTrace":
        tr = super().from_profile(pd)
        tr.program_spans = [
            (float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns),
             ev.name)
            for plane in pd.planes if not plane.name.startswith("/device:TPU:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PROGRAM_PREFIX)]
        return tr

    def program_span_s(self, name: str) -> float:
        """Seconds inside the window covered by program spans of this
        name (`name` without the prefix)."""
        lo, hi = self._bounds()
        return length(clip(((s, e) for s, e, n in self.program_spans
                            if n == PROGRAM_PREFIX + name), lo, hi)) * 1e-9

    def idle_by_program_span(self, n: int = 10) -> List[List]:
        """[[program span, seconds]] of device idle time by the innermost
        program span open on the host, mean over devices; idle time under
        none is `xplane.IDLE_OUTSIDE_SPANS`."""
        lo, hi = self._bounds()
        spans = [(s, e, name[len(PROGRAM_PREFIX):])
                 for s, e, name in self.program_spans]
        tot: Dict[str, float] = defaultdict(float)
        for dev in self.ops:
            gaps = complement(self.busy_intervals(dev), lo, hi)
            for label, ns in label_gaps(gaps, spans).items():
                tot[label] += ns * 1e-9
        k = max(len(self.ops), 1)
        return [[label, v / k] for label, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def window_share(run, name: str) -> Optional[float]:
    """Percent of the traced window covered by program span `name`, for
    a per-layer reader; None where the trace has no window or no program
    spans (a trace reduced by plain `xplane.Trace` keeps none)."""
    tr = run.trace
    if tr.window is None or not getattr(tr, "program_spans", None):
        return None
    return 100.0 * tr.program_span_s(name) / tr.window_s()
