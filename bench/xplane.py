"""Reduce a profiler trace (`.xplane.pb`) to the benchmark's device numbers.

    tr = Trace.from_file(path)          # or Trace(ops, spans) in tests
    tr.window                           # the "bench:window" span (ns)
    tr.busy_s(), tr.window_s()          # device busy time, traced window
    tr.module_time_s(KERNEL_MODULES)    # device time of matching modules
    tr.top_ops(10), tr.idle_by_span(10) # the breakdown's two lists

Device operations come from the TPU planes ("/device:TPU:<n>", their
"XLA Ops" and "XLA Modules" lines).  On a CPU trace, which has no device
plane, the XLA operations the host threads ran stand in for them (events
carrying an `hlo_module` stat): that is how the reduction is tested
without a chip.  Host spans are the benchmark's own `TraceAnnotation`s,
named with the `SPAN_PREFIX`.

Busy time is the union of a device's operation intervals inside the
window, averaged over the devices; an idle gap is a stretch of the window
in which the device ran nothing, and it is labelled with the innermost
benchmark span open on the host at each instant of it.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "bench:"
WINDOW = SPAN_PREFIX + "window"
IDLE_OUTSIDE_SPANS = "no span"

#: Module names of the chunk kernels (plain, site-coupled, and their
#: shard_map wrappers all trace `_scan_chunk_jax*_impl`).  The one place
#: the kernel's name pattern lives.
KERNEL_MODULES = re.compile(r"_scan_chunk_jax")

#: Control-flow operations whose events span the operations of their
#: bodies; the breakdown leaves them out so no time is counted twice.
CONTAINER_OPS = ("while", "conditional", "call")

Interval = Tuple[float, float]


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------
def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def complement(busy: List[Interval], lo: float, hi: float
               ) -> List[Interval]:
    """The gaps of a merged interval list inside [lo, hi)."""
    gaps, t = [], lo
    for s, e in clip(busy, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def innermost(spans: List[Tuple[float, float, str]], lo: float, hi: float
              ) -> List[Tuple[float, float, str]]:
    """Partition [lo, hi) by the innermost span open at each instant (the
    one that started last among those open), `IDLE_OUTSIDE_SPANS` where
    none is."""
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        open_ = [(s, name) for s, e, name in spans if s <= mid < e]
        label = max(open_)[1] if open_ else IDLE_OUTSIDE_SPANS
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def label_gaps(gaps: List[Interval], spans: List[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Nanoseconds of gap under each innermost host span."""
    if not gaps:
        return {}
    lo, hi = gaps[0][0], gaps[-1][1]
    parts = innermost([s for s in spans if s[1] > lo and s[0] < hi], lo, hi)
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for gs, ge in gaps:
        while j < len(parts) and parts[j][1] <= gs:
            j += 1
        i = j
        while i < len(parts) and parts[i][0] < ge:
            a, b, label = parts[i]
            out[label] += min(b, ge) - max(a, gs)
            i += 1
    return dict(out)


# ---------------------------------------------------------------------------
# The trace
# ---------------------------------------------------------------------------
class Op:
    __slots__ = ("start", "end", "name", "module")

    def __init__(self, start: float, end: float, name: str, module: str):
        self.start, self.end, self.name, self.module = start, end, name, module


class Trace:
    """Device operations per device, module executions per device, and
    the benchmark's host spans, all in nanoseconds on one clock."""

    def __init__(self, ops: Dict[str, List[Op]],
                 spans: List[Tuple[float, float, str]],
                 modules: Optional[Dict[str, List[Op]]] = None):
        self.ops = ops
        self.spans = spans
        self.modules = modules if modules is not None else {
            dev: _modules_from_ops(lst) for dev, lst in ops.items()}
        windows = [(s, e) for s, e, n in spans if n == WINDOW]
        self.window = windows[0] if windows else None

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(path))

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        ops: Dict[str, List[Op]] = defaultdict(list)
        modules: Dict[str, List[Op]] = defaultdict(list)
        host_ops: List[Op] = []
        spans = []
        for plane in pd.planes:
            device = plane.name.startswith("/device:TPU:")
            for line in plane.lines:
                for ev in line.events:
                    s = float(ev.start_ns)
                    e = s + float(ev.duration_ns)
                    if device and line.name == "XLA Ops":
                        ops[plane.name].append(
                            Op(s, e, _op_name(ev.name),
                               _stat(ev, "hlo_module")))
                    elif device and line.name == "XLA Modules":
                        modules[plane.name].append(Op(s, e, ev.name,
                                                      ev.name))
                    elif not device and ev.name.startswith(SPAN_PREFIX):
                        spans.append((s, e, ev.name))
                    elif not device and plane.name.startswith("/host:"):
                        mod = _stat(ev, "hlo_module")
                        if mod:
                            host_ops.append(Op(s, e, ev.name, mod))
        if not ops and host_ops:        # a CPU trace: no device plane
            ops["/host:CPU"] = host_ops
            return cls(dict(ops), spans)
        for dev in ops:
            modules.setdefault(dev, _modules_from_ops(ops[dev]))
        return cls(dict(ops), spans, dict(modules))

    # ---- window and busy time -------------------------------------------
    def _bounds(self) -> Interval:
        if self.window is None:
            raise ValueError(f"trace has no {WINDOW!r} span")
        return self.window

    def window_s(self) -> float:
        lo, hi = self._bounds()
        return (hi - lo) * 1e-9

    def busy_intervals(self, device: str) -> List[Interval]:
        lo, hi = self._bounds()
        return merge(clip(((o.start, o.end) for o in self.ops[device]),
                          lo, hi))

    def busy_s(self) -> float:
        """Seconds some operation ran, mean over devices."""
        if not self.ops:
            return 0.0
        return sum(length(self.busy_intervals(d)) for d in self.ops) \
            / len(self.ops) * 1e-9

    def module_time_s(self, pattern: re.Pattern) -> float:
        """Device seconds of matching module executions inside the
        window, mean over devices."""
        if not self.modules:
            return 0.0
        lo, hi = self._bounds()
        tot = 0.0
        for mods in self.modules.values():
            tot += length(clip(((m.start, m.end) for m in mods
                                if pattern.search(m.name)), lo, hi))
        return tot / len(self.modules) * 1e-9

    def span_s(self, name: str) -> float:
        """Seconds inside the window covered by spans of this name."""
        lo, hi = self._bounds()
        return length(clip(((s, e) for s, e, n in self.spans
                            if n == SPAN_PREFIX + name), lo, hi)) * 1e-9

    # ---- breakdown -------------------------------------------------------
    def top_ops(self, n: int = 10) -> List[List]:
        """[[op name, seconds]] of the costliest operations (control-flow
        containers left out), mean over devices, inside the window."""
        lo, hi = self._bounds()
        tot: Dict[str, float] = defaultdict(float)
        for lst in self.ops.values():
            for o in lst:
                if o.name.startswith(CONTAINER_OPS):
                    continue
                s, e = max(o.start, lo), min(o.end, hi)
                if e > s:
                    tot[o.name] += (e - s) * 1e-9
        k = max(len(self.ops), 1)
        return [[name, v / k] for name, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_span(self, n: int = 10) -> List[List]:
        """[[host span, seconds]] of device idle time by what the host
        was doing, mean over devices."""
        lo, hi = self._bounds()
        spans = [(s, e, name[len(SPAN_PREFIX):]) for s, e, name in
                 self.spans if name != WINDOW]
        tot: Dict[str, float] = defaultdict(float)
        for dev in self.ops:
            gaps = complement(self.busy_intervals(dev), lo, hi)
            for label, ns in label_gaps(gaps, spans).items():
                tot[label] += ns * 1e-9
        k = max(len(self.ops), 1)
        return [[label, v / k] for label, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def _op_name(text: str) -> str:
    """"fusion.12" from a TPU op event's HLO text
    ("%fusion.12 = f32[...] fusion(...)"); other names pass through."""
    head = text.split(" = ", 1)[0] if " = " in text else text
    return head.lstrip("%").strip()


def _stat(ev, key: str) -> str:
    for k, v in ev.stats:
        if k == key:
            return str(v)
    return ""


def _modules_from_ops(ops: List[Op]) -> List[Op]:
    """Module executions as the union of their operations' intervals
    (per module name)."""
    by: Dict[str, List[Interval]] = defaultdict(list)
    for o in ops:
        by[o.module].append((o.start, o.end))
    return [Op(s, e, mod, mod) for mod, iv in by.items()
            for s, e in merge(iv)]
