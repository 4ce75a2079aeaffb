"""Run one benchmark cell traced, with the program's own spans read too.

    python3 bench/program_trace.py --workload <name> --seed <n> --seconds <s>

The run is `run.py --trace 1`'s (`run.run_cell`), with its trace reduced
by `program_spans.ProgramTrace` in place of `xplane.Trace`.  So the cell's
per-layer metrics read what they read there, and besides them come the
metrics read from the program's spans (`SPAN_METRICS`), the seconds each
program span covers inside the window, device idle time by innermost
program span, the share of device idle time some program span covers,
and the traced window's cases per second (its cases over the end of its
last request).  The last stdout line is the result object of `run.py`
with those numbers under `program`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

import run

#: Per-layer metrics read from the program's spans (`bench/metrics/`).
SPAN_METRICS = ("classify_share.sweep", "upload_share.sweep",
                "fetch_share.sweep")


def trace_cell(c: dict, seed: int, seconds: float, *,
               require_chip: bool = True) -> dict:
    """`run.run_cell` traced, on a trace that keeps the program's spans;
    the result object with the program's numbers under `program`."""
    import xplane
    from program_spans import PROGRAM_PREFIX, ProgramTrace

    c = dict(c, per_layer=list(c["per_layer"]), readers=dict(c["readers"]))
    for name in SPAN_METRICS:
        c["per_layer"].append({"name": name, "unit": "%"})
        c["readers"][name] = os.path.join(c["root"], "bench", "metrics",
                                          name + ".py")
    kept: List[ProgramTrace] = []

    class Kept(ProgramTrace):
        @classmethod
        def from_file(cls, path: str) -> ProgramTrace:
            kept.append(super().from_file(path))
            return kept[-1]

    plain, xplane.Trace = xplane.Trace, Kept
    try:
        result, info = run.run_cell(c, seed, seconds, True,
                                    require_chip=require_chip)
    finally:
        xplane.Trace = plain
    tr = kept[-1]
    idle = tr.idle_by_program_span(len(tr.program_spans) + 1)
    idle_s = sum(v for _, v in idle)
    outside = dict(idle).get(xplane.IDLE_OUTSIDE_SPANS, 0.0)
    ends = info["requests end at (s into the window)"]
    names = sorted({n[len(PROGRAM_PREFIX):] for _, _, n in tr.program_spans})
    result["program"] = {
        "cases_per_s_traced": (result["attempted"] / ends[-1]
                               if ends and not result["failed"] else None),
        "requests": len(ends),
        "spans": len(tr.program_spans),
        "span_s": {n: tr.program_span_s(n) for n in names},
        "idle_by_program_span": idle,
        "idle_covered_pct": (100.0 * (idle_s - outside) / idle_s
                             if idle_s else None),
    }
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(run.BENCH)
    c = run.resolve(root, args.workload)
    run.configure_process(root)
    try:
        result = trace_cell(c, args.seed, args.seconds)
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
