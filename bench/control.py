"""Readings that set a cell's correctness limits: the program as the
configuration states it, and the lower-precision control, on many seeds
in one process.

    python3 bench/control.py --workload oem1-refresh \
        --seeds 11,12,13 --requests 4 --precisions fp64,mixed

For each precision and seed: the cell's inputs from the seed, `requests`
closed-loop requests at the cell's own size through the timed path (the
first compiles), then the same comparison with the plain reference that
decides a run's `correct`.  The control is the program's own "mixed"
dtype policy (fp32 per-slot dynamics, fp64 accumulators), the step below
the fp64 the configurations state.  One JSON line per reading, then a
summary line: the largest reading of the program (the lower reading) and
the smallest of the control (the upper reading), per compared number.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def readings(c: dict, seeds: List[int], requests: int, precision: str,
             carina) -> List[Dict]:
    """One reading per seed: {seed, precision, seconds, request_s (each
    request's host-clock seconds), checks}."""
    from clients import client_class
    cls = client_class(c["spec"]["kind"], c["root"])
    out = []
    for seed in seeds:
        t = time.perf_counter()
        client = cls(carina, c["cfg"], c["spec"], seed, c["cell"]["chips"],
                     precision=precision)
        client.build()
        took = []
        for k in range(1, requests + 1):
            t_k = time.perf_counter()
            client.request(k)
            took.append(time.perf_counter() - t_k)
        checks = client.check()
        out.append({"seed": seed, "precision": precision,
                    "seconds": time.perf_counter() - t,
                    "request_s": took,
                    "checks": {n: v for n, (v, _) in checks.items()}})
    return out


def summary(rows: List[Dict], program: str, control: str) -> Dict:
    """Lower (program's largest) and upper (control's smallest) reading
    of each compared number."""
    names = sorted({n for r in rows for n in r["checks"]})
    out = {}
    for n in names:
        low = [r["checks"][n] for r in rows if r["precision"] == program]
        up = [r["checks"][n] for r in rows if r["precision"] == control]
        out[n] = {"lower": max(low) if low else None,
                  "upper": min(up) if up else None}
    return out


def main(argv=None) -> int:
    import run
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--requests", type=int, default=1)
    ap.add_argument("--precisions", default="fp64,mixed")
    args = ap.parse_args(argv)
    root = os.path.dirname(BENCH)
    c = run.resolve(root, args.workload)
    run.configure_process(root)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"control: JAX found no TPU ({dev.platform})", file=sys.stderr)
        return 3
    import repro.carina as carina
    seeds = [int(s) for s in args.seeds.split(",")]
    precisions = args.precisions.split(",")
    rows = []
    for p in precisions:
        for r in readings(c, seeds, args.requests, p, carina):
            print(json.dumps(r), flush=True)
            rows.append(r)
    print(json.dumps({"workload": args.workload, "device": dev.device_kind,
                      "summary": summary(rows, precisions[0],
                                         precisions[-1])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
