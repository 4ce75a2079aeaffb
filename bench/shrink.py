"""The benchmark's cells shrunk to sizes a CPU test run holds (tests only;
the benchmark runs the cells as BENCHMARK.json states them), and the
cells of `deferred.json` merged into the manifest."""
import json
import os

import run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: traffic overrides per cell, and requests enough to check
SMALL = {
    "oem1-refresh": ({"candidates": {"parametric": {"count": 8},
                                     "deadline": {"count": 8}}}, 2),
    "oem1-refresh-4chip": ({"candidates": {"parametric": {"count": 8},
                                           "deadline": {"count": 8}}}, 2),
    "fleet-capped": ({"assignments": 8}, 2),
    "oem1-replan": ({"candidates": 16, "iterations": 2, "steps": 3}, 1),
}


def manifest() -> dict:
    """BENCHMARK.json with the entries of `bench/deferred.json` added."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    with open(os.path.join(ROOT, "bench", "deferred.json")) as f:
        d = json.load(f)
    for key in ("workloads", "end_to_end", "per_layer"):
        m[key] += d[key]
    return m


def small_cell(workload: str) -> dict:
    """The resolved cell with its traffic shrunk."""
    c = run.resolve(ROOT, workload, manifest())

    def merge(dst, src):
        for k, v in src.items():
            if isinstance(v, dict):
                merge(dst[k], v)
            else:
                dst[k] = v
    merge(c["spec"], SMALL[workload][0])
    return c
