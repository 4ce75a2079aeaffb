"""The benchmark's cells shrunk to sizes a CPU test run holds (tests only;
the benchmark runs the cells as BENCHMARK.json states them), and the
cells of `deferred.json` merged into the manifest.

A mix's CPU size is its own: the traffic file's `cpu` block holds the
`overrides` merged into the mix and the `requests` enough to check."""
import json
import os
from typing import List

import run
from clients import ROOT


def manifest(root: str = ROOT) -> dict:
    """BENCHMARK.json with the entries of `bench/deferred.json` added."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        m = json.load(f)
    with open(os.path.join(root, "bench", "deferred.json")) as f:
        d = json.load(f)
    for key in ("workloads", "end_to_end", "per_layer"):
        m[key] += d[key]
    return m


def cpu_cells(root: str = ROOT) -> List[str]:
    """The manifest's cells whose mix has a `cpu` block, in its order."""
    m = manifest(root)
    return [w["name"] for w in m["workloads"]
            if "cpu" in run.resolve(root, w["name"], m)["spec"]]


def small_cell(workload: str, root: str = ROOT) -> dict:
    """The resolved cell with its mix's `cpu` overrides merged in."""
    c = run.resolve(root, workload, manifest(root))

    def merge(dst, src):
        for k, v in src.items():
            if isinstance(v, dict):
                merge(dst[k], v)
            else:
                dst[k] = v
    merge(c["spec"], c["spec"]["cpu"]["overrides"])
    return c
