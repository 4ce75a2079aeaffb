"""`plan_hit_share.sweep`: the reader on synthetic counters, and traced
runs of the refresh and fleet cells shrunk to CPU size.  The refresh
re-scores the same carbon-blind candidates against a new forecast each
request, so after the warm-up every lookup is a memo hit; the fleet's
assignments are fresh each request, so none is."""
import importlib.util
import os
import types

import pytest

import run
from shrink import small_cell

BENCH = os.path.dirname(os.path.abspath(__file__))


def reader():
    path = os.path.join(BENCH, "metrics", "plan_hit_share.sweep.py")
    spec = importlib.util.spec_from_file_location("reader_plan_hit_share",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("hits, disk, misses, share", [
    (6, 0, 0, 100.0), (0, 0, 6, 0.0), (1, 2, 1, 75.0), (0, 0, 0, None)])
def test_plan_hit_share_reader(hits, disk, misses, share):
    stats = types.SimpleNamespace(plan_hits=hits, disk_hits=disk,
                                  plan_misses=misses)
    assert reader()(types.SimpleNamespace(stats=stats)) == share


@pytest.mark.parametrize("workload, share", [("oem1-refresh", 100.0),
                                             ("fleet-capped", 0.0)])
def test_plan_hit_share_in_a_traced_window(workload, share):
    c = small_cell(workload)
    # the CPU has no entry in the roofline's peak table
    c["per_layer"] = [m for m in c["per_layer"]
                      if m["name"] != "kernel_roofline.sweep"]
    result, info = run.run_cell(c, 2**31 + 777, 0.01, True,
                                require_chip=False)
    assert result["correct"] is True
    assert len(info["requests end at (s into the window)"]) >= 1
    assert result["metrics"]["plan_hit_share.sweep"]["value"] == share
