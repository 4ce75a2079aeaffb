"""A run with its timed path broken underneath must read `correct` false.

Each test drives the rest of a run (the look for a chip skipped) on a cell
shrunk to CPU size, with one fault planted after the warm-up: a chunk
step that hands back its state unchanged, half of a batch's answers
replaced by the mean of the other half, or one answer altered where it is
produced.  These cells run on one chip; the four-chip path's fault, one
chip's results left out, is planted in `test_bench_sharded.py`."""
import numpy as np
import pytest

import clients
import run
from shrink import small_cell

STATE_AT = {"_scan_chunk_jax": 7, "_scan_chunk_jax_coupled": 10}


def state_unchanged(monkeypatch):
    from repro.core import engine_jax
    for name, first in STATE_AT.items():
        n = 6 if "coupled" in name else 5
        monkeypatch.setattr(engine_jax, name,
                            lambda *a, _i=first, _n=n, **kw: a[_i:_i + _n])


def half_left_out(monkeypatch):
    from repro.core import engine_jax
    orig = engine_jax.summarize_plan

    def summarize(plan, state):
        out = orig(plan, state)
        h = len(out) // 2
        for f in ("runtime_h", "energy_kwh", "co2_kg"):
            mean = float(np.mean([getattr(r, f) for r in out[:h]]))
            for r in out[h:]:
                setattr(r, f, mean)
        return out
    monkeypatch.setattr(engine_jax, "summarize_plan", summarize)


def answer_altered(monkeypatch):
    from repro.core import engine_jax
    orig = engine_jax.summarize_plan

    def summarize(plan, state):
        out = orig(plan, state)
        out[len(out) // 2].energy_kwh *= 1.0 + 1e-6
        return out
    monkeypatch.setattr(engine_jax, "summarize_plan", summarize)


FAULTS = {"state_unchanged": state_unchanged, "half_left_out": half_left_out,
          "answer_altered": answer_altered}
CASES = [(w, f) for w in ("oem1-refresh", "fleet-capped", "oem1-replan")
         for f in FAULTS if not (w == "oem1-replan" and f == "half_left_out")]


@pytest.mark.parametrize("workload,fault", CASES,
                         ids=[f"{w}-{f}" for w, f in CASES])
def test_broken_timed_path_reads_incorrect(monkeypatch, workload, fault):
    c = small_cell(workload)
    cls = clients.CLIENTS[c["spec"]["kind"]]
    orig = cls.warm_up

    def warm_up_then_break(self):
        orig(self)
        FAULTS[fault](monkeypatch)
    monkeypatch.setattr(cls, "warm_up", warm_up_then_break)
    result, _ = run.run_cell(c, 5, 0.01, False, require_chip=False)
    assert result["correct"] is False
    assert result["failed"] > 0 or any(
        c["value"] > c["limit"] for c in result["checks"].values())


@pytest.mark.parametrize("workload", ["oem1-refresh", "fleet-capped",
                                      "oem1-replan"])
def test_sound_run_reads_correct(workload):
    result, _ = run.run_cell(small_cell(workload), 5, 0.01, False,
                             require_chip=False)
    assert result["correct"] is True and result["failed"] == 0
