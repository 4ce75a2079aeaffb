"""A run with its timed path broken underneath must read `correct` false.

Each test drives the rest of a run (the look for a chip skipped) on a cell
shrunk to CPU size, with one fault planted after the warm-up: a chunk
step that hands back its state unchanged, half of a batch's answers
replaced by the mean of the other half, or one answer altered where it is
produced.  The faults are those the cell's kind names (`Client.faults`),
each planted by the kind (`Client.plant`).  The cells are those whose
mix has a `cpu` block (`cells`); the sharded path of the four-chip
refresh runs in `test_bench_sharded.py`'s child process, with its own
fault: one chip's results left out."""
import json

import jax
import pytest

import clients
import run
from shrink import cpu_cells, small_cell



def cells() -> list:
    """Every cell whose mix has a `cpu` block, run on the devices this
    process holds (the program clamps a cell to them), less each cell on
    more chips whose run that would make the run of a held cell: the same
    configuration and the same mix, but for its `about`."""
    def run_of(w):
        c = small_cell(w)
        spec = {k: v for k, v in c["spec"].items() if k != "about"}
        return c["cell"]["config"], json.dumps(spec, sort_keys=True)
    chips = {w: small_cell(w)["cell"]["chips"] for w in cpu_cells()}
    held = {run_of(w) for w, n in chips.items() if n <= len(jax.devices())}
    return [w for w, n in chips.items()
            if n <= len(jax.devices()) or run_of(w) not in held]


CELLS = cells()
CASES = [(w, f) for w in CELLS
         for f in clients.client_class(small_cell(w)["spec"]["kind"]).faults]


@pytest.mark.parametrize("workload,fault", CASES,
                         ids=[f"{w}-{f}" for w, f in CASES])
def test_broken_timed_path_reads_incorrect(monkeypatch, workload, fault):
    c = small_cell(workload)
    cls = clients.client_class(c["spec"]["kind"])
    orig = cls.warm_up

    def warm_up_then_break(self):
        orig(self)
        self.plant(fault, monkeypatch)
    monkeypatch.setattr(cls, "warm_up", warm_up_then_break)
    result, _ = run.run_cell(c, 5, 0.01, False, require_chip=False)
    assert result["correct"] is False
    assert result["failed"] > 0 or any(
        c["value"] > c["limit"] for c in result["checks"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_reads_correct(workload):
    result, _ = run.run_cell(small_cell(workload), 5, 0.01, False,
                             require_chip=False)
    assert result["correct"] is True and result["failed"] == 0
