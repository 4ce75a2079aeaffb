"""Traffic kinds are found by file: a cell of a new deployment lands as new
files only (a kind with its client and reference, a configuration, a mix
with its CPU size, manifest entries), on which the benchmark's own tests
pass unedited; and the kinds the cells run give the kernel, the work and
the roofline bytes they gave as one module."""
import filecmp
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import clients
import roofline
import run
import shrink
import xplane

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = shrink.ROOT

#: A kind written as a later change would write it: one campaign scores
#: the same seeded day schedules every request under a flat grid factor.
FLAT_SWEEP = '''"""The request client of traffic kind `flat_sweep`."""
import numpy as np

import generate
import reference as ref
from clients import Client, program_campaigns, scan_settings


class FlatSweep(Client):
    def build(self):
        c, s = self.carina, self.spec["schedules"]
        _, (self.camp,) = program_campaigns(c, self.cfg)
        self.wl, self.mach = self.camp.calibrated()
        self.logits = generate.parametric_logits(
            s, s["count"], generate.rng(self.seed, 1))
        self.schedules = [
            c.ParametricSchedule(tuple(float(v) for v in row),
                                 u_min=s["u_min"], u_max=s["u_max"],
                                 batch_size=s["batch_size"], name=f"flat-{i}")
            for i, row in enumerate(self.logits)]
        hours = 24 * self.cfg["engine"]["max_days"]
        self.carbon = c.trace_windows(
            np.full(hours, self.cfg["carbon"]["kg_per_kwh"]), hours)

    def request(self, k):
        c, eng = self.carina, self.cfg["engine"]
        cases = [c.SweepCase(s, self.wl, self.mach, self.camp.bands,
                             self.carbon, self.camp.start_hour, label=s.name)
                 for s in self.schedules]
        res = c.sweep(cases, progress_buckets=eng["progress_buckets"],
                      max_days=eng["max_days"], precision=self.precision,
                      devices=self.chips)
        self.records.append((k, res))
        return len(res)

    def size(self):
        return len(self.schedules)

    def check(self):
        if not self.records:
            return self.nothing_to_compare()
        s, campaign = self.spec["schedules"], self.cfg["campaigns"][0]
        bands = ref.Bands(self.cfg["bands"])
        rate, machine = ref.calibrate(campaign, bands)
        lanes = ref.lanes_for(
            campaign, rate, machine,
            table=ref.parametric_table(self.logits, s["u_min"], s["u_max"]),
            batch=np.full(len(self.logits), float(s["batch_size"])))
        want = ref.scan(lanes, bands.table(),
                        ref.constant_carbon(self.cfg["carbon"]["kg_per_kwh"]),
                        **scan_settings(self.cfg))
        want = np.stack([want["runtime_h"], want["kwh"], want["co2"][:, 0]],
                        axis=1)
        got = np.array([[(r.runtime_h, r.energy_kwh, r.co2_kg) for r in res]
                        for _, res in self.records])
        gap = ref.relative_gap(got, np.broadcast_to(want, got.shape))
        return {"max_rel_gap": (gap, self.spec["check"]["max_rel_gap"]),
                "unscored": (float((~np.isfinite(got)).any(axis=2).sum()),
                             0.0)}


CLIENT = FlatSweep
'''

FLAT_MIX = {
    "kind": "flat_sweep",
    "about": "the same 64 seeded day schedules every request",
    "schedules": {"count": 64, "slots": 24, "logit_mean": 0.0,
                  "logit_std": 1.5, "u_min": 0.45, "u_max": 1.0,
                  "batch_size": 50},
    "check": {"max_rel_gap": 1e-9},
    "cpu": {"overrides": {"schedules": {"count": 4}}, "requests": 2},
}

NEW_FILES = {"kinds/flat_sweep.py", "configs/oem1-flat.json",
             "traffic/flat-sweep.json"}


def files(top: str) -> set:
    return {os.path.relpath(os.path.join(d, f), top)
            for d, dirs, names in os.walk(top) if "__pycache__" not in d
            for f in names if not f.endswith(".pyc")}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout whose `bench/` and BENCHMARK.json are copies, with a cell
    of a new deployment added as new files and manifest entries only; the
    program's sources are linked in."""
    top = tmp_path_factory.mktemp("checkout")
    bench = top / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), top)
    os.symlink(os.path.join(ROOT, "src"), top / "src")
    (bench / "kinds" / "flat_sweep.py").write_text(FLAT_SWEEP)
    with open(bench / "configs" / "oem1-campaign.json") as f:
        cfg = json.load(f)
    cfg.update(name="oem1-flat", carbon={"kind": "constant",
                                         "kg_per_kwh": 0.448})
    (bench / "configs" / "oem1-flat.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "flat-sweep.json").write_text(json.dumps(FLAT_MIX))
    with open(top / "BENCHMARK.json") as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "oem1-flat", "source": "https://arxiv.org/abs/2605.24561",
        "file": "bench/configs/oem1-flat.json", "reduced": [],
        "why": "a test configuration"})
    manifest["workloads"].append({
        "name": "oem1-flat", "config": "oem1-flat", "traffic": "flat-sweep",
        "chips": 1, "why": "a test cell"})
    manifest["end_to_end"][0]["workloads"].append("oem1-flat")
    with open(top / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f)
    return top


def test_a_new_kind_runs_from_new_files_only(checkout):
    bench = checkout / "bench"
    assert "oem1-flat" in shrink.cpu_cells(str(checkout))
    c = shrink.small_cell("oem1-flat", str(checkout))
    assert c["spec"]["schedules"]["count"] == 4
    result, info = run.run_cell(c, 2 ** 31 + 2024, 0.01, False,
                                require_chip=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 4 * len(
        info["requests end at (s into the window)"])
    assert 0 < result["checks"]["max_rel_gap"]["value"] <= 1e-9
    assert set(result["metrics"]) == {"cases_per_s", "setup_s"}

    # no file of the copy but the manifest differs from bench/
    assert files(bench) - files(BENCH) == NEW_FILES
    assert files(BENCH) <= files(bench)
    for rel in files(BENCH):
        assert filecmp.cmp(os.path.join(BENCH, rel), bench / rel,
                           shallow=False), rel
    with pytest.raises(FileNotFoundError, match="flat_sweep.py"):
        clients.client_class("flat_sweep")


#: What the copy's own tests run for the new cell, found by name alone.
NEW_CELL_CASES = [
    "test_bench_faults.py::test_broken_timed_path_reads_incorrect"
    "[oem1-flat-state_unchanged]",
    "test_bench_faults.py::test_broken_timed_path_reads_incorrect"
    "[oem1-flat-half_left_out]",
    "test_bench_faults.py::test_broken_timed_path_reads_incorrect"
    "[oem1-flat-answer_altered]",
    "test_bench_faults.py::test_sound_run_reads_correct[oem1-flat]",
    "test_bench_control.py::test_program_passes_and_control_fails"
    "[oem1-flat]",
]


def test_the_benchmark_tests_pass_with_the_new_cell(checkout):
    """Every test of the copy's `bench/` but this file's two runs with the
    new cell in the manifest, and the cell-parametrised ones take it."""
    this = "bench/" + os.path.basename(__file__) + "::"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:randomly", "bench",
         "--deselect", this + "test_a_new_kind_runs_from_new_files_only",
         "--deselect", this + "test_the_benchmark_tests_pass_with_the_new_cell"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-6000:] + p.stderr[-2000:]
    for case in NEW_CELL_CASES:
        assert f"bench/{case} PASSED" in p.stdout, case


#: Each cell's kernel and work() keys as `clients.py` gave them when it
#: held every kind (the cells of today; a later cell brings its own).
BEFORE = {"oem1-refresh": ("plain", {"lane_slots", "group_slots", "members"}),
          "oem1-refresh-4chip": ("plain", {"lane_slots", "group_slots",
                                           "members"}),
          "fleet-capped": ("coupled", {"lane_slots", "group_slots",
                                       "members"}),
          "oem1-replan": ("plain", set())}


@pytest.mark.parametrize("workload", sorted(BEFORE))
def test_every_cell_finds_the_client_it_had(workload):
    import repro.carina as carina

    c = shrink.small_cell(workload)
    cls = clients.client_class(c["spec"]["kind"])
    assert issubclass(cls, clients.Client)
    assert cls is clients.client_class(c["spec"]["kind"], ROOT)
    client = cls(carina, c["cfg"], c["spec"], 5, 1)
    client.build()
    assert (cls.kernel, set(client.work())) == BEFORE[workload]
    assert cls.kernel_modules is xplane.KERNEL_MODULES


def roofline_reader():
    path = os.path.join(BENCH, "metrics", "kernel_roofline.sweep.py")
    return run.load_reader(path)


@pytest.mark.parametrize("workload", ["oem1-refresh", "fleet-capped"])
def test_useful_bytes_are_the_work_functions(workload):
    import repro.carina as carina

    c = shrink.small_cell(workload)
    client = clients.client_class(c["spec"]["kind"])(
        carina, c["cfg"], c["spec"], 2 ** 31 + 99, 1)
    client.build()
    for k in range(1, c["spec"]["cpu"]["requests"] + 1):
        client.request(k)
    dtype = c["cfg"]["engine"]["dtype"]
    w = client.work()
    nbytes = roofline.useful_bytes(client.kernel, w["lane_slots"],
                                   w["group_slots"], w["members"], dtype)
    assert nbytes > 0 and client.useful_bytes(dtype) == nbytes
    # the reader divides the same bytes by the same module time
    trace = types.SimpleNamespace(
        module_time_s=lambda pattern: 1e-3 if pattern is
        xplane.KERNEL_MODULES else 0.0)
    got = roofline_reader()(types.SimpleNamespace(
        trace=trace, client=client, cfg=c["cfg"], chips=1,
        device={"kind": "TPU v5 lite"}))
    assert got == 100.0 * roofline.least_time_s(nbytes, "TPU v5 lite",
                                                1) / 1e-3
