"""Traffic generation and the harness's lookup of cells by name."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np

import clients
import generate
import run

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_inputs():
    cfg = load("configs", "oem1-campaign")
    refresh, fleet = load("traffic", "refresh"), load("traffic",
                                                       "fleet-refresh")
    seed = 2 ** 31 + 12345
    a = generate.archive(cfg["carbon"], 400, seed)
    np.testing.assert_array_equal(a, generate.archive(cfg["carbon"], 400,
                                                      seed))
    assert not np.array_equal(a, generate.archive(cfg["carbon"], 400,
                                                  seed + 1))
    c1 = generate.refresh_candidates(refresh["candidates"], seed)
    c2 = generate.refresh_candidates(refresh["candidates"], seed)
    np.testing.assert_array_equal(c1["logits"], c2["logits"])
    np.testing.assert_array_equal(c1["deadline_h"], c2["deadline_h"])
    f1 = generate.fleet_assignments(fleet, 2, seed, 3)
    np.testing.assert_array_equal(f1, generate.fleet_assignments(
        fleet, 2, seed, 3))
    assert not np.array_equal(f1, generate.fleet_assignments(fleet, 2,
                                                             seed, 4))
    assert generate.search_seed(seed, 2) == generate.search_seed(seed, 2)
    assert generate.search_seed(-5, 0) == generate.search_seed(2 ** 64 - 5,
                                                               0)


def test_forecasts_move_on_and_carbon_blind_candidates_hit_the_plan_memo():
    """Each refresh brings a new forecast, one day on; the candidates'
    plans do not read carbon, so the second refresh finds all six in the
    program's plan memo."""
    import repro.carina as carina

    cfg, spec = load("configs", "oem1-campaign"), load("traffic", "refresh")
    spec["candidates"]["parametric"]["count"] = 3
    spec["candidates"]["deadline"]["count"] = 3
    d = clients.client_class("refresh")(carina, cfg, spec, 99, 1)
    d.build()
    m1, m2 = d.members(1), d.members(2)
    assert m1.shape == m2.shape == (51, 336)
    assert not np.array_equal(m1, m2)
    np.testing.assert_array_equal(m1[:, 24:], m2[:, :-24])  # one day on
    d.request(1)
    carina.reset_scan_stats()
    d.request(2)
    st = carina.scan_stats()
    assert st.plan_hits == 6 and st.plan_misses == 0


def test_four_chip_mix_is_the_one_chip_mix():
    """`oem1-refresh-4chip` needs a mix file of its own (a configuration
    and mix pair names one cell), and is compared with `oem1-refresh`
    request for request: the two files differ in `about` alone."""
    one, four = load("traffic", "refresh"), load("traffic", "refresh-4chip")
    one.pop("about"), four.pop("about")
    assert one == four


def run_script(root: str, workload: str = "oem1-refresh"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", workload, "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300, cwd=root)


def test_harness_refuses_a_device_that_is_not_a_tpu():
    p = run_script(ROOT)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_harness_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_script(str(tmp_path))
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_new_traffic_and_cell_are_found_by_name(tmp_path):
    """A mix, a cell and a per-layer metric added as new files and new
    manifest entries run with no edit to any existing file."""
    for part in ("configs", "traffic", "kinds", "metrics"):
        shutil.copytree(os.path.join(BENCH, part), tmp_path / "bench" / part)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    small = load("traffic", "refresh")
    small["candidates"]["parametric"]["count"] = 2
    small["candidates"]["deadline"]["count"] = 2
    with open(tmp_path / "bench" / "traffic" / "refresh-tiny.json",
              "w") as f:
        json.dump(small, f)
    (tmp_path / "bench" / "metrics" / "requests.tiny.py").write_text(
        "def read(run):\n    return run.window['requests']\n")
    manifest["workloads"].append({
        "name": "oem1-tiny", "config": "oem1-campaign",
        "traffic": "refresh-tiny", "chips": 1, "why": "a test cell"})
    manifest["end_to_end"][0]["workloads"].append("oem1-tiny")
    manifest["per_layer"].append({
        "name": "requests.tiny", "unit": "1", "better": "higher",
        "source": "host_clock", "layer": "Planning",
        "moves": "cases_per_s", "workloads": ["oem1-tiny"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f)

    c = run.resolve(str(tmp_path), "oem1-tiny")
    assert c["spec"]["candidates"]["parametric"]["count"] == 2
    assert c["cfg"]["name"] == "oem1-campaign"
    assert [m["name"] for m in c["e2e"]] == ["cases_per_s", "setup_s"]
    assert list(c["readers"]) == ["requests.tiny"]
    assert run.load_reader(c["readers"]["requests.tiny"])(
        type("R", (), {"window": {"requests": 3}})) == 3
    result, _ = run.run_cell(c, 5, 0.01, False, require_chip=False)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == 4 * 51
    assert set(result["metrics"]) == {"cases_per_s", "setup_s"}
    assert list(result)[-1] == "checks"
