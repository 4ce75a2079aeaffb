"""Algorithm-1 support features: system auto-detection and unit-log
verification (resume/merge/verify)."""
import json
import os

import pytest

from repro.core import GridCarbonModel, RunTracker
from repro.core.sysinfo import (chip_profile_from_host, detect_host,
                                machine_profile_from_host)
from repro.core.verify import verify_unit_log


def test_detect_host_fields():
    info = detect_host()
    assert info["cpus"] >= 1
    assert "jax_backend" in info


def test_machine_profile_autodetect():
    m = machine_profile_from_host()
    assert m.idle_w > 0 and m.dyn_w > m.idle_w * 0.5
    assert m.name.startswith("auto-")


def test_chip_profile_autodetect_defaults_v5e():
    """Profiles are looked up by the exact device kind jax reports: a
    v5e reports "TPU v5 lite" (which must not match v5p's "TPU v5"),
    and a kind not in the table is an error, never a default."""
    c = chip_profile_from_host({"jax_device_kind": "TPU v5 lite"})
    assert c.name == "tpu-v5e" and c.peak_flops == 197e12
    assert chip_profile_from_host(
        {"jax_device_kind": "TPU v5"}).name == "tpu-v5p"
    assert chip_profile_from_host(
        {"jax_device_kind": "TPU v4"}).name == "tpu-v4"
    for kind in ("cpu", "TPU v6 lite", None):
        with pytest.raises(ValueError, match="no chip profile"):
            chip_profile_from_host({"jax_device_kind": kind})


def test_verify_clean_log(tmp_path):
    log = tmp_path / "units.jsonl"
    t = RunTracker("v", log_path=str(log))
    for i in range(5):
        t.record_unit(phase="night", intensity=0.9, runtime_s=10.0,
                      energy_kwh=0.02, sim_time_h=float(i))
    t.close()
    rep = verify_unit_log(str(log))
    assert rep.ok, rep.errors
    assert rep.n_units == 5
    assert abs(rep.energy_kwh - 0.1) < 1e-9


def test_verify_detects_tampering(tmp_path):
    log = tmp_path / "units.jsonl"
    t = RunTracker("v", log_path=str(log))
    for i in range(3):
        t.record_unit(phase="peak", intensity=0.4, runtime_s=5.0,
                      energy_kwh=0.01, sim_time_h=float(i))
    t.close()
    lines = log.read_text().splitlines()
    rec = json.loads(lines[1])
    rec["co2_kg"] *= 2            # corrupt the carbon translation
    lines[1] = json.dumps(rec)
    log.write_text("\n".join(lines) + "\n")
    rep = verify_unit_log(str(log))
    assert not rep.ok
    assert any("carbon mismatch" in e for e in rep.errors)


def test_verify_detects_missing_units_vs_summary(tmp_path):
    log = tmp_path / "units.jsonl"
    t = RunTracker("v", log_path=str(log))
    for i in range(4):
        t.record_unit(phase="shoulder", intensity=0.9, runtime_s=5.0,
                      energy_kwh=0.01, sim_time_h=float(i))
    t.close()
    lines = log.read_text().splitlines()
    del lines[0]                  # lose a unit (simulated crash/partial copy)
    log.write_text("\n".join(lines) + "\n")
    rep = verify_unit_log(str(log))
    assert not rep.ok
    assert any("summary" in e for e in rep.errors)
