"""The correctness check at sizes a test run holds: the program reads
inside each cell's limit, the lower-precision control reads outside it,
and the reference's calibration is the program's to the last bit."""
import pytest

import control
import reference as ref
import run
from shrink import ROOT, cpu_cells, small_cell


@pytest.mark.parametrize("workload", sorted(cpu_cells()))
def test_program_passes_and_control_fails(workload):
    import repro.carina as carina

    c = small_cell(workload)
    requests = c["spec"]["cpu"]["requests"]
    rows = (control.readings(c, [3, 2 ** 31 + 11], requests, "fp64", carina)
            + control.readings(c, [3], requests, "mixed", carina))
    limit = c["spec"]["check"]["max_rel_gap"]
    s = control.summary(rows, "fp64", "mixed")["max_rel_gap"]
    assert s["lower"] <= limit < s["upper"]
    assert all(r["checks"]["unscored"] == 0 for r in rows)


@pytest.mark.parametrize("config", ["oem1-campaign", "oem-fleet-capped"])
def test_reference_calibration_matches_the_program(config):
    import repro.carina as carina
    from clients import program_campaigns

    c = run.resolve(ROOT, "fleet-capped" if "fleet" in config
                    else "oem1-refresh")
    _, camps = program_campaigns(carina, c["cfg"])
    bands = ref.Bands(c["cfg"]["bands"])
    for campaign, camp in zip(c["cfg"]["campaigns"], camps):
        rate, machine = ref.calibrate(campaign, bands)
        wl, m = camp.calibrated()
        assert rate == wl.rate_at_full and machine["dyn_w"] == m.dyn_w
