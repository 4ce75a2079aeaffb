"""The work function and the peak table."""
import json
import os

import numpy as np
import pytest

import clients
import roofline

BENCH = os.path.dirname(os.path.abspath(__file__))


def test_useful_bytes_hand_computed():
    # plain: 10 lane-slots x ((51 members + 4) x 8 B + 4 B index)
    assert roofline.useful_bytes("plain", 10, 0, 51, "float64") == 4440
    # coupled: 6 lane-slots x (5 x 8 + 4) + 3 group-slots x 8
    assert roofline.useful_bytes("coupled", 6, 3, 1, "float64") == 288
    assert roofline.useful_bytes("plain", 2, 0, 1, "float32") == 2 * 24
    with pytest.raises(ValueError):
        roofline.useful_bytes("pallas", 1, 0, 1, "float64")


def test_peaks_by_device_kind():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        roofline.peaks("TPU v4")
    assert roofline.least_time_s(819e9, "TPU v5 lite", 1) == pytest.approx(1)
    assert roofline.least_time_s(819e9, "TPU v5 lite", 4) == \
        pytest.approx(0.25)


def test_useful_slots_count_hours_with_work():
    assert clients.useful_slots([1.0, 1.5, 2.0, 0.25]) == 1 + 2 + 2 + 1


def test_padding_does_not_change_the_work():
    """Five cases pad to eight scan lanes; the work counts the five."""
    import repro.carina as carina

    with open(os.path.join(BENCH, "configs", "oem1-campaign.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", "refresh.json")) as f:
        spec = json.load(f)
    spec["candidates"]["parametric"]["count"] = 3
    spec["candidates"]["deadline"]["count"] = 2
    d = clients.client_class("refresh")(carina, cfg, spec, 7, 1)
    d.build()
    carina.reset_scan_stats()
    d.request(1)
    padded = carina.scan_stats().slot_work
    work = d.work()
    rt = d.answers()[0]
    assert work["lane_slots"] == int(np.ceil(rt - 1e-9).sum())
    assert work["members"] == 51
    assert padded > work["lane_slots"]
    # the same five cases in a batch padded differently: same work
    d2 = clients.client_class("refresh")(carina, cfg, spec, 7, 1)
    d2.build()
    d2.schedules = d2.schedules + d2.schedules[:1]   # six lanes, pad to 8
    d2.request(1)
    rt2 = d2.answers()[0][:, :5]
    assert clients.useful_slots(rt2) == work["lane_slots"]
