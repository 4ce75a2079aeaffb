"""The rescore cell's readers (`recompute_share.rescore`,
`relower_share.rescore`) on synthetic counters, and the cell shrunk to CPU
size: a traced window reads the revised share of the candidate set in
both, compiles nothing, and the kernel's work counts the re-scanned cases
alone."""
import os
import types

import pytest

import clients
import run
from shrink import small_cell

BENCH = os.path.dirname(os.path.abspath(__file__))
SHARES = ("recompute_share.rescore", "relower_share.rescore")


def reader(name):
    return run.load_reader(os.path.join(BENCH, "metrics", name + ".py"))


@pytest.mark.parametrize("recomputed, spliced, relowered, shares", [
    (20, 2028, 20, (100 * 20 / 2048, 100 * 20 / 2048)),
    (20, 2028, 2048, (100 * 20 / 2048, 100.0)),
    (20, 2028, None, (100 * 20 / 2048, None)),     # a program without it
    (0, 0, 0, (None, None)),                       # no delta in the window
], ids=["changed-rows", "full-restack", "no-counter", "no-delta"])
def test_recurrence_readers(recomputed, spliced, relowered, shares):
    stats = types.SimpleNamespace(lanes_recomputed=recomputed,
                                  lanes_spliced=spliced)
    if relowered is not None:
        stats.lanes_relowered = relowered
    got = [reader(n)(types.SimpleNamespace(stats=stats)) for n in SHARES]
    for g, want in zip(got, shares):
        assert g == (None if want is None else pytest.approx(want))


def test_rescore_window_at_cpu_size():
    c = small_cell("oem1-rescore")
    spec = c["spec"]
    S = sum(spec["candidates"][f]["count"] for f in ("parametric",
                                                      "deadline"))
    result, info = run.run_cell(c, 2 ** 31 + 1601, 0.2, True,
                                require_chip=False)
    assert result["correct"] is True and result["failed"] == 0
    requests = len(info["requests end at (s into the window)"])
    assert result["attempted"] == requests * S * 51
    assert info["programs compiled or loaded in the window"] == 0
    assert set(result["metrics"]) == {"device_idle.sweep",
                                      "lane_pad_share.sweep", *SHARES}
    for name in SHARES:
        assert result["metrics"][name]["value"] == pytest.approx(
            100.0 * spec["changed"] / S)


def test_work_counts_the_rescanned_cases_alone():
    import repro.carina as carina

    c = small_cell("oem1-rescore")
    client = clients.client_class("rescore")(carina, c["cfg"], c["spec"],
                                             2 ** 31 + 1602, 1)
    client.build()
    client.warm_up()
    for k in range(1, c["spec"]["cpu"]["requests"] + 1):
        client.request(k)
    rescanned, every = [], []
    for _, res, recomputed, _ in client.records:
        assert len(recomputed) == c["spec"]["changed"]
        rescanned += [res[i].runtime_h for i in recomputed]
        every += [r.runtime_h for r in res]
    work = client.work()
    assert work["lane_slots"] == clients.useful_slots(rescanned) > 0
    assert work["lane_slots"] < clients.useful_slots(every)
    assert (work["group_slots"], work["members"]) == (0, 51)


def test_rescore_deployment_runs_the_refresh_campaign():
    """The rescore cycle's configuration is OEM workflow 1 as the refresh
    runs it: the same campaign, calibration, bands, carbon and engine, so
    the two cells read on one scale; it adds its cycle and guarantees."""
    import json

    with open(os.path.join(clients.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w["config"] for w in manifest["workloads"]}
    assert cells["oem1-rescore"] != cells["oem1-refresh"]
    refresh = run.resolve(clients.ROOT, "oem1-refresh")["cfg"]
    rescore = run.resolve(clients.ROOT, "oem1-rescore")["cfg"]
    for key in ("campaigns", "bands", "carbon", "site", "engine",
                "assumed", "reduced"):
        assert rescore[key] == refresh[key], key
    assert set(refresh["guarantees"].items()) < set(
        rescore["guarantees"].items())
    assert rescore["name"] == cells["oem1-rescore"]
