"""The four-chip cell's path on four virtual CPU devices.

A sound run reads `correct` true with the chunks sharded over all four
devices, and a run in which one device's share of the results is left
out (the host gets back, for that device's lanes, the state it sent)
reads `correct` false.  Each run is a child process, because the number
of devices is fixed when JAX starts."""
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

CHILD = r"""
import json, sys
import numpy as np
import clients, run
from repro.core import engine_jax
from shrink import small_cell

STATE = slice(7, 12)          # the carried state among the kernel's inputs


def exchange_left_out():
    orig = engine_jax._sharded_plain

    def sharded(n_dev, B):
        fn = orig(n_dev, B)

        def last_device_left_out(*args):
            out = [np.array(o) for o in fn(*args)]
            blk = len(out[0]) // n_dev
            for o, sent in zip(out, args[STATE]):
                o[-blk:] = np.asarray(sent)[-blk:]
            return tuple(out)
        return last_device_left_out
    engine_jax._sharded_plain = sharded


if sys.argv[1] == "exchange_left_out":
    warm_up = clients.Client.warm_up

    def warm_up_then_break(self):
        warm_up(self)
        exchange_left_out()
    clients.Client.warm_up = warm_up_then_break
result, _ = run.run_cell(small_cell("oem1-refresh-4chip"), 5, 0.01, False,
                         require_chip=False)
import repro.carina as carina
print(json.dumps({"correct": result["correct"],
                  "devices_used": carina.scan_stats().devices_used}))
"""


CONTROL = r"""
import json
import control
import repro.carina as carina
from shrink import small_cell

c = small_cell("oem1-refresh-4chip")
reads = {}
for chips in (4, 1):
    c["cell"]["chips"] = chips
    reads[chips] = control.readings(c, [7], c["spec"]["cpu"]["requests"],
                                    "mixed", carina)[0]["checks"]
print(json.dumps(reads))
"""


def four_devices(script: str, *args: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([BENCH, os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", script, *args], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("exchange_left_out", False)])
def test_four_device_run(fault, correct):
    out = four_devices(CHILD, fault)
    assert out == {"correct": correct, "devices_used": 4}


def test_control_reads_alike_on_four_devices_and_one():
    """The lower-precision control's reading does not depend on how the
    lanes are sharded: lanes never interact in the plain kernel."""
    reads = four_devices(CONTROL)
    assert reads["4"] == reads["1"]
    assert reads["4"]["max_rel_gap"] > 0
