"""Serve a small model with batched requests (continuous batching) under
CARINA per-request energy/carbon accounting — wired through the
`ServingSession` live mode: the session's carbon gate throttles
admissions and every engine tick is accounted (energy, CO2, band).

    PYTHONPATH=src python examples/serving.py --arch tinyllama-1.1b

Set CARINA_EXAMPLE_FAST=1 for the CI smoke mode (fewer requests).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import get_config
from repro.core import (RunTracker, ServingSession, SimClock, StepCost,
                        render_run_dashboard, scan_stats)
from repro.models import build_model
from repro.serving.engine import ServingEngine

FAST = bool(int(os.environ.get("CARINA_EXAMPLE_FAST", "0")))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--requests", type=int, default=4 if FAST else 8)
    ap.add_argument("--max-new", type=int, default=4 if FAST else 8)
    ap.add_argument("--slots", type=int, default=4)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    print(f"serving {cfg.name} ({model.param_count():,} params), "
          f"{args.slots} slots")

    tracker = RunTracker(f"serve-{cfg.name}")
    session = ServingSession(
        tracker=tracker, clock=SimClock(start_hour=10.0),
        step_cost=StepCost(flops=2e9 * model.param_count() / 1e9,
                           hbm_bytes=2 * model.param_count(), ici_bytes=0.0))

    engine = ServingEngine(model, params, slots=args.slots, s_max=128,
                           session=session)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, size=rng.integers(4, 12))
        rid = engine.submit(prompt.astype(np.int32), max_new=args.max_new)
        print(f"  submitted request {rid} (prompt len {len(prompt)})")

    done = engine.run_until_drained()
    for r in done:
        dt = (r.t_finish - r.t_submit) * 1e3
        print(f"  request {r.rid}: {len(r.generated)} tokens in {dt:.0f} ms "
              f"-> {r.generated[:6]}...")
    print(f"  session: {session.live_units} ticks, "
          f"{session.live_energy_kwh:.3e} kWh, "
          f"{session.live_co2_kg:.3e} kg CO2e")
    st = scan_stats()
    print(f"  engine: devices_used={st.devices_used} "
          f"precision={st.precision_mode or 'fp64'} "
          f"requests_seen={st.requests_seen} "
          "(live ticks are accounted directly; window-mode sweeps run "
          "through execute_plan and report its scale-out counters here)")

    md = render_run_dashboard(tracker.close(), "experiments/serving")
    print()
    print(md)


if __name__ == "__main__":
    main()
