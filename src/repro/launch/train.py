"""Production training entry point.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --steps 100 --policy peak_aware_boosted_offhours [--smoke]

On a real TPU fleet this binary runs per host (jax.distributed.initialize);
here it sizes itself to the local device count.  Selects the Pallas kernel
path automatically on TPU backends.  The chip's energy profile comes
from its device kind (core/sysinfo.py), so it needs a known TPU; for a
CPU run of the controller-gated loop use examples/quickstart.py.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

import repro.carina as carina
from repro.configs import ARCH_NAMES, get_config
from repro.core import POLICIES, SimClock
from repro.data.pipeline import SyntheticLM
from repro.distributed.fault_tolerance import Supervisor
from repro.launch.mesh import make_mesh_for
from repro.models import build_model
from repro.models import layers as L
from repro.optim.adamw import AdamWConfig
from repro.training.loop import LoopConfig, run_training


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--policy", default="baseline", choices=list(POLICIES))
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--blocked-xent", action="store_true")
    args = ap.parse_args()
    # Algorithm 1 line 3: detect machine characteristics.  The chip
    # profile is looked up by device kind, so a host without a known
    # accelerator stops here instead of borrowing another chip's numbers.
    from repro.core.sysinfo import chip_profile_from_host, detect_host
    host = detect_host()
    chip = chip_profile_from_host(host)

    L.set_kernel_mode("auto")      # pallas on TPU, XLA elsewhere
    cfg = get_config(args.arch, smoke=args.smoke)
    cfg = dataclasses.replace(cfg, remat=args.remat,
                              blocked_xent=args.blocked_xent)
    model = build_model(cfg)
    n_dev = len(jax.devices())
    print(f"devices={n_dev} arch={cfg.name} params={model.param_count():,}")

    def mesh_fn(replicas):
        m = make_mesh_for(replicas)
        L.set_activation_sharding(m)
        return m

    opt = AdamWConfig(total_steps=args.steps,
                      warmup_steps=max(1, args.steps // 10))
    data = SyntheticLM(cfg, batch=args.batch, seq=args.seq)
    campaign = carina.Campaign(
        carina.TrainingCampaign(f"train-{cfg.name}", cfg.name,
                                total_steps=args.steps, steps_per_unit=10),
        POLICIES[args.policy],
        name=f"train-{cfg.name}", out_dir="experiments/train_run")
    controller = campaign.controller(
        max_replicas=n_dev,
        clock=SimClock(start_hour=9.0, speedup=600.0),
        chip=chip)
    campaign.tracker.meta["host"] = host
    res = run_training(model, opt, data,
                       LoopConfig(total_steps=args.steps, steps_per_unit=10,
                                  ckpt_dir=args.ckpt_dir, log_every=10),
                       controller=controller, supervisor=Supervisor(),
                       mesh_fn=mesh_fn if n_dev > 1 else None,
                       initial_replicas=n_dev)
    print(f"done at step {res.final_step}; restarts={res.restarts}")
    summary = campaign.finish(render=False)
    print(carina.render_run_dashboard(summary, "experiments/train_run"))


if __name__ == "__main__":
    main()
