"""Distributed runtime tests: sharding rules, checkpoint roundtrip incl.
cross-mesh elastic restore, fault-tolerant training loop, int8 ring
all-reduce, overlap helper, compressed-DP step.  Multi-device cases run in
subprocesses (device count is locked at first jax init).
"""
import json
import math
import os
import subprocess
import sys
import tempfile
import textwrap

import jax

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout


# ---------------------------------------------------------------------------
def test_resolve_pspec_divisibility_fallback():
    from repro.distributed.sharding import resolve_pspec
    code = """
    import jax
    from repro.distributed.sharding import resolve_pspec
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    # heads=6 not divisible by model=2? it is; kv=3 is not
    print(resolve_pspec((16, 6, 8), ("embed", "heads", None), mesh))
    print(resolve_pspec((16, 3, 8), ("embed", "kv_heads", None), mesh))
    print(resolve_pspec((100, 16), ("vocab", "embed"), mesh))
    """
    out = run_subprocess(code, devices=8)
    lines = out.strip().splitlines()
    assert "'model'" in lines[0]                    # heads sharded
    assert "'model'" not in lines[1]                # kv=3 replicated
    assert "'model'" in lines[2] and "'data'" in lines[2]


def test_checkpoint_roundtrip_identity():
    from repro.checkpoint.checkpoint import restore_checkpoint, save_checkpoint
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.bfloat16) * 1.5,
                  "d": jnp.zeros((), jnp.int32) + 7}}
    with tempfile.TemporaryDirectory() as td:
        save_checkpoint(td, 3, tree, {"step": 3})
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        got, meta = restore_checkpoint(td, like)
        assert meta["step"] == 3
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_checkpoint_roundtrip_property(seed):
    from repro.checkpoint.checkpoint import restore_checkpoint, save_checkpoint
    rng = np.random.default_rng(seed)
    tree = {"w": jnp.asarray(rng.normal(size=(4, 4)).astype(np.float32)),
            "s": jnp.asarray(rng.integers(0, 100), jnp.int32)}
    with tempfile.TemporaryDirectory() as td:
        save_checkpoint(td, 1, tree)
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        got, _ = restore_checkpoint(td, like)
        np.testing.assert_array_equal(np.asarray(tree["w"]), np.asarray(got["w"]))
        assert int(tree["s"]) == int(got["s"])


def test_checkpoint_keep_k_and_latest():
    from repro.checkpoint.checkpoint import latest_step, save_checkpoint
    tree = {"x": jnp.ones((2,))}
    with tempfile.TemporaryDirectory() as td:
        for s in (1, 2, 3, 4, 5):
            save_checkpoint(td, s, tree, keep=2)
        assert latest_step(td) == 5
        dirs = sorted(d for d in os.listdir(td) if d.startswith("step_"))
        assert len(dirs) == 2


def test_elastic_cross_mesh_restore():
    """Save on an 8-device mesh, restore on 4 devices (elastic shrink)."""
    code = """
    import jax, jax.numpy as jnp, numpy as np, tempfile, os
    from repro.configs import get_config
    from repro.models import build_model, layers as L
    from repro.optim.adamw import AdamWConfig
    from repro.data.pipeline import SyntheticLM
    from repro.training.loop import LoopConfig, run_training
    from repro.launch.mesh import make_mesh_for

    cfg = get_config('tinyllama-1.1b', smoke=True)
    model = build_model(cfg)
    opt = AdamWConfig(total_steps=6, warmup_steps=1)
    data = SyntheticLM(cfg, batch=8, seq=16)
    def mesh_fn(r):
        m = make_mesh_for(r)
        L.set_activation_sharding(m)
        return m
    td = tempfile.mkdtemp()
    r1 = run_training(model, opt, data, LoopConfig(total_steps=3,
                      steps_per_unit=3, ckpt_dir=td),
                      mesh_fn=mesh_fn, initial_replicas=8)
    r2 = run_training(model, opt, data, LoopConfig(total_steps=6,
                      steps_per_unit=3, ckpt_dir=td),
                      mesh_fn=mesh_fn, initial_replicas=4)
    assert r2.final_step == 6
    print('OK', r1.final_step, r2.final_step)
    """
    out = run_subprocess(code, devices=8)
    assert "OK 3 6" in out


def test_failure_injection_and_restart():
    code = """
    import tempfile
    from repro.configs import get_config
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig
    from repro.data.pipeline import SyntheticLM
    from repro.training.loop import LoopConfig, run_training
    from repro.distributed.fault_tolerance import FailureInjector, Supervisor

    cfg = get_config('tinyllama-1.1b', smoke=True)
    model = build_model(cfg)
    opt = AdamWConfig(total_steps=20, warmup_steps=2)
    data = SyntheticLM(cfg, batch=4, seq=16)
    td = tempfile.mkdtemp()
    res = run_training(model, opt, data,
                       LoopConfig(total_steps=20, steps_per_unit=4, ckpt_dir=td),
                       injector=FailureInjector(fail_at_steps=(6, 13)),
                       supervisor=Supervisor(elastic=False))
    assert res.final_step == 20 and res.restarts == 2
    print('OK', res.final_step, res.restarts)
    """
    out = run_subprocess(code, devices=1)
    assert "OK 20 2" in out


def test_restart_budget_exhaustion():
    from repro.distributed.fault_tolerance import Supervisor, WorkerFailure
    s = Supervisor(max_restarts=2, elastic=False)
    s.on_failure(1, 4, WorkerFailure("x"))
    s.on_failure(2, 4, WorkerFailure("x"))
    with pytest.raises(RuntimeError, match="budget"):
        s.on_failure(3, 4, WorkerFailure("x"))


def test_straggler_detector():
    from repro.distributed.fault_tolerance import StragglerDetector
    d = StragglerDetector(threshold=2.0, policy="exclude")
    for i in range(10):
        assert d.observe(i, 1.0) is None
    ev = d.observe(10, 5.0)
    assert ev is not None and d.should_exclude(ev)
    assert d.observe(11, 1.0) is None


# ---------------------------------------------------------------------------
def test_int8_ring_allreduce_and_compressed_step():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.collectives import int8_ring_allreduce, \
        allgather_matmul_overlapped

    mesh = jax.make_mesh((8,), ('data',))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

    def f(xs):
        return int8_ring_allreduce(xs[0], 'data')   # same value all shards

    # each shard contributes its row; compare vs exact sum
    y = jax.shard_map(lambda xs: int8_ring_allreduce(xs, 'data')[None],
                      mesh=mesh, in_specs=P('data', None),
                      out_specs=P('data', None), check_vma=False)(x)
    exact = np.asarray(x).sum(0)
    got = np.asarray(y)[0]
    rel = np.abs(got - exact).max() / (np.abs(exact).max() + 1e-9)
    assert rel < 0.05, rel           # int8 quantization tolerance
    for r in range(1, 8):            # every rank agrees
        np.testing.assert_allclose(np.asarray(y)[r], got, rtol=1e-6)

    # overlapped all-gather matmul == plain matmul
    k, f_ = 64, 32
    xx = jax.random.normal(jax.random.PRNGKey(1), (16, k))
    w = jax.random.normal(jax.random.PRNGKey(2), (k, f_)) * 0.1
    y2 = jax.shard_map(
        lambda w_s: allgather_matmul_overlapped(xx, w_s, 'data'),
        mesh=mesh, in_specs=P('data', None), out_specs=P(), check_vma=False)(w)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(xx @ w),
                               rtol=1e-4, atol=1e-4)
    print('OK')
    """
    out = run_subprocess(code, devices=8)
    assert "OK" in out


def test_dp_compressed_train_step_decreases_loss():
    code = """
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig
    from repro.training.step import make_dp_compressed_step, \
        init_dp_compressed_state
    from repro.data.pipeline import SyntheticLM

    mesh = jax.make_mesh((4,), ('data',))
    cfg = get_config('tinyllama-1.1b', smoke=True)
    model = build_model(cfg)
    opt = AdamWConfig(total_steps=30, warmup_steps=2, peak_lr=1e-3)
    state = init_dp_compressed_state(model, jax.random.PRNGKey(0), opt)
    step = jax.jit(make_dp_compressed_step(model, opt, mesh))
    data = SyntheticLM(cfg, batch=8, seq=16)
    losses = []
    with mesh:
        for i in range(15):
            batch = jax.tree.map(jnp.asarray, data.batch_at(0))  # same batch
            state, m = step(state, batch)
            losses.append(float(m['loss']))
    assert losses[-1] < losses[0], losses
    print('OK', round(losses[0], 3), round(losses[-1], 3))
    """
    out = run_subprocess(code, devices=4)
    assert "OK" in out


# ---------------------------------------------------------------------------
def test_data_pipeline_determinism_and_sharding():
    from repro.configs import get_config
    from repro.data.pipeline import Prefetcher, SyntheticLM, synth_tokens
    cfg = get_config("tinyllama-1.1b", smoke=True)
    d = SyntheticLM(cfg, batch=4, seq=16, seed=7)
    a = d.batch_at(3)["tokens"]
    b = d.batch_at(3)["tokens"]
    np.testing.assert_array_equal(a, b)
    # row-sharded regeneration equals the full batch's rows
    shard = synth_tokens(7, 3, 2, 16, cfg.vocab_size, start_row=2)
    np.testing.assert_array_equal(a[2:4], shard)
    # prefetcher yields the same stream
    pf = Prefetcher(d.iterate(0), depth=2)
    first = next(pf)["tokens"]
    np.testing.assert_array_equal(first, d.batch_at(0)["tokens"])
    pf.close()
