"""Train / serve step factories.

`make_train_step(model, opt_cfg)` -> train_step(state, batch) with:
  * value_and_grad over model.loss (remat policy set in ModelConfig),
  * optional microbatch gradient accumulation (lax.scan over splits),
  * AdamW update (sharded states).
Under pjit, the same function serves 1-device CPU tests and the 512-chip
production mesh — sharding comes entirely from in_shardings.

`make_dp_compressed_step(...)` is the explicit shard_map DP variant with
int8+error-feedback gradient all-reduce (replicated params; <~2B models) —
see distributed/collectives.py.

`make_prefill_step` / `make_decode_step` are the serving lowerings used by
the dry-run's inference cells and the serving engine.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.model import Model
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state
from repro.distributed import collectives as C

F32 = jnp.float32


def init_train_state(model: Model, key, opt_cfg: AdamWConfig) -> Dict[str, Any]:
    params = model.init(key)
    return {"params": params, "opt": init_opt_state(params, opt_cfg)}


def abstract_train_state(model: Model, opt_cfg: AdamWConfig) -> Dict[str, Any]:
    from repro.optim.adamw import abstract_opt_state
    aparams = model.abstract_params()
    return {"params": aparams, "opt": abstract_opt_state(aparams, opt_cfg)}


def _split_microbatches(batch: Dict[str, jax.Array], n: int):
    def split(x):
        b = x.shape[0]
        assert b % n == 0, (b, n)
        return x.reshape(n, b // n, *x.shape[1:])
    return jax.tree.map(split, batch)


def make_train_step(model: Model, opt_cfg: AdamWConfig, *, grad_accum: int = 1):
    def loss_fn(params, mb):
        loss, metrics = model.loss(params, mb)
        return loss, metrics

    def train_step(state, batch):
        params = state["params"]
        if grad_accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            from repro.models import layers as _L
            mbs = _split_microbatches(batch, grad_accum)

            def acc_body(carry, mb):
                g_acc, l_acc = carry
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                g_acc = jax.tree.map(lambda a, b: a + b.astype(F32), g_acc, g)
                return (g_acc, l_acc + l), m

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
            if _L.exact_costing():   # unroll: scan bodies undercount in HLO cost
                carry, ms_list = (g0, jnp.zeros((), F32)), []
                for i in range(grad_accum):
                    mb = jax.tree.map(lambda t: t[i], mbs)
                    carry, m = acc_body(carry, mb)
                    ms_list.append(m)
                grads, loss_sum = carry
                ms = jax.tree.map(lambda *ts: jnp.stack(ts), *ms_list)
            else:
                (grads, loss_sum), ms = jax.lax.scan(
                    acc_body, (g0, jnp.zeros((), F32)), mbs)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            loss = loss_sum / grad_accum
            metrics = jax.tree.map(lambda x: x.mean(), ms)

        new_params, new_opt, opt_metrics = adamw_update(params, grads, state["opt"], opt_cfg)
        metrics = dict(metrics, **opt_metrics, loss=loss)
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


# ---------------------------------------------------------------------------
def make_dp_compressed_step(model: Model, opt_cfg: AdamWConfig, mesh: Mesh,
                            dp_axis: str = "data"):
    """Explicit shard_map DP with int8+EF compressed gradient all-reduce.
    Params/opt replicated; batch sharded on dp_axis; state carries
    `residuals` (error-feedback buffers)."""

    def local_step(state, batch):
        params = state["params"]

        def loss_fn(p):
            return model.loss(p, batch)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads, new_res = C.compressed_psum_grads(grads, state["residuals"], dp_axis)
        loss = jax.lax.pmean(loss, dp_axis)
        metrics = jax.tree.map(lambda x: jax.lax.pmean(x, dp_axis), metrics)
        new_params, new_opt, opt_metrics = adamw_update(params, grads, state["opt"], opt_cfg)
        metrics = dict(metrics, **opt_metrics, loss=loss)
        return {"params": new_params, "opt": new_opt, "residuals": new_res}, metrics

    rep = P()

    def step_fn(state, batch):
        in_specs = (jax.tree.map(lambda _: rep, state),
                    jax.tree.map(lambda _: P(dp_axis), batch))
        out_state_spec = jax.tree.map(lambda _: rep, state)
        fn = jax.shard_map(
            local_step, mesh=mesh, in_specs=in_specs,
            out_specs=(out_state_spec,
                       {"nll": rep, "acc": rep, "aux": rep, "lr": rep,
                        "grad_norm": rep, "loss": rep}),
            check_vma=False)
        return fn(state, batch)

    return step_fn


def init_dp_compressed_state(model: Model, key, opt_cfg: AdamWConfig):
    params = model.init(key)
    return {"params": params, "opt": init_opt_state(params, opt_cfg),
            "residuals": C.init_residuals(params)}


# ---------------------------------------------------------------------------
def make_prefill_step(model: Model):
    def prefill_step(params, batch):
        return model.prefill(params, batch)
    return prefill_step


def make_decode_step(model: Model):
    def decode_step(params, cache, tokens, index):
        return model.decode_step(params, cache, tokens, index)
    return decode_step
