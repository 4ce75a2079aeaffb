"""GPipe-style pipeline parallelism over a "pipe" mesh axis.

For meshes that dedicate an axis to pipeline stages (an alternative to the
production 2-axis mesh — e.g. (pipe=4, data=8, model=8) on odd-shaped
fleets), layers are split into `P` contiguous stages; `M` microbatches flow
through a ppermute ring with the classic GPipe schedule (M + P - 1 ticks,
bubble fraction (P-1)/(M+P-1)).

Implementation: jax.shard_map over the "pipe" axis; each device holds its
stage's layer parameters (stacked dim 0 sharded over "pipe") and runs
`stage_fn` every tick; activations hop stages via collective-permute.
Forward-only ticks are jit-traceable (static loop, M and P are config);
the whole pipeline is differentiable (ppermute has a transpose rule), so
training works through it.

    y = pipeline_apply(mesh, stage_fn, stage_params, x, n_micro=M)

Contract: x: (B, ...) with B % M == 0; stage_params leaves stacked (P, ...);
stage_fn(stage_param_slice, micro_x) -> micro_y with y.shape == x.shape
(uniform width across stages, as in a decoder LM).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(mesh: Mesh, stage_fn: Callable, stage_params: Any,
                   x: jax.Array, n_micro: int, axis: str = "pipe") -> jax.Array:
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    mb = b // n_micro
    micro = x.reshape((n_micro, mb) + x.shape[1:])

    def per_stage(params, micro_in):
        # params: this stage's slice (leaves had leading dim P, now sliced)
        params = jax.tree.map(lambda t: t[0], params)
        idx = jax.lax.axis_index(axis)
        ticks = n_micro + n_stages - 1
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        carry = jnp.zeros((mb,) + x.shape[1:], x.dtype)
        out = jnp.zeros_like(micro_in[0])
        outs = jnp.zeros((n_micro, mb) + x.shape[1:], x.dtype)
        for t in range(ticks):
            # stage 0 ingests microbatch t (if any); others take the hop
            feed = micro_in[min(t, n_micro - 1)]
            inp = jnp.where(idx == 0,
                            feed if t < n_micro else jnp.zeros_like(feed),
                            carry)
            out = stage_fn(params, inp)
            # last stage emits microbatch (t - (P-1)) at tick t
            emit_i = t - (n_stages - 1)
            if emit_i >= 0:
                outs = jax.lax.cond(
                    idx == n_stages - 1,
                    lambda o: o.at[emit_i].set(out),
                    lambda o: o, outs)
            carry = jax.lax.ppermute(out, axis, fwd)
        # only the last stage's buffer is meaningful; broadcast it to every
        # stage via a masked psum so the caller sees a replicated result
        outs = jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    # Auto axes: the replicated result then carries no mesh type, so
    # callers may differentiate it outside a mesh context
    auto = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    in_specs = (jax.tree.map(lambda _: P(axis), stage_params), P())
    y = jax.shard_map(per_stage, mesh=auto, in_specs=in_specs,
                  out_specs=P(), check_vma=False)(stage_params, micro)
    return y.reshape((b,) + x.shape[1:])
