"""Fused RMSNorm kernel: one HBM pass (read x, write y) instead of XLA's
separate square/mean/rsqrt/mul chain.  Row-tiled: grid = (T/bt); each cell
loads a (bt, d) tile into VMEM, reduces, scales, writes back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps, d):
    x = x_ref[...].astype(F32)                          # (bt, d)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * (1.0 + s_ref[...].astype(F32))[None, :]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_t", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-6, block_t: int = 256,
            interpret: bool = False):
    """x: (T, d); scale: (d,). Returns (T, d) in x.dtype."""
    t, d = x.shape
    bt = min(block_t, t)
    nt = -(-t // bt)
    t_p = nt * bt
    if t_p != t:
        x = jnp.pad(x, ((0, t_p - t), (0, 0)))
    o = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps, d=d),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t_p, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, scale)
    return o[:t]
