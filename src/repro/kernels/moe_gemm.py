"""Grouped (per-expert) GEMM kernel — MegaBlocks-style block-diagonal matmul.

Contract: tokens are pre-sorted by expert and padded so every bm-row block
belongs to exactly one expert; `block_ids` (n_row_blocks,) gives that
expert.  block_ids is a scalar-prefetch operand (pltpu.PrefetchScalarGridSpec)
so the expert-weight BlockSpec index_map can select w[block_ids[im]] while
the block is being DMA'd — data-dependent weight streaming with no gather
materialization of (T, d, f).

Grid: (nm, nn, nkd); the d (contraction) axis is the sequential minor dim,
accumulating into the output tile (revisited across kd).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _gg_kernel(ids_ref, x_ref, w_ref, o_ref, acc_ref):
    kd = pl.program_id(2)
    nkd = pl.num_programs(2)

    @pl.when(kd == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(F32), w_ref[0].astype(F32),
        (((1,), (0,)), ((), ())), preferred_element_type=F32)

    @pl.when(kd == nkd - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def grouped_gemm(x, w, block_ids, *, block_m: int = 128, block_n: int = 128,
                 block_k: int = 512, interpret: bool = False):
    """x: (T, d) block-sorted rows; w: (E, d, f); block_ids: (T//block_m,) int32.
    Returns (T, f)."""
    t, d = x.shape
    e, _, f = w.shape
    assert t % block_m == 0, (t, block_m)
    bn = min(block_n, f)
    bk = min(block_k, d)
    nm = t // block_m
    nn = -(-f // bn)
    nkd = -(-d // bk)
    f_p, d_p = nn * bn, nkd * bk
    if f_p != f or d_p != d:
        w = jnp.pad(w, ((0, 0), (0, d_p - d), (0, f_p - f)))
        x = jnp.pad(x, ((0, 0), (0, d_p - d)))

    grid = (nm, nn, nkd)
    o = pl.pallas_call(
        _gg_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, bk), lambda im, jn, kd, ids: (im, kd)),
                pl.BlockSpec((1, bk, bn), lambda im, jn, kd, ids: (ids[im], kd, jn)),
            ],
            out_specs=pl.BlockSpec((block_m, bn), lambda im, jn, kd, ids: (im, jn)),
            scratch_shapes=[pltpu.VMEM((block_m, bn), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t, f_p), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_ids, x, w)
    return o[:, :f]
