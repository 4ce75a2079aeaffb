"""Flash-decoding attention kernel for serve_step (q_len = 1).

Design: the 32k-long KV cache is the bandwidth-bound operand; we split it
into `nsplit` slices processed by parallel grid cells.  Each cell streams
its slice through VMEM in bk-sized blocks (sequential minor grid dim),
maintaining online-softmax partials in VMEM scratch, and emits
(o_partial * l, m, l) per split.  The final rescale-combine over splits is
O(nsplit*d) and runs as a tiny XLA epilogue in the wrapper.

Grid: (B, Hkv, nsplit, nk_per_split).  All q heads of one KV head (the GQA
group, rows of q) are processed together: q tile is (g, d) so the score
matmul (g, d) x (d, bk) feeds the MXU with the group as the M dim.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30
LANES = 128


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, ms_ref, ls_ref, *, scale, bk, per_split):
    isplit = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        ms_ref[...] = jnp.full_like(ms_ref, NEG_INF)
        ls_ref[...] = jnp.zeros_like(ls_ref)

    length = len_ref[0]
    k_start = isplit * per_split + ik * bk

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0, 0].astype(F32)                      # (g, d)
        k = k_ref[0, 0].astype(F32)                      # (bk, d)
        v = v_ref[0, 0].astype(F32)                      # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale  # (g, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = ms_ref[:, :1]
        l_prev = ls_ref[:, :1]
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        ls_ref[...] = jnp.broadcast_to(l_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
                                       ls_ref.shape)
        ms_ref[...] = jnp.broadcast_to(m_new, ms_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)

    @pl.when(ik == nk - 1)
    def _emit():
        o_ref[0, 0, 0] = (acc_ref[...]).astype(o_ref.dtype)  # un-normalized (o*l)
        m_ref[0, 0, 0] = ms_ref[...].astype(F32)
        l_ref[0, 0, 0] = ls_ref[...].astype(F32)


@functools.partial(jax.jit, static_argnames=("nsplit", "block_k", "interpret", "scale"))
def decode_attention(q, k, v, length, *, nsplit: int = 8, block_k: int = 256,
                     scale: Optional[float] = None, interpret: bool = False):
    """q: (B, H, D); k, v: (B, Sk, Hkv, D); length: scalar int32 valid prefix.
    Returns (B, H, D)."""
    b, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # layout: (B, Hkv, Sk, D) for contiguous streaming
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    qg = q.reshape(b, hkv, g, d)

    nsplit = max(1, min(nsplit, sk // block_k or 1))
    per_split = -(-sk // nsplit)
    bk = min(block_k, per_split)
    nk = -(-per_split // bk)
    per_split = nk * bk
    sk_p = per_split * nsplit
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))

    length = jnp.asarray(length, jnp.int32).reshape(1)
    grid = (b, hkv, nsplit, nk)
    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk,
                               per_split=per_split)
    o_p, m_p, l_p = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, si, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, si, ki, _nk=nk: (bi, hi, si * _nk + ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, si, ki, _nk=nk: (bi, hi, si * _nk + ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, g, d), lambda bi, hi, si, ki: (bi, hi, si, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, LANES), lambda bi, hi, si, ki: (bi, hi, si, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, LANES), lambda bi, hi, si, ki: (bi, hi, si, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, nsplit, g, d), F32),
            jax.ShapeDtypeStruct((b, hkv, nsplit, g, LANES), F32),
            jax.ShapeDtypeStruct((b, hkv, nsplit, g, LANES), F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, d), F32),
            pltpu.VMEM((g, LANES), F32),
            pltpu.VMEM((g, LANES), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(length, qg, k, v)

    # combine splits (tiny XLA epilogue)
    m = m_p[..., 0]                                         # (B,Hkv,ns,g)
    l = l_p[..., 0]
    m_max = jnp.max(m, axis=2, keepdims=True)
    w = jnp.exp(m - m_max) * jnp.where(l > 0, 1.0, 0.0)
    l_tot = jnp.sum(l * jnp.exp(m - m_max), axis=2)         # (B,Hkv,g)
    o = jnp.sum(o_p * (jnp.exp(m - m_max) )[..., None], axis=2)
    o = o / jnp.maximum(l_tot, 1e-30)[..., None]
    del w
    return o.reshape(b, h, d).astype(q.dtype)
