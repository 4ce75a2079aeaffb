"""Fused blockwise-softmax (flash) attention forward kernel for TPU.

TPU-native design (DESIGN.md §6):
  * grid = (B, H, Sq/bq, Sk/bk); the K axis is the minor (sequential) grid
    dim — online-softmax statistics (m, l) and the output accumulator live
    in VMEM scratch and carry across K iterations ("arbitrary" semantics).
  * q/k/v tiles are MXU-aligned (block sizes multiples of 128 where the
    shape allows); softmax statistics are stored (bq, 128) lane-replicated
    (Mosaic-friendly 2D layout).
  * GQA is handled in the K/V index_map (kv head = q head // group) — no
    jnp.repeat materialization.
  * causal masking skips fully-masked K blocks via pl.when.

Forward-only kernel + residuals (o, lse); the backward pass is a chunked
pure-XLA implementation wired through jax.custom_vjp in ops.py (recompute
per K block, flash-style memory).
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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, bq, bk, sk):
    ik = pl.program_id(3)
    iq = pl.program_id(2)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * bq
    k_start = ik * bk
    # causal: skip blocks entirely above the diagonal
    run = True
    if causal:
        run = k_start <= q_start + bq - 1

    @pl.when(run if causal else True)
    def _compute():
        q = q_ref[0, 0].astype(F32)                       # (bq, d)
        k = k_ref[0, 0].astype(F32)                       # (bk, d)
        v = v_ref[0, 0].astype(F32)                       # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < sk
        if causal:
            mask &= kpos <= qpos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                             # (bq, 1)
        l_prev = l_ref[:, :1]
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)                            # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(l_safe)
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:]).astype(F32)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q", "block_k",
                                             "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D) -> (o (B,H,Sq,D), lse (B,H,Sq,LANES))."""
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    nq = -(-sq // bq)
    nk = -(-sk // bk)
    # pad sequence dims to block multiples
    sq_p, sk_p = nq * bq, nk * bk
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))

    grid = (b, h, nq, nk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, sk=sk)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, qi, ki, _g=g: (bi, hi // _g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, qi, ki, _g=g: (bi, hi // _g, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq_p, LANES), F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), F32),
            pltpu.VMEM((bq, LANES), F32),
            pltpu.VMEM((bq, LANES), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return o[:, :, :sq], lse[:, :, :sq, 0]
