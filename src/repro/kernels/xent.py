"""Vocab-blocked fused softmax cross-entropy kernel.

The (T, V) logits matrix never exists: grid = (T/bt, V/bv) with the vocab
axis sequential; each cell computes a (bt, bv) logits tile on the MXU from
the resident (bt, d) hidden tile and the streamed (bv, d) embedding tile,
updating running (max, sumexp, label-logit) statistics in VMEM scratch.
Final NLL is emitted on the last vocab block.

This is the kernel twin of models/loss.py:blocked_cross_entropy (the
XLA-scan formulation used off-TPU); both are validated against
kernels/ref.py:blocked_xent_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30
LANES = 128


def _xent_kernel(x_ref, e_ref, lab_ref, nll_ref, m_ref, s_ref, ll_ref,
                 *, bv, v, bt):
    jv = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(jv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)
        ll_ref[...] = jnp.full_like(ll_ref, NEG_INF)

    x = x_ref[...].astype(F32)                              # (bt, d)
    e = e_ref[...].astype(F32)                              # (bv, d)
    logits = jax.lax.dot_general(x, e, (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)  # (bt, bv)
    base = jv * bv
    col = base + jax.lax.broadcasted_iota(jnp.int32, (bt, bv), 1)
    logits = jnp.where(col < v, logits, NEG_INF)

    m_prev = m_ref[:, :1]
    blk_max = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, blk_max)
    s_ref[...] = jnp.broadcast_to(
        s_ref[:, :1] * jnp.exp(m_prev - m_new)
        + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True), s_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    labels = lab_ref[:, :1]                                 # (bt, 1) int32
    in_blk = (labels >= base) & (labels < base + bv)
    hit = (col == labels)                                   # (bt, bv)
    cand = jnp.max(jnp.where(hit, logits, NEG_INF), axis=1, keepdims=True)
    ll_ref[...] = jnp.where(jnp.broadcast_to(in_blk, ll_ref.shape),
                            jnp.broadcast_to(cand, ll_ref.shape), ll_ref[...])

    @pl.when(jv == nv - 1)
    def _emit():
        nll = m_ref[:, :1] + jnp.log(s_ref[:, :1]) - ll_ref[:, :1]
        nll_ref[...] = jnp.broadcast_to(nll, nll_ref.shape).astype(F32)


@functools.partial(jax.jit, static_argnames=("block_t", "block_v", "interpret"))
def blocked_xent(x, emb, labels, *, block_t: int = 256, block_v: int = 2048,
                 interpret: bool = False):
    """x: (T, d); emb: (V, d); labels: (T,) int32. Returns nll (T,) fp32."""
    t, d = x.shape
    v = emb.shape[0]
    bt = min(block_t, t)
    bv = min(block_v, v)
    nt, nv = -(-t // bt), -(-v // bv)
    t_p, v_p = nt * bt, nv * bv
    if t_p != t:
        x = jnp.pad(x, ((0, t_p - t), (0, 0)))
        labels = jnp.pad(labels, (0, t_p - t))
    if v_p != v:
        emb = jnp.pad(emb, ((0, v_p - v), (0, 0)))
    labels2 = jnp.broadcast_to(labels[:, None], (t_p, LANES)).astype(jnp.int32)

    nll = pl.pallas_call(
        functools.partial(_xent_kernel, bv=bv, v=v, bt=bt),
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((bt, d), lambda it, jv: (it, 0)),
            pl.BlockSpec((bv, d), lambda it, jv: (jv, 0)),
            pl.BlockSpec((bt, LANES), lambda it, jv: (it, 0)),
        ],
        out_specs=pl.BlockSpec((bt, LANES), lambda it, jv: (it, 0)),
        out_shape=jax.ShapeDtypeStruct((t_p, LANES), F32),
        scratch_shapes=[
            pltpu.VMEM((bt, LANES), F32),
            pltpu.VMEM((bt, LANES), F32),
            pltpu.VMEM((bt, LANES), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, emb, labels2)
    return nll[:t, 0]
