"""Chunked diagonal linear-recurrence kernel: h_t = a_t * h_{t-1} + b_t.

Serves both Mamba-1 selective scans (C = d_inner * d_state, flattened) and
Griffin RG-LRU (C = lru_width).

TPU-native design: grid = (B, C/bc, T/chunk).  The time axis is the minor
(sequential) grid dim; the carried state h (bc,) lives in VMEM scratch and
persists across time-chunk iterations.  Channels are "parallel" — each
channel block scans its own recurrence, so the kernel parallelizes over
B x C/bc cells while time advances sequentially within each — the same
tiling as models/ssm.py's chunked_diag_scan, but with the chunk loop in
VMEM instead of XLA scan-carried HBM round-trips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _scan_kernel(a_ref, b_ref, hs_ref, hf_ref, h_ref, *, chunk):
    it = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(F32)                 # (chunk, bc)
    b = b_ref[0].astype(F32)

    def step(t, h):
        h = a[t] * h + b[t]
        hs_ref[0, t] = h.astype(hs_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[0])
    h_ref[0] = h

    @pl.when(it == nt - 1)
    def _emit():
        hf_ref[0] = h.astype(hf_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "block_c", "interpret"))
def ssm_scan(a, b, *, chunk: int = 128, block_c: int = 512, interpret: bool = False):
    """a, b: (B, T, C). Returns (hs (B,T,C) fp32, h_final (B,C) fp32)."""
    B, T, C = a.shape
    bc = min(block_c, C)
    nc = -(-C // bc)
    ch = min(chunk, T)
    nt = -(-T // ch)
    c_p, t_p = nc * bc, nt * ch
    if c_p != C or t_p != T:
        a = jnp.pad(a, ((0, 0), (0, t_p - T), (0, c_p - C)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, t_p - T), (0, c_p - C)))

    grid = (B, nc, nt)
    hs, hf = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=ch),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ch, bc), lambda bi, ci, ti: (bi, ti, ci)),
            pl.BlockSpec((1, ch, bc), lambda bi, ci, ti: (bi, ti, ci)),
        ],
        out_specs=[
            pl.BlockSpec((1, ch, bc), lambda bi, ci, ti: (bi, ti, ci)),
            pl.BlockSpec((1, bc), lambda bi, ci, ti: (bi, ci)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, t_p, c_p), F32),
            jax.ShapeDtypeStruct((B, c_p), F32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bc), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return hs[:, :T, :C], hf[:, :C]
