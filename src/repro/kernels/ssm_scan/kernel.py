"""Chunked gated-linear-attention (GLA / WKV / mamba2-SSD) scan as a Pallas
TPU kernel.

Recurrence (state S: (Dk, Dv) per (batch, head)):
    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    mamba : y_t = q_t . S_t
    rwkv  : y_t = q_t . S_{t-1} + (q_t . (u*k_t)) v_t

Tiling: grid (B*H, num_chunks); the chunk axis is sequential ("arbitrary")
and the carried state lives in a (Dk, Dv) fp32 VMEM scratch.  Each grid step
loads one (C, Dk)/(C, Dv) chunk of q/k/v/log_w, does three MXU matmuls
(intra-chunk (C x C) attention, state readout, state update) and advances
the state — the TPU-native port of GPU chunked-scan kernels (FLA / SSD):
what a GPU does with warp-level scans becomes chunk-level matmuls sized to
the 128-wide MXU, with the sequential dependency carried in VMEM instead of
shared memory.  VMEM working set per step: C·(2Dk+Dv)·4B + Dk·Dv·4B
(C=128, Dk=Dv=128 -> ~0.26 MB).

The algorithm (including the exp-of-cumulative-log numerics) is shared
line-for-line with the nn.linear_attn oracle, so fp32 results agree to
roundoff.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gla_kernel(q_ref, k_ref, v_ref, lw_ref, bonus_ref, s0_ref,
                y_ref, sfin_ref, state_ref, *,
                chunk: int, variant: str):
    ni = pl.program_id(1)
    nn = pl.num_programs(1)

    @pl.when(ni == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32)            # (C, Dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)            # (C, Dv)
    lw = lw_ref[0, 0].astype(jnp.float32)          # (C, Dk), <= 0

    lc = jnp.cumsum(lw, axis=0)                    # inclusive cum log decay
    lc_total = lc[-1]                              # (Dk,)
    q_lc = lc if variant == "mamba" else lc - lw
    q_s = q * jnp.exp(q_lc)
    k_s = k * jnp.exp(-lc)
    k_adv = k * jnp.exp(lc_total[None, :] - lc)

    att = jax.lax.dot_general(q_s, k_s, (((1,), (1,)), ((), ())))  # (C, C)
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = ti >= tj if variant == "mamba" else ti > tj
    att = jnp.where(mask, att, 0.0)
    y = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())))      # (C, Dv)
    if variant == "rwkv":
        u = bonus_ref[0].astype(jnp.float32)       # (Dk,)
        diag = jnp.sum(q * u[None, :] * k, axis=1)                 # (C,)
        y = y + diag[:, None] * v

    s = state_ref[...]                             # (Dk, Dv)
    y = y + jax.lax.dot_general(q_s, s, (((1,), (0,)), ((), ())))
    state_ref[...] = s * jnp.exp(lc_total)[:, None] + jax.lax.dot_general(
        k_adv, v, (((0,), (0,)), ((), ())))
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ni == nn - 1)
    def _final():
        sfin_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "variant", "num_heads",
                                             "interpret"))
def gla_chunked_bhncd(q, k, v, lw, bonus, s0, *, chunk: int, variant: str,
                      num_heads: int, interpret: bool = False):
    """q,k,lw: (BH, N, C, Dk); v: (BH, N, C, Dv); bonus: (H, Dk);
    s0: (BH, Dk, Dv).  Returns (y (BH, N, C, Dv), s_final (BH, Dk, Dv))."""
    bh, n, c, dk = q.shape
    dv = v.shape[-1]
    assert c == chunk
    h = num_heads
    grid = (bh, n)
    kernel = functools.partial(_gla_kernel, chunk=chunk, variant=variant)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, c, dk), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, 1, c, dk), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, 1, c, dv), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, 1, c, dk), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, dk), lambda b, i: (b % h, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, dv), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n, c, dv), v.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, lw, bonus, s0)
