"""Flash attention as a Pallas TPU kernel (causal / sliding-window).

Tiling: grid (BH, num_q_blocks, num_k_blocks); the k axis is the innermost,
sequential ("arbitrary") dimension so the (m, l, acc) running softmax state
lives in VMEM scratch and persists across k steps of one (bh, q-block).
Block shapes are (1, BQ, D) for q/o and (1, BK, D) for k/v — with
BQ = BK = 128 and D <= 256 the working set is ~(2·128·256 + 128·256 +
running state) · 4 B ≈ 0.6 MB, comfortably inside a v5e core's 128 MB VMEM
while keeping the 128-wide MXU dims fully utilized.

Numerics: fp32 running max/sum/accumulator regardless of input dtype —
matches the ref.py oracle bit-for-bit at fp32 inputs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0e9
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  offset: int, valid_k: int, block_q: int, block_k: int,
                  causal: bool, window: Optional[int], scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale                  # (BQ, D)
    k = k_ref[0].astype(jnp.float32)                          # (BK, D)
    v = v_ref[0].astype(jnp.float32)                          # (BK, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (BQ, BK)

    # absolute positions; queries offset so the last REAL query aligns with
    # the last REAL key (offset = real_sk - real_sq); padded keys
    # (k_pos >= valid_k) are always masked.
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + offset
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < valid_k
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    if window is not None:
        mask = jnp.logical_and(mask, k_pos > q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                       # (BQ,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_cur[:, None])
    # keep fully-masked rows finite
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_cur

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-20)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret",
                                             "offset", "valid_k"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True,
                         window: Optional[int] = None,
                         block_q: int = DEFAULT_BLOCK_Q,
                         block_k: int = DEFAULT_BLOCK_K,
                         offset: Optional[int] = None,
                         valid_k: Optional[int] = None,
                         interpret: bool = False):
    """q: (BH, Sq, D); k, v: (BH, Sk, D).  Sq % block_q == 0 and
    Sk % block_k == 0 (ops.py pads; ``offset``/``valid_k`` carry the real
    query offset and real key count through the padding)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)
    if offset is None:
        offset = sk - sq
    if valid_k is None:
        valid_k = sk
    grid = (bh, sq // block_q, sk // block_k)
    kernel = functools.partial(
        _flash_kernel, offset=offset, valid_k=valid_k,
        block_q=block_q, block_k=block_k,
        causal=causal, window=window, scale=1.0 / (d ** 0.5))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # running max m
            pltpu.VMEM((block_q,), jnp.float32),      # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
