"""Pairwise prediction-disagreement matrix as a Pallas kernel.

Tiling: grid (N/BN, N/BN, M/BM) with the data axis sequential; each step
loads two (BN, BM) prediction tiles and accumulates the (BN, BN) pairwise
mismatch counts in VMEM scratch — an int-compare analogue of a blocked
GEMM (same data reuse: each tile pair is read once per output block).
VMEM per step: 2·BN·BM·4 + BN²·4 bytes (BN=128, BM=512 -> ~0.6 MB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _disagree_kernel(pi_ref, pj_ref, vm_ref, out_ref, acc_ref):
    mi = pl.program_id(2)
    nm = pl.num_programs(2)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pi = pi_ref[...]                                  # (BN, BM) int32
    pj = pj_ref[...]
    v = vm_ref[...].astype(jnp.float32)               # (1, BM)
    neq = (pi[:, None, :] != pj[None, :, :]).astype(jnp.float32)
    acc_ref[...] += jnp.sum(neq * v[0][None, None, :], axis=-1)

    @pl.when(mi == nm - 1)
    def _final():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block_n", "block_m",
                                             "interpret"))
def disagreement_counts(preds, valid, *, block_n: int = 128,
                        block_m: int = 512, interpret: bool = False):
    """preds: (N, M) int32, valid: (M,) float32 -> raw counts (N, N)."""
    n, m = preds.shape
    bn = min(block_n, n)
    bm = min(block_m, m)
    pad_n = (-n) % bn
    pad_m = (-m) % bm
    p = jnp.pad(preds, ((0, pad_n), (0, pad_m)))
    v = jnp.pad(valid.astype(jnp.float32), (0, pad_m))[None, :]
    np_, mp_ = p.shape
    grid = (np_ // bn, np_ // bn, mp_ // bm)
    out = pl.pallas_call(
        _disagree_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bm), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bm), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, bm), lambda i, j, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(p, p, v)
    return out[:n, :n]
