"""Weighted source->target parameter mixing as a Pallas kernel.

out (T, P) = alpha^T (T, S) @ theta (S, P) over the flattened parameter
vector — ST-LF's model-transfer hot loop when the client count and model
size are large (HBM-bound: every source's parameters are streamed once
regardless of how many targets consume them, instead of once per target as
in the naive per-target gather).

Tiling: grid (T / BT, P / BP, S / BS).  Each step loads a (BS, BT) block
of alpha and a (BS, BP) slab of the stacked parameters and accumulates
into the (BT, BP) output block, which stays resident across the S
(contraction, "arbitrary") axis.  ``_tiles`` picks the blocks from S, T
and P so the working set — double-buffered alpha, theta and output
blocks plus the dot's (BT, BP) result — stays under ``VMEM_BUDGET``, below
the 16 MiB of scoped VMEM a v5e kernel gets by default.  It keeps T whole
where it can (theta then crosses HBM once), then S whole, then widens BP
up to ``block_p``.  Small pools take one block over all of S and T:
S=T=64, BP=2048 is (2·64·64 + 2·64·2048 + 3·64·2048)·4 B ~ 2.6 MB.  At
S=T=1024 alpha alone is 4 MiB, so S is tiled (BS=512, BP=512, 12 MiB);
at N=4096 the whole alpha (64 MiB) could never be one block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bytes of VMEM the kernel's blocks may take (of the 16 MiB v5e default)
VMEM_BUDGET = 12 * 2**20


def _combine_kernel(alpha_ref, theta_ref, out_ref):
    a = alpha_ref[...].astype(jnp.float32)           # (BS, BT)
    th = theta_ref[...].astype(jnp.float32)          # (BS, BP)
    part = jax.lax.dot_general(a, th, (((0,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST)

    @pl.when(pl.program_id(2) == 0)
    def _():
        out_ref[...] = part

    @pl.when(pl.program_id(2) > 0)
    def _():
        out_ref[...] += part


def _widths(n: int):
    """Candidate block widths along an axis of length n: the whole axis,
    then the power-of-two multiples of 128 below it."""
    out, w = [n], 128
    while w < n:
        out.append(w)
        w *= 2
    return out


def _tiles(s: int, t: int, p: int, block_p: int):
    """(BS, BT, BP) under ``VMEM_BUDGET``; see the module docstring."""
    bp_max = p if p <= 128 else min(block_p, -(-p // 128) * 128)

    def working_set(bs, bt, bp):
        return 4 * (2 * bs * bt + 2 * bs * bp + 3 * bt * bp)

    fits = [(bs, bt, bp) for bt in _widths(t) for bs in _widths(s)
            for bp in _widths(bp_max)
            if working_set(bs, bt, bp) <= VMEM_BUDGET]
    if not fits:
        raise ValueError(f"alpha_combine: no block of (S={s}, T={t}, "
                         f"P={p}) fits {VMEM_BUDGET} B of VMEM")
    # whole T first, then lane-dense slabs (>= 512), whole S, widest slab
    return max(fits, key=lambda c: (c[1], min(c[2], 512), c[0], c[2]))


def _pad_to(x, axis: int, block: int):
    pad = (-x.shape[axis]) % block
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def alpha_combine_flat(theta, alpha, *, block_p: int = 2048,
                       interpret: bool = False):
    """theta: (S, P); alpha: (S, T) -> (T, P) float32."""
    s, p = theta.shape
    t = alpha.shape[1]
    bs, bt, bp = _tiles(s, t, p, block_p)
    # zero padding: padded sources add 0, padded targets and lanes are cut
    th = _pad_to(_pad_to(theta, 0, bs), 1, bp)
    al = _pad_to(_pad_to(alpha, 0, bs), 1, bt)
    (ss, pp), tt = th.shape, al.shape[1]
    out = pl.pallas_call(
        _combine_kernel,
        grid=(tt // bt, pp // bp, ss // bs),
        in_specs=[
            pl.BlockSpec((bs, bt), lambda i, j, k: (k, i)),
            pl.BlockSpec((bs, bp), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bt, bp), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((tt, pp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(al, th)
    return out[:t, :p]
