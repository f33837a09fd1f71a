"""Source -> target model transfer: h_t = sum_s alpha[s, t] h_s.

On a pod this is the sparse weighted gather along the client-sharded axis
(GSPMD lowers the einsum to all-gather / reduce-scatter / collective-permute
chains depending on alpha's sparsity); the ST-LF energy term prices exactly
this traffic.  The inner flattened weighted-combine is also available as a
Pallas kernel (kernels/alpha_combine) for the HBM-bound many-clients case.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def combine_models(params_stack, alpha, *, impl: str = "xla"):
    """params_stack: pytree with leading device axis N; alpha: (N, N)
    column-stochastic over targets (alpha[s, t]).  Returns the same pytree
    where entry t = sum_s alpha[s, t] * params[s].  Rows of sources are
    untouched targets' mixtures; callers select which rows to keep.

    Both impls contract in full float32 (``HIGHEST``; a TPU's default
    matmul would round the operands to bfloat16), so the XLA path and
    the Pallas kernel compute the same mixture on every backend."""
    alpha = jnp.asarray(alpha, jnp.float32)
    if impl == "pallas":
        from repro.kernels.alpha_combine import ops as ac_ops
        return ac_ops.alpha_combine_tree(params_stack, alpha)
    with jax.named_scope("transfer_combine"):
        return jax.tree_util.tree_map(
            lambda p: jnp.einsum("s...,st->t...", p.astype(jnp.float32),
                                 alpha, precision=jax.lax.Precision.HIGHEST
                                 ).astype(p.dtype), params_stack)


def apply_transfer(params_stack, alpha, psi):
    """Targets (psi=1) receive their alpha-mixture; sources keep their own
    locally-trained parameters."""
    mixed = combine_models(params_stack, alpha)
    psi = jnp.asarray(psi, jnp.float32)

    def sel(own, mix):
        shape = (-1,) + (1,) * (own.ndim - 1)
        m = jnp.reshape(psi, shape).astype(own.dtype)
        return own * (1 - m) + mix * m

    return jax.tree_util.tree_map(sel, params_stack, mixed)


def column_normalize(alpha: np.ndarray, psi: np.ndarray,
                     energy_K: np.ndarray = None,
                     eps_hat: np.ndarray = None) -> np.ndarray:
    """Project raw link weights onto (P)'s feasible set: zero rows for
    targets / columns for sources, unit column sums at targets.

    A target whose column sums to ~0 (every candidate link deactivated)
    still must receive unit weight — constraints (75)+(76) squeeze
    |sum_i alpha_ij - psi_j| <= eps_C.  The rescue source is chosen by the
    cheapest criterion available rather than arbitrarily: minimum link
    energy ``energy_K[:, j]`` when given, else the lowest-error source
    (``eps_hat``), else the first source (the historical tie-break, kept
    as the final fallback so callers without measurements stay valid).
    """
    a = np.array(alpha, float)
    a[psi == 1.0, :] = 0.0
    a[:, psi == 0.0] = 0.0
    np.fill_diagonal(a, 0.0)
    for j in np.flatnonzero(psi == 1.0):
        c = a[:, j].sum()
        if c > 1e-12:
            a[:, j] /= c
        else:
            srcs = np.flatnonzero(psi == 0.0)
            if len(srcs) == 0:
                continue
            if energy_K is not None:
                pick = srcs[int(np.argmin(np.asarray(energy_K)[srcs, j]))]
            elif eps_hat is not None:
                pick = srcs[int(np.argmin(np.asarray(eps_hat)[srcs]))]
            else:
                pick = srcs[0]
            a[pick, j] = 1.0
    return a
