"""Public wrappers: flat and pytree alpha-combine."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.alpha_combine.kernel import alpha_combine_flat
from repro.nn.param import flatten_to_vector, unflatten_from_vector

#: the layer name the combine's ops carry in the HLO metadata (the same
#: as the XLA combine's in ``fl.transfer.combine_models``)
SCOPE = "transfer_combine"


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def alpha_combine(theta, alpha, *, interpret: Optional[bool] = None):
    """theta: (S, P); alpha: (S, T) -> (T, P)."""
    if interpret is None:
        interpret = _on_cpu()
    with jax.named_scope(SCOPE):
        return alpha_combine_flat(theta, alpha, interpret=interpret)


def alpha_combine_slab(theta, alpha_cols, *,
                       interpret: Optional[bool] = None):
    """Per-shard transfer slab: the FULL flattened source stack against a
    local block of target columns.  theta: (S, P); alpha_cols: (S, T_loc)
    -> (T_loc, P).  This is the mesh-sharded pool's transfer hot path —
    each shard all-gathers theta once and streams it through the kernel
    for just its own target columns, so every source's parameters cross
    the interconnect once regardless of how many shards consume them."""
    if interpret is None:
        interpret = _on_cpu()
    with jax.named_scope(SCOPE):
        return alpha_combine_flat(theta,
                                  jnp.asarray(alpha_cols, jnp.float32),
                                  interpret=interpret)


def alpha_combine_tree(params_stack, alpha, *,
                       interpret: Optional[bool] = None):
    """Pytree with leading device axis -> same pytree, mixed columns."""
    if interpret is None:
        interpret = _on_cpu()
    flat = jax.vmap(flatten_to_vector)(params_stack)      # (S, P)
    with jax.named_scope(SCOPE):
        mixed = alpha_combine_flat(flat, jnp.asarray(alpha, jnp.float32),
                                   interpret=interpret)   # (T, P)
    like = jax.tree_util.tree_map(lambda a: a[0], params_stack)
    return jax.vmap(lambda v: unflatten_from_vector(v, like))(mixed)
