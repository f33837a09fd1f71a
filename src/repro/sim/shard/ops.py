"""shard_map building blocks of the sharded device pool.

Each builder closes over a pool mesh and returns ONE jitted callable so
the per-round pipeline compiles once per shape.  All four follow the
same contract: the device (pool) axis of every array argument is
partitioned over ``DEVICE_AXIS`` in contiguous blocks, per-lane
computation is reused VERBATIM from the single-host implementations
(``network_step_core``, ``pairwise_divergence_values``,
``true_accuracies``, the alpha-combine kernel), and anything a shard
needs beyond its own block arrives through an explicit collective:

  train     — none: local training is embarrassingly parallel in the
              device axis, each shard just runs its block's lanes.
  pair divergence — the Algorithm-1 pair subsets are partitioned over
              shards, and each shard ALL-GATHERS the client arrays so
              it can stage any (i, j) pair regardless of which shards
              own i and j (the cross-shard gather; a pod would fetch
              just the pair members' rows, the program shape is the
              same).
  transfer  — each shard flattens its local source block, all-gathers
              the (S, P) stacked parameter matrix once, and emits ONLY
              its own target columns through the Pallas alpha_combine
              kernel (kernels/alpha_combine) — the model-transfer hot
              path: every source crosses the interconnect once, however
              many shards consume it.
  accuracies — per-lane eval, no collective.

Because every per-lane computation is the single-host one and lanes are
independent, a sharded run reproduces the single-host trajectory
bit-for-bit — the mesh changes WHERE lanes run, never what they
compute.  (``check_vma=False``: pallas_call has no replication rule;
every output here is genuinely device-sharded anyway.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.fl.client import true_accuracies
from repro.fl.divergence import pairwise_divergence_values
from repro.kernels.alpha_combine.ops import alpha_combine_slab
from repro.nn.param import flatten_to_vector, unflatten_from_vector
from repro.sim.shard.mesh import DEVICE_AXIS
from repro.sim.training import network_step_core


def _smap(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def build_train_step(mesh, *, iters: int, batch: int, lr: float,
                     client=None):
    """(frozen, params, clients, keys, active, train_mask) -> (params',
    eps, acc): ``frozen`` (the client's shared part) replicated, every
    other argument padded to a multiple of the shard count and
    device-sharded; per-device keys come from the caller (the full
    pool's ``split``, exactly the single-host stream)."""
    spec = P(DEVICE_AXIS)

    def body(f, p, c, k, a, m):
        return network_step_core(p, c, k, a, m, iters=iters, batch=batch,
                                 lr=lr, client=client, frozen=f)

    return jax.jit(_smap(body, mesh, (P(),) + (spec,) * 5, (spec,) * 3))


def build_pair_values(mesh, *, tau: int, T: int, batch: int, lr: float,
                      client=None):
    """(h0, rows, pi, pj, keys) -> (npairs,) d_H values; the PAIR axis
    is device-sharded (padded by the caller), the rows Algorithm 1
    classifies (``PairInputs``) are device-sharded and all-gathered
    inside — the cross-shard gather that lets any shard estimate any
    pair."""
    spec = P(DEVICE_AXIS)

    def body(h0, c, pi, pj, keys):
        full = jax.tree_util.tree_map(
            lambda a: jax.lax.all_gather(a, DEVICE_AXIS, tiled=True), c)
        return pairwise_divergence_values(h0, full, pi, pj, keys,
                                          tau=tau, T=T, batch=batch, lr=lr,
                                          client=client)

    return jax.jit(_smap(body, mesh, (P(), spec, spec, spec, spec), spec))


def build_transfer(mesh):
    """(params, alpha, psi) -> params' with targets (psi=1) holding their
    alpha-mixtures — ``fl.transfer.apply_transfer`` with the combine
    routed through the Pallas kernel per shard.  alpha is sharded over
    its COLUMN (target) axis to match the row-sharded parameter stack."""
    spec = P(DEVICE_AXIS)

    def body(p, a_cols, psi_loc):
        flat = jax.vmap(flatten_to_vector)(p)                  # (loc, V)
        theta = jax.lax.all_gather(flat, DEVICE_AXIS, tiled=True)
        mixed_flat = alpha_combine_slab(theta, a_cols)         # (loc, V)
        like = jax.tree_util.tree_map(lambda x: x[0], p)
        mixed = jax.vmap(lambda v: unflatten_from_vector(v, like))(
            mixed_flat)

        def sel(own, mix):
            shape = (-1,) + (1,) * (own.ndim - 1)
            m = jnp.reshape(psi_loc, shape).astype(own.dtype)
            return own * (1 - m) + mix.astype(own.dtype) * m

        return jax.tree_util.tree_map(sel, p, mixed)

    return jax.jit(_smap(body, mesh, (spec, P(None, DEVICE_AXIS), spec),
                         spec))


def build_accuracies(mesh, client=None):
    """(frozen, params, clients) -> (P',) ground-truth accuracies,
    per-shard; ``frozen`` replicated."""
    spec = P(DEVICE_AXIS)
    return jax.jit(_smap(
        lambda f, p, c: true_accuracies(p, c, client=client, frozen=f),
        mesh, (P(), spec, spec), spec))
