"""Batched per-round client training: the whole device axis in ONE
compiled call.

Reuses the StackedClients layout and the vmapped SGD of repro.fl.client;
the fusion here is that local training, the empirical-error refresh and
the ground-truth accuracy sweep all run inside a single jit so a 64+
device network advances one round without returning to Python in between.

Unlike the one-shot prepare_round (where untrained unlabeled devices are
simply overwritten by the transfer), the simulator CONTINUES from mixed
parameters round after round — so devices with no labeled data must keep
their received parameters instead of drifting under the dummy y=0 SGD that
train_sources runs for them; ``network_step`` masks their update out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.fl.client import (StackedClients, empirical_errors,
                             train_sources, true_accuracies)


def network_step_core(params, clients: StackedClients, keys, active,
                      train_mask=None, *, iters: int, batch: int,
                      lr: float, client=None, frozen=None):
    """The traceable body shared by every entry point: ``network_step``
    (full pool, one host), ``subset_network_step`` (compact gathered
    lanes), and the mesh-sharded pool (per-shard slices under shard_map).
    ``keys``: per-device PRNG keys, (N, key_dim) — every lane is
    independent, so callers may gather/shard the device axis freely
    without changing any lane's result.  ``client`` is the model
    (default the paper's CNN), ``frozen`` its shared part."""
    model = dict(client=client, frozen=frozen)
    with jax.named_scope("train_scan"):
        trained = train_sources(params, clients, keys,
                                iters=iters, batch=batch, lr=lr, **model)
    update = jnp.logical_and(jnp.any(clients.labeled, axis=1),
                             jnp.asarray(active))           # (N,)
    if train_mask is not None:
        update = jnp.logical_and(update, jnp.asarray(train_mask))

    def keep(new, old):
        m = update.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old)

    params = jax.tree_util.tree_map(keep, trained, params)
    eps = empirical_errors(params, clients, **model)
    acc = true_accuracies(params, clients, **model)
    return params, eps, acc


@functools.partial(jax.jit, static_argnames=("iters", "batch", "lr",
                                             "client"))
def network_step(params, clients: StackedClients, key, active,
                 train_mask=None, *, iters: int, batch: int, lr: float,
                 client=None, frozen=None):
    """One simulator round of local training for every device at once.

    ``active``: (N,) bool — devices currently in the network.  Departed
    devices must NOT keep training while away: their params stay frozen
    until they rejoin.  (The SGD itself still runs for every pool slot —
    shapes stay static across churn — only its result is discarded.)

    ``train_mask``: optional (N,) bool — the async-gossip executor's
    clock-eligibility subset.  Devices outside it keep their params this
    tick; the call stays ONE jitted computation (the masked lanes still
    run and are discarded — free under SPMD on a pod, and the price of a
    static shape on one host).  ``None`` (the sync engine) trains every
    active device and compiles to the same graph as before the mask
    existed.

    Returns (params', eps_hat, own_acc):
      params'  — updated stacked params; inactive devices, devices
                 without labeled data, and devices outside train_mask
                 are left untouched
      eps_hat  — empirical errors (unlabeled counted as 1), shape (N,)
      own_acc  — ground-truth accuracy of each device's own params, (N,)
    """
    keys = jax.random.split(key, clients.n_devices)
    return network_step_core(params, clients, keys, active, train_mask,
                             iters=iters, batch=batch, lr=lr,
                             client=client, frozen=frozen)


@functools.partial(jax.jit, static_argnames=("iters", "batch", "lr",
                                             "client"))
def subset_network_step(params, clients: StackedClients, keys, active, *,
                        iters: int, batch: int, lr: float, client=None,
                        frozen=None):
    """Compact-lane variant for the async subset-gather path: the caller
    gathers ONLY the clock-eligible lanes (params/clients rows and their
    per-device keys from the full pool's ``split``), so no masked no-op
    SGD runs for the ineligible majority.  Per-lane results are identical
    to the masked full-pool step — lanes are independent and keep their
    full-pool PRNG keys — which the parity test pins."""
    return network_step_core(params, clients, keys, active, None,
                             iters=iters, batch=batch, lr=lr,
                             client=client, frozen=frozen)


@functools.partial(jax.jit, static_argnames=("client",))
def mixed_accuracies(params, clients: StackedClients, *, client=None,
                     frozen=None):
    """Ground-truth accuracy of (post-transfer) stacked params."""
    return true_accuracies(params, clients, client=client, frozen=frozen)
