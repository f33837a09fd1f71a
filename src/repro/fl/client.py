"""Client-local training, vectorized across devices.

Every device's (padded) dataset is stacked into one array so local training
for all devices is ONE vmapped, jit-compiled scan — the TPU-native analogue
of the paper's per-device SGD loops (clients map onto the 'data' mesh axis in
the distributed runtime; on CPU the vmap simply vectorizes).

Paper protocol (Sec. V): SGD, 100 iterations, mini-batch 10, lr 0.01.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.partition import DeviceData
from repro.fl import cnn


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["x", "y", "labeled", "valid", "true_y",
                                "counts"], meta_fields=[])
@dataclasses.dataclass
class StackedClients:
    """Device-major stacked data.  x: (N, n_max, ...); counts: (N,)."""
    x: jnp.ndarray
    y: jnp.ndarray              # shown labels; -1 where unlabeled
    labeled: jnp.ndarray        # (N, n_max) bool
    valid: jnp.ndarray          # (N, n_max) bool (False = padding)
    true_y: jnp.ndarray         # ground truth (eval only)
    counts: jnp.ndarray         # (N,)

    @property
    def n_devices(self) -> int:
        return self.x.shape[0]


def pad_clients(devices: List[DeviceData], n_max: int) -> StackedClients:
    """The stack layout, on the host: each device's data padded to
    ``n_max`` rows, as numpy leaves.  ``stack_clients`` pads the whole
    pool with it; the pool's row write pads the changed devices."""
    def pad(a, fill=0):
        out = np.full((len(devices), n_max) + a[0].shape[1:], fill,
                      dtype=a[0].dtype)
        for i, arr in enumerate(a):
            out[i, :len(arr)] = arr
        return out

    return StackedClients(
        x=pad([d.images for d in devices], 0.0),
        y=pad([d.labels for d in devices], -1),
        labeled=pad([d.labeled_mask for d in devices], False),
        valid=pad([np.ones(d.n, bool) for d in devices], False),
        true_y=pad([d.true_labels for d in devices], -1),
        counts=np.asarray([d.n for d in devices], np.int32),
    )


def stack_clients(devices: List[DeviceData]) -> StackedClients:
    return jax.tree_util.tree_map(
        jnp.asarray, pad_clients(devices, max(d.n for d in devices)))


def set_client_rows(clients: StackedClients, idx, rows: StackedClients):
    """Rows ``idx`` of every leaf of ``clients`` set to ``rows``."""
    return jax.tree_util.tree_map(lambda a, r: a.at[idx].set(r),
                                  clients, rows)


# ------------------------------------------------------------- local SGD
def _sgd_scan(params, x, y, sel_weight, key, *, iters, batch, lr,
              loss_fn):
    """Train on data sampled ∝ sel_weight (0/1 mask).  Shapes static."""
    n = x.shape[0]
    logits_w = jnp.where(sel_weight > 0, 0.0, -1e30)

    def step(p, k):
        idx = jax.random.categorical(k, logits_w, shape=(batch,))
        g = jax.grad(loss_fn)(p, x[idx], y[idx])
        p = jax.tree_util.tree_map(
            lambda a, b: a - lr * b.astype(a.dtype), p, g)
        return p, None

    keys = jax.random.split(key, iters)
    params, _ = jax.lax.scan(step, params, keys)
    return params


@functools.partial(jax.jit, static_argnames=("iters", "batch", "lr"))
def train_sources(params_stack, clients: StackedClients, keys, *,
                  iters: int = 100, batch: int = 10, lr: float = 0.01):
    """vmapped local supervised training on each device's LABELED data.

    Devices with no labeled data get a uniform dummy distribution over
    valid rows with y clamped to 0 — their output is discarded by the
    caller (they will be targets).
    """
    def one(p, x, y, labeled, valid, key):
        sel = jnp.where(jnp.any(labeled), labeled.astype(jnp.float32),
                        valid.astype(jnp.float32))
        y_safe = jnp.maximum(y, 0)
        return _sgd_scan(p, x, y_safe, sel, key, iters=iters, batch=batch,
                         lr=lr, loss_fn=cnn.xent_loss)

    return jax.vmap(one)(params_stack, clients.x, clients.y,
                         clients.labeled, clients.valid, keys)


@jax.jit
def empirical_errors(params_stack, clients: StackedClients) -> jnp.ndarray:
    """eq (3) per device: unlabeled data counted as error 1."""
    def one(p, x, y, labeled, valid):
        pred = jnp.argmax(cnn.cnn_forward(p, x), axis=-1)
        wrong_lab = jnp.logical_and(labeled, pred != y)
        err = jnp.logical_or(wrong_lab,
                             jnp.logical_and(valid, ~labeled))
        return jnp.sum(err.astype(jnp.float32)) / jnp.maximum(
            jnp.sum(valid.astype(jnp.float32)), 1.0)

    return jax.vmap(one)(params_stack, clients.x, clients.y,
                         clients.labeled, clients.valid)


@jax.jit
def true_accuracies(params_stack, clients: StackedClients) -> jnp.ndarray:
    """Ground-truth accuracy of each device's model on its own data."""
    def one(p, x, ty, valid):
        return cnn.accuracy(p, x, ty, mask=valid)

    return jax.vmap(one)(params_stack, clients.x, clients.true_y,
                         clients.valid)


def init_client_params(n_devices: int, key, num_classes: int = 10,
                       shared_init: bool = True):
    """Stacked per-device parameters.  ``shared_init=True`` (the FL norm,
    and a precondition for meaningful parameter averaging at targets)
    broadcasts ONE initialization to every device."""
    if shared_init:
        p = cnn.cnn_init(key, num_classes)
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (n_devices,) + a.shape), p)
    keys = jax.random.split(key, n_devices)
    return jax.vmap(lambda k: cnn.cnn_init(k, num_classes))(keys)
