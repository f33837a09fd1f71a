"""A second client for the harness's tests, shaped like a language-model
client and unlike the CNN in each way the contract of ``spec.py`` has to
carry: its inputs are int32 token ids (up to 4,095, far past what
bfloat16 holds exactly), its per-device parameters are a nested tree (a
low-rank adapter and a head), and its token embedding is a frozen part
every device shares, held inside this module and never returned by
``init``.

The model: the mean of the embedded tokens, plus the adapter's
correction, through a linear head.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

VOCAB = 4096
SEQ = 8
EMBED = 16
RANK = 4
TRAIN_FACTOR = 3


@dataclasses.dataclass
class TokenDevice:
    tokens: np.ndarray          # (n, SEQ) int32
    labels: np.ndarray          # (n,) int32; -1 where unlabeled
    labeled_mask: np.ndarray    # (n,) bool
    true_labels: np.ndarray     # (n,) int32


def make_devices(seed: int, n_devices: int, rows, classes: int):
    """Devices of ``rows[i % len(rows)]`` samples each, half labeled,
    with ids drawn over the whole vocabulary."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_devices):
        n = rows[i % len(rows)]
        true = rng.integers(0, classes, n).astype(np.int32)
        mask = np.arange(n) % 2 == 0
        out.append(TokenDevice(
            tokens=rng.integers(0, VOCAB, (n, SEQ)).astype(np.int32),
            labels=np.where(mask, true, -1).astype(np.int32),
            labeled_mask=mask, true_labels=true))
    return out


@functools.lru_cache(maxsize=None)
def embedding():
    """The frozen embedding every device shares, from a fixed seed."""
    return np.random.default_rng(7).standard_normal(
        (VOCAB, EMBED)).astype(np.float32)


def init(key, num_classes: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"adapter": {
                "down": jax.random.normal(k1, (EMBED, RANK)) /
                math.sqrt(EMBED),
                "up": jax.random.normal(k2, (RANK, EMBED)) / math.sqrt(RANK)},
            "head": {
                "w": jax.random.normal(k3, (EMBED, num_classes)) /
                math.sqrt(EMBED),
                "b": jnp.zeros((num_classes,), jnp.float32)}}


def cast(tree, dtype):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), tree)


def stack(devices, dtype, n_max=None) -> dict:
    """Token ids stay int32 whatever ``dtype`` is."""
    if n_max is None:
        n_max = max(len(d.labels) for d in devices)

    def pad(arrs, fill, dt):
        out = np.full((len(arrs), n_max) + arrs[0].shape[1:], fill, dt)
        for i, a in enumerate(arrs):
            out[i, :len(a)] = a
        return jnp.asarray(out)

    return {"x": pad([d.tokens for d in devices], 0, np.int32),
            "y": pad([d.labels for d in devices], -1, np.int32),
            "labeled": pad([d.labeled_mask for d in devices], False, bool),
            "valid": pad([np.ones(len(d.labels), bool) for d in devices],
                         False, bool),
            "true_y": pad([d.true_labels for d in devices], -1, np.int32)}


def forward(p, x, dtype):
    h = jnp.asarray(embedding(), dtype)[x].mean(axis=1)
    h = h + jax.nn.relu(h @ p["adapter"]["down"]) @ p["adapter"]["up"]
    return h @ p["head"]["w"] + p["head"]["b"]


def xent(p, x, y, dtype):
    logits = forward(p, x, dtype)
    picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def _sgd(p, g, lr, dtype):
    return jax.tree_util.tree_map(
        lambda a, b: a - jnp.asarray(lr, dtype) * b, p, g)


@functools.partial(jax.jit, static_argnames=("iters", "batch", "lr",
                                             "dtype"))
def train(params, x, y, labeled, valid, lane_keys, update, *, iters: int,
          batch: int, lr: float, dtype):
    def lane(p, xl, yl, lab, val, key):
        logits_w = jnp.where(jnp.where(jnp.any(lab), lab, val), 0.0, -1e30)

        def step(p, k):
            idx = jax.random.categorical(k, logits_w, shape=(batch,))
            g = jax.grad(xent)(p, xl[idx], jnp.maximum(yl, 0)[idx], dtype)
            return _sgd(p, g, lr, dtype), None

        return jax.lax.scan(step, p, jax.random.split(key, iters))[0]

    new = jax.vmap(lane)(params, x, y, labeled, valid, lane_keys)
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(update.reshape((-1,) + (1,) * (n.ndim - 1)),
                               n, o), new, params)


@functools.partial(jax.jit, static_argnames=("dtype",))
def accuracies(params, x, true_y, valid, *, dtype):
    def lane(p, xl, yl, val):
        hit = jnp.argmax(forward(p, xl, dtype), axis=-1) == yl
        return jnp.sum(hit & val) / jnp.maximum(jnp.sum(val), 1)

    return jax.vmap(lane)(params, x, true_y, valid)


@functools.partial(jax.jit, static_argnames=("tau", "T", "batch", "lr",
                                             "dtype"))
def pair_divergence(h0, xi, ni, xj, nj, keys, *, tau: int, T: int,
                    batch: int, lr: float, dtype):
    def lane(h, xa, na, xb, nb, key):
        def step(carry, kt):
            ha, hb = carry
            ka, kb = jax.random.split(kt)
            ia = jax.random.randint(ka, (batch,), 0, na)
            ib = jax.random.randint(kb, (batch,), 0, nb)
            ha = _sgd(ha, jax.grad(xent)(ha, xa[ia], jnp.zeros(
                batch, jnp.int32), dtype), lr, dtype)
            hb = _sgd(hb, jax.grad(xent)(hb, xb[ib], jnp.ones(
                batch, jnp.int32), dtype), lr, dtype)
            return (ha, hb), None

        (ha, hb), _ = jax.lax.scan(step, (h, h),
                                   jax.random.split(key, tau * T))
        hbar = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, ha, hb)
        rows = jnp.arange(xa.shape[0])

        def wrong(xd, nd, label):
            pred = jnp.argmax(forward(hbar, xd, dtype), axis=-1)
            return jnp.sum((rows < nd) & (pred != label))

        eps = (wrong(xa, na, 0) + wrong(xb, nb, 1)) / (na + nb)
        return jnp.clip(2.0 * (1.0 - 2.0 * eps), 0.0, 2.0)

    return jax.vmap(lane)(h0, xi, ni, xj, nj, keys)


def mix_targets(params, alpha, psi, dtype):
    tgt = jnp.asarray(np.asarray(psi) == 1.0)

    def mix(leaf):
        th = leaf.reshape(leaf.shape[0], -1)
        mixed = jnp.asarray(alpha, dtype).T @ th
        return jnp.where(tgt[:, None], mixed, th).reshape(leaf.shape)

    return jax.tree_util.tree_map(mix, params)


def forward_macs(num_classes: int) -> int:
    return 2 * EMBED * RANK + EMBED * num_classes


def costs(sim: dict, num_classes: int = 3) -> dict:
    f = 2 * forward_macs(num_classes)
    f2 = 2 * forward_macs(2)
    steps = 2 * sim["div_tau"] * sim["div_T"] * sim["batch"]
    return {"train_sample": TRAIN_FACTOR * f, "eval_sample": f,
            "pair": (steps * TRAIN_FACTOR
                     + 2 * sim["samples_per_device"]) * f2,
            "transfer_width": 2 * EMBED * RANK + (EMBED + 1) * num_classes}
