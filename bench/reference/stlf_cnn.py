"""Plain reference of the client math of arXiv:2304.12422, Sec. V: the
CNN (5x5 convolutions with 10 and 20 maps, each followed by ReLU and 2x2
max pooling, then FC 320->128->classes), local SGD on labeled samples,
Algorithm 1's pair divergence, the alpha-mixture of models, and
accuracies.

Straightforward ``jax.numpy`` with nothing of the program imported.
``dtype=float32`` computes every convolution and product at ``HIGHEST``
precision: the reference.  ``dtype=bfloat16`` holds parameters, data and
arithmetic in bfloat16: the control, the precision below the float32 the
configuration states.  Random draws (sample indices, initial weights)
come from ``jax.random`` with the simulator's key schedule, so the
reference sees the inputs the simulator saw.

This file is the client's side of the harness's contract
(``bench/harness/spec.py``): ``init``, ``cast``, ``stack``, ``train``,
``accuracies``, ``pair_divergence``, ``mix_targets`` and ``costs``.  The
CNN has no shared part: ``init`` returns the whole model, flat by leaf
name, and ``stack`` the device-major images.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
DN = ("NHWC", "HWIO", "NHWC")
#: the client's classes (the configuration's ``model.classes``) and
#: Algorithm 1's domain classifier, which tells device i's rows from j's
NUM_CLASSES = 10
DOMAIN_CLASSES = 2
#: a training sample costs three forwards: the forward, and a backward of
#: twice the work
TRAIN_FACTOR = 3


def shapes(num_classes: int, in_ch: int = 3) -> dict:
    """Leaf shapes (28 -> conv5 -> 24 -> pool -> 12 -> conv5 -> 8 ->
    pool -> 4, so the flattened features are 20 * 4 * 4 = 320)."""
    return {"conv1": (5, 5, in_ch, 10), "b1": (10,),
            "conv2": (5, 5, 10, 20), "b2": (20,),
            "fc1": (320, 128), "fcb1": (128,),
            "fc2": (128, num_classes), "fcb2": (num_classes,)}


def init(key, num_classes: int) -> dict:
    """Fan-in-scaled normal weights and zero biases, one key per leaf
    from ``split(key, 8)`` in sorted leaf order."""
    sh = shapes(num_classes)
    names = sorted(sh)
    keys = jax.random.split(key, len(names))
    out = {}
    for name, k in zip(names, keys):
        s = sh[name]
        if len(s) == 1:
            out[name] = jnp.zeros(s, jnp.float32)
        else:
            out[name] = jax.random.normal(k, s, jnp.float32) / \
                math.sqrt(math.prod(s[:-1]))
    return out


def stack(devices, dtype, n_max=None) -> dict:
    """Device-major arrays of the devices' data, padded to ``n_max`` rows
    (default: the largest device's); the images take ``dtype``, labels
    and masks keep their integer and boolean types."""
    if n_max is None:
        n_max = max(len(d.labels) for d in devices)

    def pad(arrs, fill, dt):
        out = np.full((len(arrs), n_max) + arrs[0].shape[1:], fill, dt)
        for i, a in enumerate(arrs):
            out[i, :len(a)] = a
        return out

    return {
        "x": jnp.asarray(pad([d.images for d in devices], 0.0,
                             np.float32), dtype),
        "y": jnp.asarray(pad([d.labels for d in devices], -1, np.int32)),
        "labeled": jnp.asarray(pad([d.labeled_mask for d in devices],
                                   False, bool)),
        "valid": jnp.asarray(pad([np.ones(len(d.labels), bool)
                                  for d in devices], False, bool)),
        "true_y": jnp.asarray(pad([d.true_labels for d in devices], -1,
                                  np.int32)),
    }


def _prec(dtype):
    return HIGHEST if jnp.dtype(dtype) == jnp.float32 else None


def forward(p, x, dtype):
    prec = _prec(dtype)

    def pool(h):
        return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    h = jax.lax.conv_general_dilated(x, p["conv1"], (1, 1), "VALID",
                                     dimension_numbers=DN, precision=prec)
    h = pool(jax.nn.relu(h + p["b1"]))
    h = jax.lax.conv_general_dilated(h, p["conv2"], (1, 1), "VALID",
                                     dimension_numbers=DN, precision=prec)
    h = pool(jax.nn.relu(h + p["b2"]))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(jnp.dot(h, p["fc1"], precision=prec) + p["fcb1"])
    return jnp.dot(h, p["fc2"], precision=prec) + p["fcb2"]


def xent(p, x, y, dtype):
    logits = forward(p, x, dtype)
    picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def cast(tree, dtype):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), tree)


# ------------------------------------------------------------ local SGD
@functools.partial(jax.jit, static_argnames=("iters", "batch", "lr",
                                             "dtype"))
def train(params, x, y, labeled, valid, lane_keys, update, *, iters: int,
          batch: int, lr: float, dtype):
    """Every lane: ``iters`` SGD steps of ``batch`` samples drawn
    uniformly from its labeled rows (all valid rows, label 0, for a lane
    with none); lanes outside ``update`` keep their parameters."""
    def lane(p, xl, yl, lab, val, key):
        sel = jnp.where(jnp.any(lab), lab, val)
        logits_w = jnp.where(sel, 0.0, -1e30)
        ys = jnp.maximum(yl, 0)

        def step(p, k):
            idx = jax.random.categorical(k, logits_w, shape=(batch,))
            g = jax.grad(xent)(p, xl[idx], ys[idx], dtype)
            return jax.tree_util.tree_map(
                lambda a, b: a - jnp.asarray(lr, dtype) * b, p, g), None

        p, _ = jax.lax.scan(step, p, jax.random.split(key, iters))
        return p

    new = jax.vmap(lane)(params, x, y, labeled, valid, lane_keys)
    keep = update
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(keep.reshape((-1,) + (1,) * (n.ndim - 1)),
                               n, o), new, params)


@functools.partial(jax.jit, static_argnames=("dtype",))
def accuracies(params, x, true_y, valid, *, dtype):
    """Share of each device's valid samples its model labels right."""
    def lane(p, xl, yl, val):
        hit = jnp.argmax(forward(p, xl, dtype), axis=-1) == yl
        return jnp.sum(hit & val) / jnp.maximum(jnp.sum(val), 1)

    return jax.vmap(lane)(params, x, true_y, valid)


# ------------------------------------------------------- Algorithm 1
@functools.partial(jax.jit, static_argnames=("tau", "T", "batch", "lr",
                                             "dtype"))
def pair_divergence(h0, xi, ni, xj, nj, keys, *, tau: int, T: int,
                    batch: int, lr: float, dtype):
    """Per lane: device i's rows labeled 0, device j's labeled 1; two
    copies of the classifier ``h0`` each take ``tau * T`` SGD steps on
    their own device's rows and are averaged every ``T`` steps; the
    average's error eps on the union gives d = clip(2 (1 - 2 eps), 0, 2).
    ``h0`` is batched with the lanes."""
    def lane(h, xa, na, xb, nb, key):
        lr_ = jnp.asarray(lr, dtype)

        def step(carry, inp):
            ha, hb = carry
            t, kt = inp
            ka, kb = jax.random.split(kt)
            ia = jax.random.randint(ka, (batch,), 0, na)
            ib = jax.random.randint(kb, (batch,), 0, nb)
            ga = jax.grad(xent)(ha, xa[ia], jnp.zeros(batch, jnp.int32),
                                dtype)
            gb = jax.grad(xent)(hb, xb[ib], jnp.ones(batch, jnp.int32),
                                dtype)
            ha = jax.tree_util.tree_map(lambda a, g: a - lr_ * g, ha, ga)
            hb = jax.tree_util.tree_map(lambda a, g: a - lr_ * g, hb, gb)
            sync = (t + 1) % T == 0
            avg = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, ha, hb)
            ha = jax.tree_util.tree_map(
                lambda a, m: jnp.where(sync, m, a), ha, avg)
            hb = jax.tree_util.tree_map(
                lambda a, m: jnp.where(sync, m, a), hb, avg)
            return (ha, hb), None

        (ha, hb), _ = jax.lax.scan(
            step, (h, h), (jnp.arange(tau * T),
                           jax.random.split(key, tau * T)))
        hbar = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, ha, hb)
        rows = jnp.arange(xa.shape[0])

        def wrong(xd, nd, label):
            pred = jnp.argmax(forward(hbar, xd, dtype), axis=-1)
            valid = rows < nd
            return jnp.sum(valid & (pred != label)), jnp.sum(valid)

        wa, ca = wrong(xa, na, 0)
        wb, cb = wrong(xb, nb, 1)
        eps = (wa + wb) / jnp.maximum(ca + cb, 1)
        return jnp.clip(2.0 * (1.0 - 2.0 * eps), 0.0, 2.0)

    return jax.vmap(lane)(h0, xi, ni, xj, nj, keys)


# ------------------------------------------------------------ transfer
def mix_targets(params: dict, alpha: np.ndarray, psi: np.ndarray,
                dtype) -> dict:
    """Targets (psi = 1) take sum_s alpha[s, t] params[s]; every other
    device keeps its own.  float32: the sum in float64 on the host."""
    tgt = np.asarray(psi) == 1.0
    out = {}
    for name, leaf in params.items():
        if jnp.dtype(dtype) == jnp.float32:
            th = np.asarray(leaf, np.float64).reshape(leaf.shape[0], -1)
            mixed = (np.asarray(alpha, np.float64).T @ th).astype(
                np.float32)
            own = np.asarray(leaf).reshape(leaf.shape[0], -1)
            out[name] = jnp.asarray(np.where(tgt[:, None], mixed, own)
                                    .reshape(leaf.shape))
        else:
            th = leaf.reshape(leaf.shape[0], -1)
            mixed = jnp.einsum("st,sv->tv", jnp.asarray(alpha, dtype), th,
                               preferred_element_type=dtype)
            out[name] = jnp.where(jnp.asarray(tgt)[:, None], mixed,
                                  th).reshape(leaf.shape)
    return out



# --------------------------------------------------------------- costs
# Multiply-adds of one forward sample on 28x28x3 inputs, by layer:
#
#   conv1  24*24 outputs * 10 maps * 5*5*3      = 432,000
#   conv2   8*8  outputs * 20 maps * 5*5*10     = 320,000
#   fc1    320 * 128                            =  40,960
#   fc2    128 * classes                        =   1,280 (10 classes)
#
# 794,240 multiply-adds (1,588,480 operations) with 10 classes.  Biases,
# activations and pooling are not counted.
def cnn_forward_macs(num_classes: int = NUM_CLASSES, hw: int = 28,
                     in_ch: int = 3, k: int = 5, maps=(10, 20),
                     hidden: int = 128) -> int:
    o1 = hw - k + 1                         # 24
    p1 = o1 // 2                            # 12
    o2 = p1 - k + 1                         # 8
    p2 = o2 // 2                            # 4
    conv1 = o1 * o1 * maps[0] * k * k * in_ch
    conv2 = o2 * o2 * maps[1] * k * k * maps[0]
    flat = p2 * p2 * maps[1]
    return conv1 + conv2 + flat * hidden + hidden * num_classes


def cnn_forward_flops(num_classes: int = NUM_CLASSES) -> int:
    return 2 * cnn_forward_macs(num_classes)


def cnn_params(num_classes: int = NUM_CLASSES) -> int:
    return (5 * 5 * 3 * 10 + 10) + (5 * 5 * 10 * 20 + 20) + \
        (320 * 128 + 128) + (128 * num_classes + num_classes)


def costs(sim: dict) -> dict:
    """Operations of the client's work under the run's settings ``sim``:

      train_sample    one training sample, three forwards
      eval_sample     one measurement forward
      pair            one Algorithm-1 estimate: 2 domain classifiers *
                      tau * T steps * batch samples, three forwards
                      each, then a forward of each device's samples
      transfer_width  V, the parameters of a device the combine moves
    """
    f = cnn_forward_flops(NUM_CLASSES)
    f2 = cnn_forward_flops(DOMAIN_CLASSES)
    steps = 2 * sim["div_tau"] * sim["div_T"] * sim["batch"]
    return {"train_sample": TRAIN_FACTOR * f, "eval_sample": f,
            "pair": (steps * TRAIN_FACTOR
                     + 2 * sim["samples_per_device"]) * f2,
            "transfer_width": cnn_params(NUM_CLASSES)}
