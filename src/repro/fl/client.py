"""Client-local training, vectorized across devices.

Every device's (padded) dataset is stacked into one array so local training
for all devices is ONE vmapped, jit-compiled scan — the TPU-native analogue
of the paper's per-device SGD loops (clients map onto the 'data' mesh axis in
the distributed runtime; on CPU the vmap simply vectorizes).

Paper protocol (Sec. V): SGD, 100 iterations, mini-batch 10, lr 0.01.

Client models: what the simulator asks of the model each device trains.
A client model is a frozen (hashable) object; the jitted phases take it
as a static argument.  It gives

  init(key)                  the per-device leaves, float32: what each
                             device trains and what the transfer mixes
  init_frozen(key)           the part every device shares and nobody
                             trains (None when there is none); it is
                             placed once and passed to every phase
                             un-batched
  loss(frozen, p, x, y)      the mean training loss of a batch
  correct(frozen, p, x, y)   (B,) the share of each sample's targets
                             predicted right (1 or 0 for a classifier of
                             whole samples; the token share for tagging)
  pair_init(key)             Algorithm 1's 2-class hypothesis
  pair_loss(h, x, y), pair_logits(h, x)
                             the hypothesis over Algorithm 1's rows
  pair_inputs(frozen, p0, clients)
                             the rows Algorithm 1 classifies (``PairInputs``)
                             of every device, from the stacked data and
                             the shared initial leaves ``p0``
  lane_chunk                 lanes (devices) one step of the train and
                             eval maps holds (None: every lane in one vmap)
  tokens_per_sample          token positions in one sample (0: the samples
                             are not token sequences); a token sample is
                             that many of Algorithm 1's rows, so its
                             batch of samples is batch x tokens_per_sample
                             rows
  featurized                 True when ``pair_inputs`` computes features,
                             so the engine keeps them per data version

``CNNClient`` is the paper's CNN (Sec. V): no frozen part, Algorithm 1's
hypothesis the same CNN with 2 outputs over the images.  ``ZambaClient``
tags every token of a sequence with one of ``num_classes`` topics: a
frozen Zamba2 backbone in bfloat16 (``repro.models.zamba``), per-device
gate_up adapters of its hybrid layers and a linear head; Algorithm 1's
hypothesis is a 2-class linear head over the backbone's per-token final
hidden states under the shared initial adapters, computed once
(``SimulationEngine.featurize``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import ClassVar, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, get_config
from repro.data.partition import DeviceData
from repro.fl import cnn

F32 = jnp.float32
#: the frozen backbone's stream off the engine's init key
FROZEN_STREAM = 2 ** 22


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


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["x", "counts"], meta_fields=[])
@dataclasses.dataclass
class PairInputs:
    """The rows Algorithm 1 classifies, device-major: x (N, rows, ...),
    counts (N,) valid rows per device."""
    x: jnp.ndarray
    counts: jnp.ndarray

    @property
    def n_devices(self) -> int:
        return self.x.shape[0]


# ---------------------------------------------------------- client models
def _xent(logits, y):
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - ll)


@dataclasses.dataclass(frozen=True)
class CNNClient:
    num_classes: int = 10
    lane_chunk: Optional[int] = None
    tokens_per_sample: int = 0
    featurized: bool = False

    def init(self, key):
        return cnn.cnn_init(key, self.num_classes)

    def init_frozen(self, key):
        return None

    def loss(self, frozen, p, x, y):
        return cnn.xent_loss(p, x, y)

    def correct(self, frozen, p, x, y):
        pred = jnp.argmax(cnn.cnn_forward(p, x), axis=-1)
        return (pred == y).astype(F32)

    def pair_init(self, key):
        return cnn.cnn_init(key, num_classes=2)

    def pair_loss(self, h, x, y):
        return cnn.xent_loss(h, x, y)

    def pair_logits(self, h, x):
        return cnn.cnn_forward(h, x)

    def pair_inputs(self, frozen, p0, clients):
        return PairInputs(clients.x, clients.counts)


def _head(key, d: int, classes: int):
    return {"w": jax.random.normal(key, (d, classes), F32) / math.sqrt(d),
            "b": jnp.zeros((classes,), F32)}


@dataclasses.dataclass(frozen=True)
class ZambaClient:
    cfg: ModelConfig
    seq_len: int
    num_classes: int = 10
    lane_chunk: Optional[int] = 1
    featurized: bool = True
    #: the matmuls' input dtype and the features' storage dtype (the
    #: frozen weights are bfloat16 as the model is distributed)
    matmul_dtype: ClassVar = jnp.bfloat16

    @property
    def tokens_per_sample(self) -> int:
        return self.seq_len

    def init(self, key):
        """{"adapters": [{down, up}] per hybrid invocation, "head": {w,
        b}}: the head from ``split(key)[0]``, the adapters from the
        other half."""
        from repro.models import zamba
        k_head, k_ad = jax.random.split(key)
        return {"adapters": zamba.init_adapters(self.cfg, k_ad),
                "head": _head(k_head, self.cfg.d_model, self.num_classes)}

    def init_frozen(self, key):
        from repro.models import zamba
        return zamba.init_backbone(self.cfg, jax.random.fold_in(
            key, FROZEN_STREAM), jnp.bfloat16)

    def _mm(self, x, w):
        return jnp.einsum("...d,dc->...c", x.astype(self.matmul_dtype),
                          w.astype(self.matmul_dtype),
                          preferred_element_type=F32)

    def hidden(self, frozen, adapters, x):
        from repro.models import zamba
        return zamba.backbone(self.cfg, frozen, adapters, x,
                              dtype=self.matmul_dtype)

    def logits(self, frozen, p, x):
        return self._mm(self.hidden(frozen, p["adapters"], x),
                        p["head"]["w"]) + p["head"]["b"]

    def loss(self, frozen, p, x, y):
        return _xent(self.logits(frozen, p, x), y)

    def correct(self, frozen, p, x, y):
        pred = jnp.argmax(self.logits(frozen, p, x), axis=-1)
        return jnp.mean((pred == y).astype(F32), axis=-1)

    def pair_init(self, key):
        """The head ``init`` would draw from ``key``, with 2 classes."""
        return {"head": _head(jax.random.split(key)[0], self.cfg.d_model,
                              2)}

    def pair_logits(self, h, x):
        return self._mm(x, h["head"]["w"]) + h["head"]["b"]

    def pair_loss(self, h, x, y):
        return _xent(self.pair_logits(h, x), y)

    def pair_inputs(self, frozen, p0, clients):
        """Per-token final hidden states, bfloat16, of every device's
        sequences under the shared initial adapters: rows (N, n_max *
        seq_len, d), ``counts`` in tokens."""
        def one(x):
            return self.hidden(frozen, p0["adapters"], x).astype(
                self.matmul_dtype)

        feats = jax.lax.map(one, clients.x, batch_size=self.lane_chunk)
        n, s = feats.shape[:2]
        return PairInputs(feats.reshape((n, -1) + feats.shape[3:]),
                          clients.counts * self.seq_len)


def make_client(name: str = "cnn", *, num_hidden_layers: int = 0,
                seq_len: int = 1024):
    """``cnn``, or a hybrid (Zamba2) configuration registered in
    ``repro.configs`` by name, cut to ``num_hidden_layers`` layers when
    that is positive."""
    if name == "cnn":
        return CNNClient()
    cfg = get_config(name)
    if cfg.arch_type != "hybrid":
        raise ValueError(f"client {name!r}: only the CNN and hybrid "
                         f"(Zamba2) configurations run as clients")
    if num_hidden_layers > 0:
        cfg = dataclasses.replace(cfg, num_layers=num_hidden_layers)
    return ZambaClient(cfg=cfg, seq_len=seq_len)


def lane_map(fn, lane_chunk, *lanes):
    """``fn`` over the leading (device) axis of ``lanes``: one vmap, or
    with ``lane_chunk`` a map over chunks of that many lanes, so the
    working set is a chunk's."""
    if lane_chunk is None:
        return jax.vmap(fn)(*lanes)
    return jax.lax.map(lambda a: fn(*a), lanes, batch_size=lane_chunk)


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
        x=pad([d.x for d in devices], 0),
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


@functools.partial(jax.jit, static_argnames=("iters", "batch", "lr",
                                             "client"))
def train_sources(params_stack, clients: StackedClients, keys, *,
                  iters: int = 100, batch: int = 10, lr: float = 0.01,
                  client=None, frozen=None):
    """Local supervised training on each device's LABELED data, lane by
    lane (``client``: the model, default the paper's CNN; ``frozen``: its
    shared part, un-batched).

    Devices with no labeled data get a uniform dummy distribution over
    valid rows with y clamped to 0 — their output is discarded by the
    caller (they will be targets).
    """
    client = client or CNNClient()

    def loss_fn(p, x, y):
        return client.loss(frozen, p, x, y)

    def one(p, x, y, labeled, valid, key):
        sel = jnp.where(jnp.any(labeled), labeled.astype(jnp.float32),
                        valid.astype(jnp.float32))
        y_safe = jnp.maximum(y, 0)
        return _sgd_scan(p, x, y_safe, sel, key, iters=iters, batch=batch,
                         lr=lr, loss_fn=loss_fn)

    return lane_map(one, client.lane_chunk, params_stack, clients.x,
                    clients.y, clients.labeled, clients.valid, keys)


@functools.partial(jax.jit, static_argnames=("client",))
def empirical_errors(params_stack, clients: StackedClients, *, client=None,
                     frozen=None) -> jnp.ndarray:
    """eq (3) per device: unlabeled data counted as error 1."""
    client = client or CNNClient()

    def one(p, x, y, labeled, valid):
        right = client.correct(frozen, p, x, y)
        err = jnp.where(labeled, 1.0 - right, valid.astype(jnp.float32))
        return jnp.sum(err) / jnp.maximum(
            jnp.sum(valid.astype(jnp.float32)), 1.0)

    return lane_map(one, client.lane_chunk, params_stack, clients.x,
                    clients.y, clients.labeled, clients.valid)


@functools.partial(jax.jit, static_argnames=("client",))
def true_accuracies(params_stack, clients: StackedClients, *, client=None,
                    frozen=None) -> jnp.ndarray:
    """Ground-truth accuracy of each device's model on its own data."""
    client = client or CNNClient()

    def one(p, x, ty, valid):
        m = valid.astype(jnp.float32)
        return jnp.sum(client.correct(frozen, p, x, ty) * m) / \
            jnp.maximum(jnp.sum(m), 1.0)

    return lane_map(one, client.lane_chunk, params_stack, clients.x,
                    clients.true_y, clients.valid)


def init_client_params(n_devices: int, key, num_classes: int = 10,
                       shared_init: bool = True, client=None):
    """Stacked per-device parameters (``client``: the model, default the
    paper's CNN with ``num_classes`` outputs).  ``shared_init=True`` (the
    FL norm, and a precondition for meaningful parameter averaging at
    targets) broadcasts ONE initialization to every device."""
    if client is None:
        client = CNNClient(num_classes)
    if shared_init:
        p = client.init(key)
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (n_devices,) + a.shape), p)
    keys = jax.random.split(key, n_devices)
    return jax.vmap(client.init)(keys)
