"""Plain reference of the Zamba2 client: Zamba2-7B-Instruct
(https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json,
arXiv:2411.15242) tagging every token of a sequence with its topic, a
frozen backbone shared by all devices and per-device adapters and head;
local SGD, Algorithm 1 as a 2-class head over frozen features, the
alpha-mixture of the per-device leaves, and accuracies.

Straightforward ``jax.numpy`` with nothing of the program imported.
``dtype=float32`` computes every product at ``HIGHEST`` precision (under
``jax.default_matmul_precision("highest")`` too): the reference.
``dtype=bfloat16`` holds the per-device leaves, their updates and every
activation, the SSD state and its decay cumsum in bfloat16: the control,
the precision below the float32 the configuration states for them.

The model, as written here from the published description.  With ``e``
the embedding of the tokens and ``h`` the residual stream (``h = e`` at
first), layer i of ``SHAPE["layers"]`` is a Mamba2 layer,

    h = h + mamba_i(rmsnorm(h))

or, the j-th of ``SHAPE["hybrid_ids"]``, a hybrid layer,

    u = rmsnorm(concat(h, e))                         (2 d wide)
    a = attention(u)     q, k, v: 2d -> H x hd, rotary over the whole
                         head (theta 10,000), causal softmax of
                         q.k (hd / 2)^-1/2, o: H x hd -> d
    m = rmsnorm(a)
    [g | v] = m W_gate_up + (m D_j) U_j               adapter j, rank r
    t = (gelu(g) * v) W_down W_linear_j               exact (erf) GELU
    h = h + mamba_i(rmsnorm(h + t))

with the shared block j mod ``shared_blocks``; then a final RMSNorm and
the task head.  mamba_i: [z | x B C | dt] = x W_in; a causal depthwise
conv of width 4 with bias and SiLU over (x, B, C); per head hh of H
(head dim P, state N; B and C shared by the H / G heads of a group)

    dt_t = softplus(dt_t + dt_bias),  a_t = exp(-dt_t exp(A_log))
    S_t  = a_t S_{t-1} + dt_t x_t B_t^T,    y_t = S_t C_t + D x_t

computed here in blocks of ``chunk`` steps from the closed form
y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < u <= t} log a_u) dt_s x_s, the
state carried from block to block; then RMSNorm of y * silu(z) over
groups of d_inner / G channels, and W_out.  Attention is computed in
query blocks of ``ATTN_BLOCK``.  RMSNorms take eps 1e-5.

Departures from the published model, each the configuration's:
  - 12 of its 81 layers (``layers_block_type[0:12]``, hybrids at 6 and
    11, so each shared block is used once);
  - random weights from the run's seed, held in bfloat16 as the model is
    distributed (compute here in ``dtype``): matrices normal over the
    square root of their input width, norms and D ones, biases zero,
    embedding normal, A_log and dt_bias Mamba2's published init
    (A ~ U(1, 16); dt log-uniform in [0.001, 0.1], floor 1e-4); leaf
    ``name`` of layer l from ``fold_in(fold_in(key, crc32(name)), l)``;
  - the adapters random too, and the language-model head replaced by a
    linear head of ``NUM_CLASSES`` topics per token;
  - three choices config.json leaves open, taken from the transformers
    implementation: the attention scale (hd / 2)^-1/2, the gate as the
    first half of gate_up, rotary over the whole head.

This file is the client's side of the harness's contract
(``bench/harness/spec.py``).  ``init(key, NUM_CLASSES)`` returns the
per-device leaves ({"adapters": [{down, up}] x 2, "head": {w, b}}) and
keeps, in this module, the frozen backbone drawn from the same key and
those initial adapters (Algorithm 1's features use them);
``init(key, DOMAIN_CLASSES)`` returns Algorithm 1's hypothesis, the
2-class head ({"head": {w, b}}) that ``init`` would draw from ``key``.
"""
from __future__ import annotations

import functools
import json
import math
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
NUM_CLASSES = 10
DOMAIN_CLASSES = 2
#: the frozen backbone's stream off the init key
FROZEN_STREAM = 2 ** 22
#: query rows of one attention block
ATTN_BLOCK = 256

#: the configuration the cell runs, the one source of its widths
CONFIG_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           os.pardir, "configs", "zamba2-7b-stlf-n8.json")


def published(path: str = CONFIG_FILE) -> dict:
    """The shape this file computes, read from the configuration's
    config.json keys (``num_hidden_layers`` as the cell cuts it)."""
    with open(path) as f:
        c = json.load(f)
    layers = c["num_hidden_layers"]
    return dict(vocab=c["vocab_size"], d=c["hidden_size"], layers=layers,
                hybrid_ids=tuple(i for i in c["hybrid_layer_ids"]
                                 if i < layers),
                shared_blocks=c["num_mem_blocks"],
                heads=c["num_attention_heads"],
                head_dim=c["attention_head_dim"], ff=c["ffn_hidden_size"],
                rank=c["adapter_rank"], expand=c["mamba_expand"],
                ssm_head_dim=c["mamba_headdim"], state=c["mamba_d_state"],
                groups=c["mamba_ngroups"], conv=c["mamba_d_conv"],
                chunk=c["chunk_size"], rope_theta=float(c["rope_theta"]),
                eps=c["rms_norm_eps"])


PUBLISHED = published()
SHAPE = dict(PUBLISHED)


def configure(**shape):
    """Replace ``SHAPE`` (the CPU tests run a small model of the same
    structure); no argument restores the published one."""
    SHAPE.clear()
    SHAPE.update(PUBLISHED)
    SHAPE.update(shape)
    _FROZEN.clear()


def _dims():
    s = SHAPE
    d_inner = s["expand"] * s["d"]
    heads = d_inner // s["ssm_head_dim"]
    conv_ch = d_inner + 2 * s["groups"] * s["state"]
    return d_inner, heads, conv_ch


# ---------------------------------------------------------------- weights
def _leaf(key, name, shape, layer=None):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    last = name.rsplit("/", 1)[-1]
    if last in ("ln", "ln1", "ln2", "ln_f", "norm_scale", "d_skip"):
        return jnp.ones(shape, F32)
    if last == "conv_b":
        return jnp.zeros(shape, F32)
    if last == "a_log":
        return jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
    if last == "dt_bias":
        u = jax.random.uniform(k, shape, F32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(0.001))
                     + math.log(0.001))
        dt = jnp.maximum(dt, 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    if last == "embedding":
        return jax.random.normal(k, shape, F32)
    fan_in = shape[0] * shape[1] if last == "wo" else shape[0]
    return jax.random.normal(k, shape, F32) / math.sqrt(fan_in)


def _backbone(key):
    """{"embedding", "ln_f", "layers": [per layer], "shared": [per
    block], "linear": [per hybrid]}, bfloat16."""
    s = SHAPE
    d, bf = s["d"], jnp.bfloat16
    d_inner, heads, conv_ch = _dims()
    mix = {"in_proj": (d, d_inner + conv_ch + heads),
           "conv_w": (s["conv"], conv_ch), "conv_b": (conv_ch,),
           "a_log": (heads,), "dt_bias": (heads,), "d_skip": (heads,),
           "norm_scale": (d_inner,), "out_proj": (d_inner, d)}
    blk = {"ln1": (2 * d,), "attn/wq": (2 * d, s["heads"], s["head_dim"]),
           "attn/wk": (2 * d, s["heads"], s["head_dim"]),
           "attn/wv": (2 * d, s["heads"], s["head_dim"]),
           "attn/wo": (s["heads"], s["head_dim"], d), "ln2": (d,),
           "mlp/gate_up": (d, 2 * s["ff"]), "mlp/down": (s["ff"], d)}
    out = {"embedding": _leaf(key, "embedding", (s["vocab"], d)).astype(bf),
           "ln_f": _leaf(key, "ln_f", (d,)).astype(bf)}
    out["layers"] = [
        {"ln": _leaf(key, "layers/ln", (d,), i).astype(bf),
         **{n: _leaf(key, "layers/mix/" + n, sh, i).astype(bf)
            for n, sh in mix.items()}}
        for i in range(s["layers"])]
    out["shared"] = [{n: _leaf(key, "shared/" + n, sh, b).astype(bf)
                      for n, sh in blk.items()}
                     for b in range(s["shared_blocks"])]
    out["linear"] = [_leaf(key, "linear", (d, d), j).astype(bf)
                     for j in range(len(s["hybrid_ids"]))]
    return out


def _head(key, classes):
    d = SHAPE["d"]
    return {"w": jax.random.normal(key, (d, classes), F32) / math.sqrt(d),
            "b": jnp.zeros((classes,), F32)}


_FROZEN: dict = {}


def _key_id(key):
    return tuple(np.asarray(jax.random.key_data(key)).ravel().tolist())


def init(key, num_classes: int) -> dict:
    k_head, k_ad = jax.random.split(key)
    if num_classes == DOMAIN_CLASSES:
        return {"head": _head(k_head, DOMAIN_CLASSES)}
    s = SHAPE
    adapters = [{
        "down": _leaf(k_ad, f"adapters/{j}/down", (s["d"], s["rank"])),
        "up": _leaf(k_ad, f"adapters/{j}/up", (s["rank"], 2 * s["ff"]))}
        for j in range(len(s["hybrid_ids"]))]
    if _FROZEN.get("key") != _key_id(key):
        _FROZEN.clear()                         # one backbone at a time
        _FROZEN.update(key=_key_id(key), adapters0=adapters,
                       backbone=_backbone(jax.random.fold_in(
                           key, FROZEN_STREAM)))
    return {"adapters": adapters, "head": _head(k_head, num_classes)}


def cast(tree, dtype):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), tree)


def stack(devices, dtype, n_max=None) -> dict:
    """Token ids and labels stay integers whatever ``dtype`` is."""
    if n_max is None:
        n_max = max(len(d.labels) for d in devices)

    def pad(arrs, fill, dt):
        out = np.full((len(arrs), n_max) + arrs[0].shape[1:], fill, dt)
        for i, a in enumerate(arrs):
            out[i, :len(a)] = a
        return jnp.asarray(out)

    return {"x": pad([d.x for d in devices], 0, np.int32),
            "y": pad([d.labels for d in devices], -1, np.int32),
            "labeled": pad([d.labeled_mask for d in devices], False, bool),
            "valid": pad([np.ones(len(d.labels), bool) for d in devices],
                         False, bool),
            "true_y": pad([d.true_labels for d in devices], -1, np.int32)}


# ---------------------------------------------------------------- forward
def _prec(dtype):
    return HIGHEST if jnp.dtype(dtype) == F32 else None


def _mm(eq, a, b, dtype):
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      precision=_prec(dtype))


def _rms(x, w, groups=1):
    sh = x.shape
    x = x.reshape(sh[:-1] + (groups, sh[-1] // groups))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + SHAPE["eps"])
    return x.reshape(sh) * w.astype(x.dtype)


def _rope(x):
    """x: (B, S, H, hd); rotate the halves of the whole head."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = 1.0 / SHAPE["rope_theta"] ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _attention(blk, u, dtype):
    s = SHAPE
    q = _rope(_mm("bsd,dhk->bshk", u, blk["attn/wq"], dtype))
    k = _rope(_mm("bsd,dhk->bshk", u, blk["attn/wk"], dtype))
    v = _mm("bsd,dhk->bshk", u, blk["attn/wv"], dtype)
    n = u.shape[1]
    scale = (s["head_dim"] / 2) ** -0.5
    outs = []
    for q0 in range(0, n, ATTN_BLOCK):
        qb = q[:, q0:q0 + ATTN_BLOCK]
        sc = _mm("bqhk,bshk->bhqs", qb, k, dtype) * scale
        rows = q0 + jnp.arange(qb.shape[1])[:, None]
        sc = jnp.where(jnp.arange(n)[None, :] <= rows, sc, -jnp.inf)
        outs.append(_mm("bhqs,bshk->bqhk", jax.nn.softmax(sc, -1), v,
                        dtype))
    return _mm("bshk,hkd->bsd", jnp.concatenate(outs, 1), blk["attn/wo"],
               dtype)


def _ssd(x, dt, a, bm, cm, dtype):
    """x: (B, L, H, P); dt: (B, L, H); a: (H,); bm, cm: (B, L, G, N).
    The closed form in blocks of ``chunk`` steps, heads expanded."""
    b, n, h, p = x.shape
    g = bm.shape[2]
    bh = jnp.repeat(bm, h // g, axis=2)                # (B, L, H, N)
    ch = jnp.repeat(cm, h // g, axis=2)
    q = SHAPE["chunk"]
    state = jnp.zeros((b, h, p, bm.shape[-1]), dtype)
    ys = []
    for t0 in range(0, n, q):
        xs, dts = x[:, t0:t0 + q], dt[:, t0:t0 + q]
        bs, cs = bh[:, t0:t0 + q], ch[:, t0:t0 + q]
        la = jnp.cumsum(dts * a, axis=1)               # (B, Q, H)
        m = la.shape[1]
        # exp(sum_{s < u <= t} log a_u) for s <= t
        diff = la[:, :, None] - la[:, None, :]         # (B, t, s, H)
        causal = (jnp.arange(m)[:, None] >= jnp.arange(m)[None, :])
        w = jnp.where(causal[None, :, :, None], jnp.exp(
            jnp.where(causal[None, :, :, None], diff, 0.0)), 0.0)
        cb = _mm("bthn,bshn->btsh", cs, bs, dtype)
        y = _mm("btsh,bshp->bthp", cb * w, xs * dts[..., None], dtype)
        # the carried state, decayed to step t, read by C_t
        y = y + _mm("bthn,bhpn->bthp", cs, state, dtype) * \
            jnp.exp(la)[..., None]
        ys.append(y)
        # state at the block's end
        tail = jnp.exp(la[:, -1:] - la)                # (B, Q, H)
        state = state * jnp.exp(la[:, -1])[..., None, None] + _mm(
            "bshp,bshn->bhpn", xs * (dts * tail)[..., None], bs, dtype)
    return jnp.concatenate(ys, 1)


def _mamba(lp, x, dtype):
    s = SHAPE
    b, n, _ = x.shape
    d_inner, heads, conv_ch = _dims()
    gn = s["groups"] * s["state"]
    zxd = _mm("bsd,dp->bsp", x, lp["in_proj"], dtype)
    z, xbc, dt = zxd[..., :d_inner], zxd[..., d_inner:d_inner + conv_ch], \
        zxd[..., d_inner + conv_ch:]
    w = lp["conv_w"].astype(dtype)
    xp = jnp.pad(xbc, ((0, 0), (s["conv"] - 1, 0), (0, 0)))
    xbc = sum(xp[:, i:i + n] * w[i] for i in range(s["conv"]))
    xbc = jax.nn.silu(xbc + lp["conv_b"].astype(dtype))
    xin = xbc[..., :d_inner].reshape(b, n, heads, s["ssm_head_dim"])
    bm = xbc[..., d_inner:d_inner + gn].reshape(b, n, s["groups"],
                                                 s["state"])
    cm = xbc[..., d_inner + gn:].reshape(b, n, s["groups"], s["state"])
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(dtype))
    a = -jnp.exp(lp["a_log"].astype(dtype))
    y = _ssd(xin, dt, a, bm, cm, dtype) + \
        xin * lp["d_skip"].astype(dtype)[:, None]
    y = y.reshape(b, n, d_inner) * jax.nn.silu(z)
    return _mm("bsp,pd->bsd", _rms(y, lp["norm_scale"], s["groups"]),
               lp["out_proj"], dtype)


def _hidden(bb, adapters, tokens, dtype, remat=False):
    """Final-normed hidden states (B, S, d) of tokens (B, S)."""
    s = SHAPE
    e = bb["embedding"][tokens].astype(dtype)

    def mamba_layer(h, lp):
        return h + _mamba(lp, _rms(h, lp["ln"]), dtype)

    def hybrid_layer(h, lp, blk, lin, ad):
        u = _rms(jnp.concatenate([h, e], -1), blk["ln1"])
        m = _rms(_attention(blk, u, dtype), blk["ln2"])
        gu = _mm("bsd,df->bsf", m, blk["mlp/gate_up"], dtype) + _mm(
            "bsr,rf->bsf", _mm("bsd,dr->bsr", m, ad["down"], dtype),
            ad["up"], dtype)
        g, v = jnp.split(gu, 2, -1)
        t = _mm("bsf,fd->bsd", jax.nn.gelu(g, approximate=False) * v,
                blk["mlp/down"], dtype)
        t = _mm("bsd,de->bse", t, lin, dtype)
        return h + _mamba(lp, _rms(h + t, lp["ln"]), dtype)

    if remat:
        mamba_layer = jax.checkpoint(mamba_layer)
        hybrid_layer = jax.checkpoint(hybrid_layer)
    h = e
    for i in range(s["layers"]):
        lp = bb["layers"][i]
        if i in s["hybrid_ids"]:
            j = s["hybrid_ids"].index(i)
            h = hybrid_layer(h, lp, bb["shared"][j % s["shared_blocks"]],
                             bb["linear"][j], adapters[j])
        else:
            h = mamba_layer(h, lp)
    return _rms(h, bb["ln_f"])


def _logits(bb, p, tokens, dtype, remat=False):
    h = _hidden(bb, p["adapters"], tokens, dtype, remat)
    return _mm("bsd,dc->bsc", h, p["head"]["w"], dtype) + p["head"]["b"]


def _xent(logits, y):
    picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


# ------------------------------------------------------------ local SGD
@functools.partial(jax.jit, static_argnames=("iters", "batch", "lr",
                                             "dtype"))
def _lane_train(bb, p, x, y, logits_w, key, *, iters, batch, lr, dtype):
    def loss(p, xb, yb):
        return _xent(_logits(bb, p, xb, dtype, remat=True), yb)

    def step(p, k):
        idx = jax.random.categorical(k, logits_w, shape=(batch,))
        g = jax.grad(loss)(p, x[idx], jnp.maximum(y, 0)[idx])
        return jax.tree_util.tree_map(
            lambda a, b: a - jnp.asarray(lr, dtype) * b, p, g), None

    return jax.lax.scan(step, p, jax.random.split(key, iters))[0]


def _lane(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def train(params, x, y, labeled, valid, lane_keys, update, *, iters: int,
          batch: int, lr: float, dtype):
    """Lanes in ``update``: ``iters`` SGD steps of ``batch`` sequences
    drawn uniformly from its labeled sequences (all valid ones for a lane
    with none); the other lanes keep their parameters."""
    bb = _FROZEN["backbone"]
    upd = np.asarray(update)
    lanes = []
    with jax.default_matmul_precision("highest"):
        for i in range(len(upd)):
            p = _lane(params, i)
            if upd[i]:
                lab, val = labeled[i], valid[i]
                sel = jnp.where(jnp.any(lab), lab, val)
                p = _lane_train(bb, p, x[i], y[i],
                                jnp.where(sel, 0.0, -1e30), lane_keys[i],
                                iters=iters, batch=batch, lr=lr,
                                dtype=dtype)
            lanes.append(p)
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *lanes)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _lane_acc(bb, p, x, ty, val, dtype):
    hit = jnp.argmax(_logits(bb, p, x, dtype), -1) == ty
    per_seq = jnp.mean(hit.astype(F32), axis=-1)
    return jnp.sum(per_seq * val) / jnp.maximum(jnp.sum(val), 1)


def accuracies(params, x, true_y, valid, *, dtype):
    """Per device: the mean over its valid sequences of the share of
    tokens tagged right."""
    bb = _FROZEN["backbone"]
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_lane_acc(bb, _lane(params, i), x[i], true_y[i],
                                    valid[i], dtype)
                          for i in range(x.shape[0])])


# ------------------------------------------------------- Algorithm 1
@functools.partial(jax.jit, static_argnames=("dtype",))
def _features(bb, adapters, x, dtype):
    """(n, S) tokens -> (n * S, d) hidden states, the rows of Algorithm 1."""
    h = _hidden(bb, adapters, x, dtype)
    return h.reshape(-1, h.shape[-1])


@functools.partial(jax.jit, static_argnames=("tau", "T", "rows", "lr",
                                             "dtype"))
def _pair_heads(h0, feats, ui, ni, uj, nj, keys, *, tau, T, rows, lr,
                dtype):
    def logits(h, f):
        return _mm("bd,dc->bc", f, h["head"]["w"], dtype) + h["head"]["b"]

    def loss(h, f, y):
        return _xent(logits(h, f), y)

    def sgd(h, g):
        return jax.tree_util.tree_map(
            lambda a, b: a - jnp.asarray(lr, dtype) * b, h, g)

    def lane(h, a, na, b, nb, key):
        fa, fb = feats[a], feats[b]

        def step(carry, inp):
            ha, hb = carry
            t, kt = inp
            ka, kb = jax.random.split(kt)
            ia = jax.random.randint(ka, (rows,), 0, na)
            ib = jax.random.randint(kb, (rows,), 0, nb)
            ha = sgd(ha, jax.grad(loss)(ha, fa[ia],
                                        jnp.zeros(rows, jnp.int32)))
            hb = sgd(hb, jax.grad(loss)(hb, fb[ib],
                                        jnp.ones(rows, jnp.int32)))
            avg = jax.tree_util.tree_map(lambda u, v: (u + v) / 2, ha, hb)
            sync = (t + 1) % T == 0
            pick = lambda u, m: jnp.where(sync, m, u)       # noqa: E731
            return (jax.tree_util.tree_map(pick, ha, avg),
                    jax.tree_util.tree_map(pick, hb, avg)), None

        (ha, hb), _ = jax.lax.scan(step, (h, h), (
            jnp.arange(tau * T), jax.random.split(key, tau * T)))
        hbar = jax.tree_util.tree_map(lambda u, v: (u + v) / 2, ha, hb)
        row = jnp.arange(fa.shape[0])

        def wrong(f, nd, label):
            pred = jnp.argmax(logits(hbar, f), axis=-1)
            return jnp.sum((row < nd) & (pred != label)), \
                jnp.sum(row < nd)

        wa, ca = wrong(fa, na, 0)
        wb, cb = wrong(fb, nb, 1)
        eps = (wa + wb) / jnp.maximum(ca + cb, 1)
        return jnp.clip(2.0 * (1.0 - 2.0 * eps), 0.0, 2.0)

    return jax.vmap(lane)(h0, ui, ni, uj, nj, keys)


def pair_divergence(h0, xi, ni, xj, nj, keys, *, tau: int, T: int,
                    batch: int, lr: float, dtype):
    """Per lane: the rows are the per-token hidden states of device i's
    and device j's sequences under the frozen backbone and the initial
    adapters (device i's labeled 0, j's 1); two copies of the 2-class
    head ``h0`` take ``tau * T`` SGD steps each on their own device's
    rows, a step on ``batch`` sequences' worth of rows (``batch * S``
    token rows drawn uniformly), averaged every ``T`` steps; the
    average's error eps on the union gives d = clip(2 (1 - 2 eps), 0,
    2).  The features are computed once per distinct device."""
    bb, ad0 = _FROZEN["backbone"], _FROZEN["adapters0"]
    seq = xi.shape[-1]
    uniq, ids = {}, []
    for arr in (np.asarray(xi), np.asarray(xj)):
        ids.append([uniq.setdefault(a.tobytes(), (len(uniq), a))[0]
                    for a in arr])
    with jax.default_matmul_precision("highest"):
        feats = jnp.stack([_features(bb, ad0, jnp.asarray(a), dtype)
                           for _, a in sorted(uniq.values(),
                                              key=lambda v: v[0])])
        return _pair_heads(h0, feats, jnp.asarray(ids[0]),
                           jnp.asarray(ni) * seq, jnp.asarray(ids[1]),
                           jnp.asarray(nj) * seq, keys, tau=tau, T=T,
                           rows=batch * seq, lr=lr, dtype=dtype)


# ------------------------------------------------------------ transfer
def mix_targets(params, alpha, psi, dtype):
    """Targets (psi = 1) take sum_s alpha[s, t] params[s]; every other
    device keeps its own.  float32: the sum in float64 on the host."""
    tgt = np.asarray(psi) == 1.0

    def mix(leaf):
        n = leaf.shape[0]
        if jnp.dtype(dtype) == F32:
            th = np.asarray(leaf, np.float64).reshape(n, -1)
            mixed = (np.asarray(alpha, np.float64).T @ th).astype(np.float32)
            own = np.asarray(leaf).reshape(n, -1)
            return jnp.asarray(np.where(tgt[:, None], mixed, own)
                               .reshape(leaf.shape))
        th = leaf.reshape(n, -1)
        mixed = jnp.einsum("st,sv->tv", jnp.asarray(alpha, dtype), th,
                           preferred_element_type=dtype)
        return jnp.where(jnp.asarray(tgt)[:, None], mixed,
                         th).reshape(leaf.shape)

    return jax.tree_util.tree_map(mix, params)


# --------------------------------------------------------------- costs
def forward_flops(seq: int, num_classes: int = NUM_CLASSES):
    """(all, grad_path, trainable) operations of one forward of a
    sequence of ``seq`` tokens, counting 2 per multiply-add of every
    product (convolution included; norms and activations not):

      all         the whole forward
      grad_path   the products a training step's backward runs again for
                  the activation gradient: those at and after the first
                  hybrid layer; a product of two activations counts twice
                  there (both operands take a gradient)
      trainable   the products with a trained weight (adapters, head),
                  whose weight gradient the backward adds
    """
    s = SHAPE
    d, q = s["d"], s["chunk"]
    d_inner, heads, conv_ch = _dims()
    hp, n, g = s["ssm_head_dim"], s["state"], s["groups"]
    hh = s["heads"] * s["head_dim"]
    qq = min(q, seq)
    mamba_w = 2 * seq * (d * (d_inner + conv_ch + heads) + d_inner * d
                         + s["conv"] * conv_ch)
    ssd_act = 2 * seq * (qq * g * n + qq * heads * hp + 2 * heads * hp * n)
    attn_w = 2 * seq * (3 * 2 * d * hh + hh * d)
    attn_act = 2 * 2 * seq * seq * hh
    mlp_w = 2 * seq * (d * 2 * s["ff"] + s["ff"] * d + d * d)
    adapter = 2 * seq * (d * s["rank"] + s["rank"] * 2 * s["ff"])
    head = 2 * seq * d * num_classes
    first = min(s["hybrid_ids"])
    n_h = len(s["hybrid_ids"])
    layers_after = s["layers"] - first
    total = s["layers"] * (mamba_w + ssd_act) + \
        n_h * (attn_w + attn_act + mlp_w + adapter) + head
    grad_path = layers_after * (mamba_w + 2 * ssd_act) + \
        n_h * (attn_w + 2 * attn_act + mlp_w + adapter) + head
    return total, grad_path, n_h * adapter + head


def costs(sim: dict) -> dict:
    """Operations of the client's work under the run's settings ``sim``:

      train_sample    one training sequence: the forward, the backward's
                      activation gradient along the path to the first
                      adapter, and the adapters' and head's weight
                      gradients
      eval_sample     one measurement forward of a sequence
      pair            one Algorithm-1 estimate: 2 heads * tau * T steps
                      * batch * seq_len token rows, three head products
                      each, then the head over each device's token rows
                      (the features are computed once, not per pair)
      transfer_width  V, the per-device leaves the combine moves
    """
    seq = sim["seq_len"]
    total, grad_path, trainable = forward_flops(seq)
    head2 = 2 * SHAPE["d"] * DOMAIN_CLASSES
    steps = 2 * sim["div_tau"] * sim["div_T"] * sim["batch"] * seq
    s = SHAPE
    return {"train_sample": total + grad_path + trainable,
            "eval_sample": total,
            "pair": (3 * steps + 2 * sim["samples_per_device"] * seq)
            * head2,
            "transfer_width": len(s["hybrid_ids"]) * (
                s["d"] * s["rank"] + s["rank"] * 2 * s["ff"])
            + (s["d"] + 1) * NUM_CLASSES}
