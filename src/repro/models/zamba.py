"""Zamba2 (arXiv:2411.15242; Zyphra/Zamba2-7B-Instruct's config.json): a
Mamba2 backbone with ``num_shared_blocks`` shared transformer blocks used
in turn at the hybrid layers (``cfg.hybrid.layer_ids``).

With ``e`` the embedding output and ``h`` the residual stream, the j-th
hybrid layer i computes

    u = concat(h, e)                                  (2 d wide)
    a = attn_{j mod nb}(rmsnorm(u))                   q, k, v: 2d -> H hd,
                                                      rope, o: H hd -> d
    t = mlp_{j mod nb}(rmsnorm(a), adapter_j)         gelu(g) * u with
                                                      [g | u] = x W + x D_j U_j
    t = t @ linear_j
    h = h + mamba_i(rmsnorm(h + t))

and every other layer i computes ``h = h + mamba_i(rmsnorm(h))``; a final
RMSNorm closes the stack.  The residual is taken before ``t`` is added,
as in the transformers implementation.  Attention scores are scaled by
(hd / 2)^-1/2 and rotary embeddings rotate the whole head, as the
transformers implementation does; there is no sliding window.

One implementation serves two callers: ``ZambaModel`` (the LM API: loss,
prefill, decode) and the federated client (``repro.fl.client``), which
holds the backbone frozen in bfloat16 and passes each device's adapters
in as operands.  Inside the forward, ``jax.named_scope``s name the Mamba2
blocks ``zamba2_mamba`` and the shared blocks ``zamba2_shared``.

Weights are drawn leaf by leaf (``init_backbone``, ``init_adapters``):
leaf ``name`` (its path, ``/``-joined) of layer ``l`` takes the key
``fold_in(fold_in(key, crc32(name)), l)``; matrices are normal over the
square root of their input width, norms and D ones, biases zero, and
A_log and dt_bias take Mamba2's published init.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

from repro.nn import attention as attn
from repro.nn import mamba
from repro.nn import mlp as mlp_lib
from repro.nn import param as P
from repro.nn.layers import ShardCtx, NO_SHARD, rmsnorm, rmsnorm_spec, \
    embedding_spec, embed, unembed
from repro.models.common import (LMBase, stack_specs, slice_tree,
                                 take_layer, chunked_softmax_xent)

F32 = jnp.float32
#: leaves stacked over layers (``layers``), shared blocks (``shared``) or
#: hybrid invocations (``linear``): drawn per index
STACKED = ("layers", "shared", "linear")


def hybrid_ids(cfg):
    return tuple(i for i in cfg.hybrid.layer_ids if i < cfg.num_layers)


def backbone_specs(cfg):
    """The frozen part: embedding, Mamba2 layers, shared blocks, the
    hybrid layers' ``linear`` and the final norm."""
    d = cfg.d_model
    shared = {
        "ln1": rmsnorm_spec(2 * d),
        "attn": attn.attention_specs(2 * d, cfg.num_heads, cfg.num_kv_heads,
                                     cfg.resolved_head_dim(), d_out=d),
        "ln2": rmsnorm_spec(d),
        "mlp": mlp_lib.gated_gelu_specs(d, cfg.d_ff),
    }
    return {
        "embedding": embedding_spec(cfg.vocab_size, d),
        "layers": stack_specs({"ln": rmsnorm_spec(d),
                               "mix": mamba.mamba_specs(cfg)},
                              cfg.num_layers),
        "shared": stack_specs(shared, cfg.hybrid.num_shared_blocks),
        "linear": P.ParamSpec((len(hybrid_ids(cfg)), d, d),
                              ("layers", "embed", None)),
        "ln_f": rmsnorm_spec(d),
    }


def adapter_specs(cfg):
    """One gate_up adapter per hybrid invocation."""
    return [mlp_lib.adapter_specs(cfg.d_model, cfg.hybrid.adapter_rank,
                                  2 * cfg.d_ff) for _ in hybrid_ids(cfg)]


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()))


def _draw(cfg, name: str, shape, key):
    """One leaf (one layer's slice of a stacked leaf), float32."""
    last = name.rsplit("/", 1)[-1]
    if last in ("ln", "ln1", "ln2", "ln_f", "norm_scale", "d_skip"):
        return jnp.ones(shape, F32)
    if last == "conv_b":
        return jnp.zeros(shape, F32)
    if last == "a_log":
        return mamba.init_a_log(key, shape[0])
    if last == "dt_bias":
        return mamba.init_dt_bias(key, shape[0])
    if last == "embedding":
        return jax.random.normal(key, shape, F32)
    fan_in = math.prod(shape[:2]) if last == "wo" else shape[0]
    return jax.random.normal(key, shape, F32) / math.sqrt(fan_in)


def _init(cfg, specs, key, dtype):
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs,
                                                         is_leaf=P.is_spec)
    out = []
    for path, spec in flat:
        name = jax.tree_util.keystr(path, simple=True, separator="/")
        k = _leaf_key(key, name)
        if name.split("/")[0] in STACKED:
            leaf = jnp.stack([_draw(cfg, name, spec.shape[1:],
                                    jax.random.fold_in(k, i)).astype(dtype)
                              for i in range(spec.shape[0])]) \
                if spec.shape[0] else jnp.zeros(spec.shape, dtype)
        else:
            leaf = _draw(cfg, name, spec.shape, k).astype(dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def init_backbone(cfg, key, dtype=jnp.bfloat16):
    return _init(cfg, backbone_specs(cfg), key, dtype)


def init_adapters(cfg, key):
    """The adapters, float32, named ``adapters/<j>/down`` and ``up``."""
    return _init(cfg, {"adapters": adapter_specs(cfg)}, key,
                 F32)["adapters"]


def _segments(cfg):
    """[("mamba", first, end) | ("hybrid", layer, invocation)] in order."""
    segs, start = [], 0
    for j, i in enumerate(hybrid_ids(cfg)):
        if i > start:
            segs.append(("mamba", start, i))
        segs.append(("hybrid", i, j))
        start = i + 1
    if start < cfg.num_layers:
        segs.append(("mamba", start, cfg.num_layers))
    return segs


def _mamba_layer(cfg, lp, h, t=None, state=None, *, decode=False,
                 dtype=jnp.bfloat16):
    x = h if t is None else h + t
    hn = rmsnorm(x, lp["ln"], cfg.norm_eps)
    with jax.named_scope("zamba2_mamba"):
        if decode:
            m, st = mamba.mamba_decode(lp["mix"], hn, cfg, state=state,
                                       dtype=dtype)
        else:
            m, st = mamba.mamba_block(lp["mix"], hn, cfg, state=state,
                                      dtype=dtype)
    return h + m, st


def _shared(cfg, sp, lin, h, e, adapter, positions, *, dtype, kv=None,
            pos=None):
    """The hybrid layer's transformer output t (before the Mamba2 block),
    float32; with ``kv`` one decode step, returning the new cache too."""
    hd = cfg.resolved_head_dim()
    kw = dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
              head_dim=hd, rope_theta=cfg.rope_theta, dtype=dtype,
              scale=(hd / 2) ** -0.5)
    with jax.named_scope("zamba2_shared"):
        u = rmsnorm(jnp.concatenate([h, e], axis=-1), sp["ln1"],
                    cfg.norm_eps).astype(dtype)
        if kv is None:
            a = attn.attend(sp["attn"], u, positions, causal=True, **kw)
        else:
            a, kv = attn.decode_attend(sp["attn"], u, kv, pos, **kw)
        t = mlp_lib.gated_gelu(sp["mlp"], rmsnorm(a.astype(F32), sp["ln2"],
                                                  cfg.norm_eps),
                               adapter, dtype)
        t = jnp.einsum("bsd,de->bse", t.astype(dtype), lin.astype(dtype),
                       preferred_element_type=F32)
    return t, kv


def backbone(cfg, params, adapters, tokens, *, dtype=jnp.bfloat16,
             remat: bool = False):
    """Final-normed hidden states (B, S, d) float32 of ``tokens`` (B, S);
    ``params`` holds the backbone's leaves, ``adapters`` one adapter per
    hybrid invocation.  ``remat`` recomputes each layer in the backward."""
    nb = cfg.hybrid.num_shared_blocks
    e = embed(tokens, params["embedding"], dtype).astype(F32)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def run(h, lps):
        body = lambda h, lp: (_mamba_layer(cfg, lp, h, dtype=dtype)[0],  # noqa
                              None)
        return jax.lax.scan(jax.checkpoint(body) if remat else body, h,
                            lps)[0]

    def hybrid(h, sp, lin, lp, adapter):
        t, _ = _shared(cfg, sp, lin, h, e, adapter, positions, dtype=dtype)
        return _mamba_layer(cfg, lp, h, t, dtype=dtype)[0]

    if remat:
        hybrid = jax.checkpoint(hybrid)
    h = e
    for kind, a, z in _segments(cfg):
        if kind == "mamba":
            h = run(h, slice_tree(params["layers"], a, z))
        else:
            h = hybrid(h, take_layer(params["shared"], z % nb),
                       params["linear"][z], take_layer(params["layers"], a),
                       adapters[z])
    return rmsnorm(h, params["ln_f"], cfg.norm_eps)


def backbone_decode(cfg, params, adapters, token, cache, pos, *,
                    dtype=jnp.bfloat16):
    """One token (B, 1) at positions ``pos`` (B,) through the cache
    {"mamba": (conv (L, ...), ssm (L, ...)), "kv": {k, v: (n_hybrid,
    ...)}}.  Returns (final-normed hidden (B, 1, d), new cache)."""
    nb = cfg.hybrid.num_shared_blocks
    e = embed(token, params["embedding"], dtype).astype(F32)
    conv, ssm = cache["mamba"]
    new_conv, new_ssm, new_kv = [], [], []

    def body(h, xs):
        lp, st = xs
        return _mamba_layer(cfg, lp, h, state=st, decode=True, dtype=dtype)

    h = e
    for kind, a, z in _segments(cfg):
        if kind == "mamba":
            h, (c, s) = jax.lax.scan(
                body, h, (slice_tree(params["layers"], a, z),
                          (conv[a:z], ssm[a:z])))
        else:
            t, kv = _shared(cfg, take_layer(params["shared"], z % nb),
                            params["linear"][z], h, e, adapters[z], None,
                            dtype=dtype, kv=take_layer(cache["kv"], z),
                            pos=pos)
            h, (c, s) = _mamba_layer(cfg, take_layer(params["layers"], a),
                                     h, t, state=(conv[a], ssm[a]),
                                     decode=True, dtype=dtype)
            c, s = c[None], s[None]
            new_kv.append(kv)
        new_conv.append(c)
        new_ssm.append(s)
    kv = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *new_kv) \
        if new_kv else cache["kv"]
    return rmsnorm(h, params["ln_f"], cfg.norm_eps), {
        "mamba": (jnp.concatenate(new_conv), jnp.concatenate(new_ssm)),
        "kv": kv}


class ZambaModel(LMBase):
    """The LM: the backbone in ``cfg.param_dtype`` with its adapters and
    an untied unembedding."""

    def param_specs(self):
        cfg = self.cfg
        return {**backbone_specs(cfg), "adapters": adapter_specs(cfg),
                "unembed": P.ParamSpec((cfg.vocab_size, cfg.d_model),
                                       ("vocab", "embed"), init="embed",
                                       scale=0.02)}

    def init(self, key):
        cfg = self.cfg
        out = init_backbone(cfg, key, jnp.dtype(cfg.param_dtype))
        out["adapters"] = init_adapters(cfg, jax.random.fold_in(key, 1))
        out["unembed"] = 0.02 * jax.random.normal(
            jax.random.fold_in(key, 2), (cfg.vocab_size, cfg.d_model), F32)
        return out

    def _hidden(self, params, tokens):
        cfg = self.cfg
        return backbone(cfg, params, params["adapters"], tokens,
                        dtype=jnp.dtype(cfg.dtype), remat=cfg.remat)

    def loss(self, params, batch, ctx: ShardCtx = NO_SHARD):
        h = self._hidden(params, batch["tokens"])
        ce = chunked_softmax_xent(h, params["unembed"], batch["labels"],
                                  ctx=ctx)
        return ce, {"ce": ce, "aux": jnp.zeros((), jnp.float32)}

    def prefill(self, params, batch, ctx: ShardCtx = NO_SHARD):
        h = self._hidden(params, batch["tokens"])
        logits = unembed(h[:, -1:], params["unembed"])
        return ctx.constrain(logits, "batch", None, "vocab")

    # --------------------------------------------------------------- decode
    def cache_specs(self, batch: int, max_len: int):
        cfg = self.cfg
        mstate = mamba.mamba_state_specs(batch, cfg, cfg.dtype)
        mstate = tuple(stack_specs(s, cfg.num_layers) for s in mstate)
        kv = stack_specs(attn.cache_specs(batch, max_len, cfg.num_kv_heads,
                                          cfg.resolved_head_dim(), cfg.dtype),
                         len(hybrid_ids(cfg)))
        return {"mamba": mstate, "kv": kv}

    def init_cache(self, batch: int, max_len: int):
        return P.materialize(self.cache_specs(batch, max_len),
                             jax.random.PRNGKey(0))

    def decode_step(self, params, cache, batch, ctx: ShardCtx = NO_SHARD,
                    window=None):
        cfg = self.cfg
        h, cache = backbone_decode(cfg, params, params["adapters"],
                                   batch["token"], cache, batch["pos"],
                                   dtype=jnp.dtype(cfg.dtype))
        logits = unembed(h, params["unembed"])
        return ctx.constrain(logits, "batch", None, "vocab"), cache
