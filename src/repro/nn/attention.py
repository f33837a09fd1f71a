"""GQA/MQA attention: train/prefill (causal or bidirectional or sliding
window), cross attention, and cached decode (full or ring-buffer window
cache).  An optional Pallas flash-attention path is used when
``config.attention_impl == 'pallas'`` (validated in interpret mode on CPU).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.nn.param import ParamSpec
from repro.nn.layers import apply_rope, ShardCtx, NO_SHARD

NEG_INF = -2.0e9


def attention_specs(d_model: int, num_heads: int, num_kv_heads: int,
                    head_dim: int, d_out: Optional[int] = None):
    """q, k, v read ``d_model`` wide inputs; o writes ``d_out`` (default
    ``d_model``) wide outputs."""
    return {
        "wq": ParamSpec((d_model, num_heads, head_dim), ("embed", "heads", "qkv")),
        "wk": ParamSpec((d_model, num_kv_heads, head_dim), ("embed", "kv_heads", "qkv")),
        "wv": ParamSpec((d_model, num_kv_heads, head_dim), ("embed", "kv_heads", "qkv")),
        "wo": ParamSpec((num_heads, head_dim, d_out or d_model),
                        ("heads", "qkv", "embed")),
    }


def _repeat_kv(k, num_heads):
    """(B, S, KV, hd) -> (B, S, H, hd) by group broadcast."""
    b, s, kv, hd = k.shape
    rep = num_heads // kv
    if rep == 1:
        return k
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, rep, hd))
    return jnp.reshape(k, (b, s, kv * rep, hd))


def dot_attention(q, k, v, mask, dtype=jnp.bfloat16, scale=None):
    """q: (B,Sq,H,hd); k,v: (B,Sk,H,hd); mask (B,1,Sq,Sk) or (1,1,Sq,Sk).
    Scores are scaled by ``scale`` (default hd^-1/2)."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32))
    scores = scores / jnp.sqrt(float(hd)) if scale is None \
        else scores * scale
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(dtype), v.astype(dtype))
    return out


def chunked_attention(q, k, v, *, causal=True, window=None,
                      chunk: int = 1024, dtype=jnp.bfloat16):
    """Online-softmax attention scanned over KV chunks — the flash
    algorithm expressed in XLA (lax.scan) so the (Sq, Sk) score matrix is
    never materialized in HBM.  This is the dry-run-visible twin of the
    Pallas kernel (which interpret-mode cannot lower at production sizes):
    peak attention HBM traffic drops from O(Sq·Sk) to O(Sq·chunk) per
    step.  q: (B,Sq,H,hd); k,v: (B,Sk,H,hd) (heads pre-repeated)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    if sk % chunk:
        pad = chunk - sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n = k.shape[1] // chunk
    qf = q.astype(jnp.float32) / jnp.sqrt(float(hd))
    kc = jnp.reshape(k.astype(jnp.float32), (b, n, chunk, h, hd))
    vc = jnp.reshape(v.astype(jnp.float32), (b, n, chunk, h, hd))
    kc = jnp.moveaxis(kc, 1, 0)                       # (n,B,C,H,hd)
    vc = jnp.moveaxis(vc, 1, 0)
    q_pos = jnp.arange(sq)[:, None] + (sk - sq)       # (Sq,1)

    def step(carry, xs):
        m, l, acc, ci = carry
        kb, vb = xs
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb)     # (B,H,Sq,C)
        k_pos = ci * chunk + jnp.arange(chunk)[None, :]
        mask = k_pos < sk
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vb)
        return (m_new, l, acc, ci + 1), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, a0, 0), (kc, vc))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return jnp.swapaxes(out, 1, 2).astype(dtype)      # (B,Sq,H,hd)


def causal_mask(sq: int, sk: int, window: Optional[int] = None,
                offset: int = 0):
    """(1,1,Sq,Sk) bool; query i attends to key j iff j <= i+offset and,
    with a window, j > i+offset-window."""
    qi = jnp.arange(sq)[:, None] + offset
    kj = jnp.arange(sk)[None, :]
    m = kj <= qi
    if window is not None:
        m = jnp.logical_and(m, kj > qi - window)
    return m[None, None]


def attend(params, x, positions, *, num_heads, num_kv_heads, head_dim,
           rope_theta, causal=True, window=None, ctx: ShardCtx = NO_SHARD,
           dtype=jnp.bfloat16, cross_kv=None, impl="xla", scale=None):
    """Self (or cross) attention over a full sequence (train / prefill).

    x: (B, S, D).  cross_kv: optional (k, v) from an encoder
    (B, S_enc, KV, hd) for cross attention (bidirectional over memory).
    """
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    if cross_kv is None:
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    else:
        k, v = cross_kv
    # 'seq' resolves to () under the default rules; seq_parallel maps it to
    # the model axis — the fallback when heads don't divide the axis
    # (llama4's 40 heads on a 16-wide axis) so score traffic still shards.
    q = ctx.constrain(q, "batch", "seq", "heads", None)
    k = ctx.constrain(k, "batch", None, "kv_heads", None)

    sk = k.shape[1]

    if impl == "pallas" and cross_kv is None and causal:
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, _repeat_kv(k, num_heads),
                                     _repeat_kv(v, num_heads), window=window)
    elif impl == "chunked" and cross_kv is None and causal:
        out = chunked_attention(q, _repeat_kv(k, num_heads),
                                _repeat_kv(v, num_heads), causal=True,
                                window=window, dtype=dtype)
    else:
        if cross_kv is not None or not causal:
            mask = jnp.ones((1, 1, s, sk), dtype=bool)
        else:
            mask = causal_mask(s, sk, window=window)
        out = dot_attention(q, _repeat_kv(k, num_heads),
                            _repeat_kv(v, num_heads), mask, dtype=dtype,
                            scale=scale)
    out = ctx.constrain(out, "batch", None, "heads", None)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dtype))


# ------------------------------------------------------------------ decode
def init_cache(batch: int, max_len: int, num_kv_heads: int, head_dim: int,
               dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, max_len, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, num_kv_heads, head_dim), dtype),
    }


def cache_specs(batch: int, max_len: int, num_kv_heads: int, head_dim: int,
                dtype="bfloat16"):
    s = ParamSpec((batch, max_len, num_kv_heads, head_dim),
                  ("batch", "kv_seq", "kv_heads", "qkv"), init="zeros",
                  dtype=dtype)
    return {"k": s, "v": s}


def decode_attend(params, x, cache, pos, *, num_heads, num_kv_heads,
                  head_dim, rope_theta, window=None, ctx: ShardCtx = NO_SHARD,
                  dtype=jnp.bfloat16, cross_kv=None, scale=None):
    """One-token decode.  x: (B, 1, D); pos: (B,) current absolute position.

    With ``window`` the cache is a ring buffer of size ``window`` (slot =
    pos % window) — the standard production memory model for sliding-window
    decode: long_500k keeps only a window-sized KV cache.
    Returns (out (B,1,D), new_cache).
    """
    b = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    if cross_kv is None:
        q = apply_rope(q, pos[:, None], rope_theta)

    if cross_kv is not None:
        k, v = cross_kv
        sk = k.shape[1]
        mask = jnp.ones((b, 1, 1, sk), dtype=bool)
        out = dot_attention(q, _repeat_kv(k, num_heads),
                            _repeat_kv(v, num_heads), mask, dtype=dtype)
        return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dtype)), cache

    kn = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    vn = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    kn = apply_rope(kn, pos[:, None], rope_theta)

    max_len = cache["k"].shape[1]
    slot = pos % max_len if window is not None else pos
    bidx = jnp.arange(b)
    k = cache["k"].at[bidx, slot].set(kn[:, 0])
    v = cache["v"].at[bidx, slot].set(vn[:, 0])
    new_cache = {"k": k, "v": v}

    kpos = jnp.arange(max_len)[None, :]                       # (1, S)
    if window is not None:
        # ring buffer: entry at slot j holds absolute position p with
        # p % window == j and p <= pos; valid iff pos - p < window.
        base = (pos[:, None] // max_len) * max_len
        abs_pos = jnp.where(kpos <= (pos[:, None] % max_len),
                            base + kpos, base - max_len + kpos)
        valid = jnp.logical_and(abs_pos >= 0, abs_pos <= pos[:, None])
        valid = jnp.logical_and(valid, abs_pos > pos[:, None] - window)
    else:
        valid = kpos <= pos[:, None]
    mask = valid[:, None, None, :]                            # (B,1,1,S)

    out = dot_attention(q, _repeat_kv(k, num_heads),
                        _repeat_kv(v, num_heads), mask, dtype=dtype,
                        scale=scale)
    out = ctx.constrain(out, "batch", None, "heads", None)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dtype)), new_cache
