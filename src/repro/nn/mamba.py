"""Mamba2 (SSD) block: the chunked state-space dual form and its
one-token recurrence.

Structure follows arXiv:2405.21060 as Zamba2 uses it: in_proj ->
[z | x B C | dt] (no bias), a short causal depthwise conv with bias over
(x, B, C) and SiLU, per-head scalar decay a_t = exp(dt_t A) with
dt_t = softplus(dt_raw + dt_bias) and A = -exp(A_log), and the SSD
recurrence per head h

    S_t = a_t S_{t-1} + (dt_t x_t) B_t^T        (state: (head_dim, N))
    y_t = S_t C_t + D x_t

where B and C are shared by the heads of one of ``ngroups`` groups.  The
output passes a gated RMSNorm, normalised per group of d_inner/ngroups
channels, and out_proj.

Precision: matmuls take ``dtype`` (bfloat16) inputs and accumulate in
float32; the decay cumsum, the segment decays and the carried state are
float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.nn.param import ParamSpec

F32 = jnp.float32


def dims(cfg: ModelConfig):
    ssm = cfg.ssm
    d_inner = ssm.expand * cfg.d_model
    nheads = d_inner // ssm.head_dim
    conv_ch = d_inner + 2 * ssm.ngroups * ssm.state_dim   # x, B, C
    return d_inner, nheads, conv_ch


def mamba_specs(cfg: ModelConfig):
    d = cfg.d_model
    d_inner, nheads, conv_ch = dims(cfg)
    proj_out = d_inner + conv_ch + nheads                   # z, xBC, dt
    return {
        "in_proj": ParamSpec((d, proj_out), ("embed", "heads")),
        "conv_w": ParamSpec((cfg.ssm.conv_width, conv_ch), (None, "heads"),
                            scale=0.5),
        "conv_b": ParamSpec((conv_ch,), ("heads",), init="zeros"),
        "a_log": ParamSpec((nheads,), ("heads",), init="zeros"),
        "dt_bias": ParamSpec((nheads,), ("heads",), init="zeros"),
        "d_skip": ParamSpec((nheads,), ("heads",), init="ones"),
        "norm_scale": ParamSpec((d_inner,), ("heads",), init="ones"),
        "out_proj": ParamSpec((d_inner, d), ("heads", "embed")),
    }


def init_a_log(key, nheads: int):
    """Mamba2's A init: A ~ U(1, 16) per head, stored as log A."""
    return jnp.log(jax.random.uniform(key, (nheads,), F32, 1.0, 16.0))


def init_dt_bias(key, nheads: int, dt_min: float = 1e-3,
                 dt_max: float = 0.1, dt_floor: float = 1e-4):
    """Mamba2's dt init: dt log-uniform in [dt_min, dt_max], floored,
    stored as its inverse softplus so softplus(dt_bias) = dt."""
    u = jax.random.uniform(key, (nheads,), F32)
    dt = jnp.exp(u * (math.log(dt_max) - math.log(dt_min))
                 + math.log(dt_min))
    dt = jnp.maximum(dt, dt_floor)
    return dt + jnp.log(-jnp.expm1(-dt))


def _mm(eq, a, b, dtype):
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=F32)


def _causal_conv(x, w, b, conv_state=None):
    """x: (B, S, C); w: (W, C) depthwise.  Returns (silu(conv + b) (B, S,
    C) float32, new_state (B, W-1, C))."""
    width = w.shape[0]
    if conv_state is None:
        conv_state = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]].astype(F32) * w[i].astype(F32)
            for i in range(width))
    return jax.nn.silu(y + b.astype(F32)), xp[:, -(width - 1):]


def _split_proj(cfg, zxbcdt):
    d_inner, nheads, conv_ch = dims(cfg)
    return jnp.split(zxbcdt, [d_inner, d_inner + conv_ch], axis=-1)


def _split_xbc(cfg, xbc):
    d_inner, _, _ = dims(cfg)
    gn = cfg.ssm.ngroups * cfg.ssm.state_dim
    return jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)


def ssd(x, dt, a, bmat, cmat, *, chunk: int, dtype, initial_state=None):
    """The SSD over a whole sequence in chunks of ``chunk``.

    x: (B, L, H, P); dt: (B, L, H) float32 (after softplus); a: (H,)
    float32 (negative); bmat, cmat: (B, L, G, N).  Returns (y (B, L, H, P)
    float32 without the D skip, final state (B, H, P, N) float32).
    """
    b, l, h, p = x.shape
    g, n = bmat.shape[2:]
    k = h // g
    pad = -l % chunk
    if pad:
        zp = lambda t: jnp.pad(t, [(0, 0), (0, pad)] +       # noqa: E731
                               [(0, 0)] * (t.ndim - 2))
        x, dt, bmat, cmat = zp(x), zp(dt), zp(bmat), zp(cmat)
    c = (l + pad) // chunk
    xdt = (x.astype(F32) * dt[..., None]).astype(dtype)
    xdt = xdt.reshape(b, c, chunk, g, k, p)
    bc = bmat.reshape(b, c, chunk, g, n)
    cc = cmat.reshape(b, c, chunk, g, n)
    da = (dt * a).reshape(b, c, chunk, g, k)
    cum = jnp.cumsum(jnp.moveaxis(da, 2, -1), axis=-1)     # (b,c,g,k,q)
    # intra-chunk: y_q = sum_{s<=q} C_q.B_s exp(cum_q - cum_s) dt_s x_s
    seg = cum[..., :, None] - cum[..., None, :]              # (..,q,s)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = _mm("bcqgn,bcsgn->bcgqs", cc, bc, dtype)
    m = cb[:, :, :, None] * decay                            # (b,c,g,k,q,s)
    y = _mm("bcgkqs,bcsgkp->bcqgkp", m, xdt, dtype)
    # each chunk's own state contribution, decayed to its end
    w_end = jnp.exp(cum[..., -1:] - cum)                     # (b,c,g,k,s)
    xw = xdt.astype(F32) * jnp.moveaxis(w_end, -1, 2)[..., None]
    states = _mm("bcsgn,bcsgkp->bcgkpn", bc, xw, dtype)
    # the carried state across chunks, float32
    s0 = (jnp.zeros((b, g, k, p, n), F32) if initial_state is None
          else initial_state.astype(F32).reshape(b, g, k, p, n))
    total = jnp.exp(cum[..., -1])                            # (b,c,g,k)

    def carry(s, inp):
        st, tot = inp
        return s * tot[..., None, None] + st, s

    s_last, s_prev = jax.lax.scan(
        carry, s0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(total, 1, 0)))
    s_prev = jnp.moveaxis(s_prev, 0, 1)                      # (b,c,g,k,p,n)
    y_off = _mm("bcqgn,bcgkpn->bcqgkp", cc, s_prev, dtype)
    y = y + y_off * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    y = y.reshape(b, c * chunk, h, p)[:, :l]
    return y, s_last.reshape(b, h, p, n)


def gated_norm(y, z, scale, ngroups: int, eps: float = 1e-5):
    """RMSNorm of y * silu(z) over groups of d_inner / ngroups channels,
    in float32, times ``scale``."""
    f = y.astype(F32) * jax.nn.silu(z.astype(F32))
    shp = f.shape
    f = f.reshape(shp[:-1] + (ngroups, shp[-1] // ngroups))
    f = f * jax.lax.rsqrt(jnp.mean(jnp.square(f), -1, keepdims=True) + eps)
    return f.reshape(shp) * scale.astype(F32)


def mamba_block(p, x, cfg: ModelConfig, *, state=None, dtype=jnp.bfloat16):
    """Full-sequence SSD.  x: (B, S, D); state: None or (conv_state,
    ssm_state).  Returns (out (B, S, D) float32, (conv_state, ssm_state))."""
    ssm = cfg.ssm
    d_inner, nheads, _ = dims(cfg)
    b, s, _ = x.shape
    zxbcdt = _mm("bsd,dp->bsp", x, p["in_proj"], dtype)
    z, xbc, dt = _split_proj(cfg, zxbcdt)
    conv_state = None if state is None else state[0]
    xbc, conv_state = _causal_conv(xbc.astype(dtype), p["conv_w"],
                                   p["conv_b"], conv_state)
    xin, bmat, cmat = _split_xbc(cfg, xbc)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["a_log"].astype(F32))
    xh = xin.reshape(b, s, nheads, ssm.head_dim)
    y, s_final = ssd(
        xh, dt, a, bmat.reshape(b, s, ssm.ngroups, ssm.state_dim),
        cmat.reshape(b, s, ssm.ngroups, ssm.state_dim), chunk=ssm.chunk,
        dtype=dtype, initial_state=None if state is None else state[1])
    y = y + xh * p["d_skip"].astype(F32)[:, None]
    y = gated_norm(y.reshape(b, s, d_inner), z, p["norm_scale"],
                   ssm.ngroups)
    out = _mm("bsp,pd->bsd", y, p["out_proj"], dtype)
    return out, (conv_state, s_final)


def mamba_decode(p, x, cfg: ModelConfig, *, state, dtype=jnp.bfloat16):
    """x: (B, 1, D); state = (conv_state (B, W-1, C), ssm_state (B, H, P,
    N)).  Returns (out (B, 1, D) float32, new state)."""
    ssm = cfg.ssm
    d_inner, nheads, _ = dims(cfg)
    b = x.shape[0]
    zxbcdt = _mm("bsd,dp->bsp", x, p["in_proj"], dtype)
    z, xbc, dt = _split_proj(cfg, zxbcdt)
    xbc, conv_state = _causal_conv(xbc.astype(dtype), p["conv_w"],
                                   p["conv_b"], state[0])
    xin, bmat, cmat = _split_xbc(cfg, xbc[:, 0])
    dt = jax.nn.softplus(dt[:, 0] + p["dt_bias"].astype(F32))   # (B, H)
    a = -jnp.exp(p["a_log"].astype(F32))
    xh = xin.reshape(b, nheads, ssm.head_dim)
    k = nheads // ssm.ngroups
    bh = jnp.repeat(bmat.reshape(b, ssm.ngroups, ssm.state_dim), k, axis=1)
    ch = jnp.repeat(cmat.reshape(b, ssm.ngroups, ssm.state_dim), k, axis=1)
    xdt = (xh * dt[..., None]).astype(dtype).astype(F32)
    s_new = state[1] * jnp.exp(dt * a)[..., None, None] + \
        xdt[..., :, None] * bh.astype(dtype).astype(F32)[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", s_new, ch.astype(dtype).astype(F32))
    y = y + xh * p["d_skip"].astype(F32)[:, None]
    y = gated_norm(y.reshape(b, 1, d_inner), z, p["norm_scale"],
                   ssm.ngroups)
    out = _mm("bsp,pd->bsd", y, p["out_proj"], dtype)
    return out, (conv_state, s_new)


def init_mamba_state(batch: int, cfg: ModelConfig, dtype=jnp.bfloat16):
    d_inner, nheads, conv_ch = dims(cfg)
    return (jnp.zeros((batch, cfg.ssm.conv_width - 1, conv_ch), dtype),
            jnp.zeros((batch, nheads, cfg.ssm.head_dim, cfg.ssm.state_dim),
                      F32))


def mamba_state_specs(batch: int, cfg: ModelConfig, dtype="bfloat16"):
    d_inner, nheads, conv_ch = dims(cfg)
    return (ParamSpec((batch, cfg.ssm.conv_width - 1, conv_ch),
                      ("batch", None, "heads"), init="zeros", dtype=dtype),
            ParamSpec((batch, nheads, cfg.ssm.head_dim, cfg.ssm.state_dim),
                      ("batch", "heads", None, "state"), init="zeros",
                      dtype="float32"))
