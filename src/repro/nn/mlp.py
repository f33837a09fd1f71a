"""Dense MLPs: SwiGLU / GeGLU / plain GELU."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn.param import ParamSpec
from repro.nn.layers import ShardCtx, NO_SHARD


def mlp_specs(d_model: int, d_ff: int, activation: str):
    if activation in ("swiglu", "geglu"):
        return {
            "wi_gate": ParamSpec((d_model, d_ff), ("embed", "mlp")),
            "wi_up": ParamSpec((d_model, d_ff), ("embed", "mlp")),
            "wo": ParamSpec((d_ff, d_model), ("mlp", "embed")),
        }
    return {
        "wi": ParamSpec((d_model, d_ff), ("embed", "mlp")),
        "wo": ParamSpec((d_ff, d_model), ("mlp", "embed")),
    }


def mlp(params, x, activation: str, ctx: ShardCtx = NO_SHARD,
        dtype=jnp.bfloat16):
    if activation in ("swiglu", "geglu"):
        g = jnp.einsum("bsd,df->bsf", x, params["wi_gate"].astype(dtype))
        u = jnp.einsum("bsd,df->bsf", x, params["wi_up"].astype(dtype))
        act = jax.nn.silu if activation == "swiglu" else \
            (lambda t: jax.nn.gelu(t, approximate=True))
        h = act(g) * u
    else:
        h = jnp.einsum("bsd,df->bsf", x, params["wi"].astype(dtype))
        h = jax.nn.gelu(h, approximate=True)
    h = ctx.constrain(h, "batch", None, "mlp")
    return jnp.einsum("bsf,fd->bsd", h, params["wo"].astype(dtype))


def gated_gelu_specs(d_model: int, d_ff: int):
    """One fused ``gate_up`` projection (gate first) and ``down``."""
    return {"gate_up": ParamSpec((d_model, 2 * d_ff), ("embed", "mlp")),
            "down": ParamSpec((d_ff, d_model), ("mlp", "embed"))}


def adapter_specs(d_model: int, rank: int, d_out: int):
    """A rank-``rank`` additive adapter ``x @ down @ up``."""
    return {"down": ParamSpec((d_model, rank), ("embed", None)),
            "up": ParamSpec((rank, d_out), (None, "mlp"))}


def gated_gelu(params, x, adapter=None, dtype=jnp.bfloat16):
    """gelu(g) * u with [g | u] = x @ gate_up (+ x @ down @ up of the
    adapter), exact (erf) GELU; then ``down``.  Matmuls take ``dtype``
    inputs and accumulate in float32; the output is float32."""
    def mm(eq, a, b):
        return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                          preferred_element_type=jnp.float32)

    gu = mm("bsd,df->bsf", x, params["gate_up"])
    if adapter is not None:
        gu = gu + mm("bsr,rf->bsf", mm("bsd,dr->bsr", x, adapter["down"]),
                     adapter["up"])
    g, u = jnp.split(gu, 2, axis=-1)
    return mm("bsf,fd->bsd", jax.nn.gelu(g, approximate=False) * u,
              params["down"])
