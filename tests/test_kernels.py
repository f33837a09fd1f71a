"""Pallas kernels vs their pure-jnp oracles (interpret=True on CPU),
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.alpha_combine.ops import alpha_combine, alpha_combine_tree
from repro.kernels.alpha_combine.ref import alpha_combine_ref
from repro.kernels.disagreement.ops import disagreement
from repro.kernels.disagreement.ref import disagreement_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssm_scan.ops import gla_chunked
from repro.kernels.ssm_scan.ref import gla_chunked_ref

RNG = np.random.default_rng(0)


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("b,sq,sk,h,d,causal,window", [
    (2, 64, 64, 2, 32, True, None),
    (1, 100, 100, 3, 64, True, None),       # padding path
    (2, 64, 64, 2, 32, True, 24),           # sliding window
    (1, 32, 160, 2, 16, True, None),        # history offset (sk > sq)
    (1, 96, 96, 1, 128, False, None),       # bidirectional
])
def test_flash_attention_matches_ref(b, sq, sk, h, d, causal, window):
    q = jnp.asarray(RNG.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, sk, h, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, sk, h, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 3e-5),
                                        (jnp.bfloat16, 4e-2)])
def test_flash_attention_dtypes(dtype, atol):
    q = jnp.asarray(RNG.normal(size=(1, 64, 2, 32)), dtype)
    k = jnp.asarray(RNG.normal(size=(1, 64, 2, 32)), dtype)
    v = jnp.asarray(RNG.normal(size=(1, 64, 2, 32)), dtype)
    out = flash_attention(q, k, v)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


# ------------------------------------------------------- chunked (XLA flash)
@pytest.mark.parametrize("b,s,h,hd,win,chunk", [
    (2, 64, 2, 32, None, 16),
    (1, 50, 2, 16, None, 16),           # ragged tail
    (1, 64, 1, 32, 24, 16),             # sliding window
])
def test_chunked_attention_matches_dot(b, s, h, hd, win, chunk):
    """The online-softmax XLA variant (the dry-run-visible flash twin)."""
    from repro.nn.attention import (causal_mask, chunked_attention,
                                    dot_attention)
    q = jnp.asarray(RNG.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, h, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, h, hd)), jnp.float32)
    out = chunked_attention(q, k, v, causal=True, window=win, chunk=chunk,
                            dtype=jnp.float32)
    ref = dot_attention(q, k, v, causal_mask(s, s, window=win),
                        dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_chunked_attention_matches_pallas_flash():
    q = jnp.asarray(RNG.normal(size=(1, 64, 2, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 64, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 64, 2, 32)), jnp.float32)
    from repro.nn.attention import chunked_attention
    out_c = chunked_attention(q, k, v, chunk=16, dtype=jnp.float32)
    out_p = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_p),
                               atol=3e-5)


# ----------------------------------------------------------------- ssm scan
@pytest.mark.parametrize("b,l,h,dk,dv,chunk,variant", [
    (2, 64, 2, 16, 16, 16, "mamba"),
    (1, 96, 3, 32, 32, 32, "rwkv"),
    (2, 50, 2, 16, 24, 16, "mamba"),        # ragged tail padding
    (1, 128, 1, 64, 64, 32, "rwkv"),
])
def test_gla_kernel_matches_ref(b, l, h, dk, dv, chunk, variant):
    q = jnp.asarray(RNG.normal(size=(b, l, h, dk)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, l, h, dk)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, l, h, dv)), jnp.float32)
    lw = jnp.asarray(-np.abs(RNG.normal(size=(b, l, h, dk)) * 0.3),
                     jnp.float32)
    bonus = (jnp.asarray(RNG.normal(size=(h, dk)), jnp.float32)
             if variant == "rwkv" else None)
    s0 = jnp.asarray(RNG.normal(size=(b, h, dk, dv)), jnp.float32)
    y1, s1 = gla_chunked(q, k, v, lw, chunk=chunk, variant=variant,
                         bonus=bonus, initial_state=s0)
    y2, s2 = gla_chunked_ref(q, k, v, lw, chunk=chunk, variant=variant,
                             bonus=bonus, initial_state=s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


def test_gla_kernel_matches_sequential_recurrence():
    """Cross-check chunked kernel against the token-by-token recurrence."""
    from repro.nn.linear_attn import gla_decode
    b, l, h, dk, dv = 1, 12, 1, 8, 8
    q = jnp.asarray(RNG.normal(size=(b, l, h, dk)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, l, h, dk)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, l, h, dv)), jnp.float32)
    lw = jnp.asarray(-np.abs(RNG.normal(size=(b, l, h, dk)) * 0.2),
                     jnp.float32)
    y_k, s_k = gla_chunked(q, k, v, lw, chunk=4, variant="mamba")
    s = jnp.zeros((b, h, dk, dv), jnp.float32)
    ys = []
    for t in range(l):
        y_t, s = gla_decode(q[:, t], k[:, t], v[:, t], lw[:, t], s)
        ys.append(y_t)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_seq), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s), atol=1e-4)


# -------------------------------------------------------------- disagreement
@pytest.mark.parametrize("n,m", [(4, 100), (10, 513), (3, 64), (17, 1000)])
def test_disagreement_matches_ref(n, m):
    p = jnp.asarray(RNG.integers(0, 5, size=(n, m)), jnp.int32)
    v = jnp.asarray(RNG.random(m) > 0.2)
    np.testing.assert_allclose(np.asarray(disagreement(p, v)),
                               np.asarray(disagreement_ref(p, v)), atol=1e-6)


def test_disagreement_properties():
    p = jnp.asarray(RNG.integers(0, 3, size=(5, 200)), jnp.int32)
    d = np.asarray(disagreement(p))
    assert np.allclose(np.diag(d), 0.0)
    assert np.allclose(d, d.T)
    assert d.min() >= 0 and d.max() <= 1.0


# ------------------------------------------------------------- alpha combine
@pytest.mark.parametrize("s,t,p", [
    (4, 3, 1000), (8, 8, 5000), (2, 1, 64),
    (1024, 1024, 600),                  # S tiled: accumulates over 2 blocks
    (128, 8192, 128),                   # T tiled: 2 target blocks
])
def test_alpha_combine_matches_ref(s, t, p):
    th = jnp.asarray(RNG.normal(size=(s, p)), jnp.float32)
    al = jnp.asarray(RNG.random((s, t)), jnp.float32)
    np.testing.assert_allclose(np.asarray(alpha_combine(th, al)),
                               np.asarray(alpha_combine_ref(th, al)),
                               atol=1e-4)


@pytest.mark.parametrize("s,t", [(64, 64), (1024, 1024), (1024, 256),
                                 (4096, 4096), (8192, 2048)])
def test_alpha_combine_tiles_fit_vmem(s, t):
    from repro.kernels.alpha_combine.kernel import VMEM_BUDGET, _tiles
    bs, bt, bp = _tiles(s, t, 48_158, 2048)
    assert 4 * (2 * bs * bt + 2 * bs * bp + 3 * bt * bp) <= VMEM_BUDGET
    assert bp % 128 == 0
    assert bs == s or bs % 128 == 0
    assert bt == t or bt % 128 == 0


def test_alpha_combine_tree_matches_einsum():
    from repro.fl.transfer import combine_models
    stack = {"w": jnp.asarray(RNG.normal(size=(4, 3, 5)), jnp.float32),
             "b": jnp.asarray(RNG.normal(size=(4, 7)), jnp.float32)}
    alpha = jnp.asarray(RNG.random((4, 4)), jnp.float32)
    out_k = alpha_combine_tree(stack, alpha)
    out_x = combine_models(stack, alpha, impl="xla")
    for key in stack:
        np.testing.assert_allclose(np.asarray(out_k[key]),
                                   np.asarray(out_x[key]), atol=1e-4)
