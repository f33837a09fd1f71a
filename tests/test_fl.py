"""Federated runtime: CNN, Algorithm 1, transfer, full-round integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.data import build_network
from repro.fl import (apply_transfer, column_normalize, combine_models,
                      estimate_divergences, prepare_round, run_stlf,
                      stack_clients)
from repro.fl import cnn
from repro.fl.client import empirical_errors, init_client_params, \
    train_sources


def test_cnn_shapes():
    p = cnn.cnn_init(jax.random.PRNGKey(0), num_classes=10)
    x = jnp.zeros((3, 28, 28, 3))
    logits = cnn.cnn_forward(p, x)
    assert logits.shape == (3, 10)
    feats = cnn.cnn_features(p, x)
    assert feats.shape == (3, cnn.FC_HIDDEN)


def test_cnn_learns_trivial_split():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 28, 28, 3)).astype(np.float32))
    y = jnp.asarray((np.asarray(x)[:, :, :, 0].mean((1, 2)) > 0)
                    .astype(np.int32))
    p = cnn.cnn_init(jax.random.PRNGKey(1), num_classes=2)

    @jax.jit
    def step(p):
        g = jax.grad(cnn.xent_loss)(p, x, y)
        return jax.tree.map(lambda a, b: a - 0.05 * b, p, g)

    for _ in range(60):
        p = step(p)
    assert float(cnn.accuracy(p, x, y)) > 0.9


def test_shared_init_broadcast():
    params = init_client_params(4, jax.random.PRNGKey(0))
    w = np.asarray(params["conv1"])
    assert np.allclose(w[0], w[1]) and np.allclose(w[0], w[3])


def test_empirical_errors_respect_unlabeled_convention():
    devs = build_network("M", num_devices=4, samples_per_device=30, seed=0)
    clients = stack_clients(devs)
    params = init_client_params(4, jax.random.PRNGKey(0))
    eps = np.asarray(empirical_errors(params, clients))
    for i, d in enumerate(devs):
        if d.n_labeled == 0:
            assert eps[i] == pytest.approx(1.0)   # all unlabeled -> 1
        else:
            assert eps[i] >= (d.n - d.n_labeled) / d.n - 1e-6


def test_divergence_same_vs_different_domain():
    """Algorithm 1 separates M vs MM pairs more than M vs M pairs."""
    devs_m = build_network("M", num_devices=2, samples_per_device=60,
                           seed=3)
    devs_split = build_network("M//MM", num_devices=2,
                               samples_per_device=60, seed=3)
    d_same = estimate_divergences(stack_clients(devs_m),
                                  jax.random.PRNGKey(0), tau=2, T=15)
    d_diff = estimate_divergences(stack_clients(devs_split),
                                  jax.random.PRNGKey(0), tau=2, T=15)
    assert d_diff[0, 1] >= d_same[0, 1] - 0.15
    assert 0 <= d_same[0, 1] <= 2.0 and 0 <= d_diff[0, 1] <= 2.0
    assert d_same[0, 0] == 0.0


@given(st.integers(2, 5))
@settings(max_examples=10, deadline=None)
def test_column_normalize_feasibility(n):
    rng = np.random.default_rng(n)
    psi = np.zeros(n)
    psi[rng.integers(1, n)] = 1.0
    a = rng.random((n, n))
    out = column_normalize(a, psi)
    for j in range(n):
        if psi[j] == 1.0:
            assert out[:, j].sum() == pytest.approx(1.0)
            assert np.all(out[psi == 1.0, j] == 0.0)
        else:
            assert out[:, j].sum() == pytest.approx(0.0)


def test_combine_models_identity_and_convexity():
    params = init_client_params(3, jax.random.PRNGKey(0),
                                shared_init=False)
    eye = jnp.eye(3)
    out = combine_models(params, eye)
    for k in ("conv1", "fc2"):
        np.testing.assert_allclose(np.asarray(out[k]),
                                   np.asarray(params[k]), atol=1e-6)
    # averaging: target = mean of sources
    alpha = jnp.asarray(np.array([[0, 0, .5], [0, 0, .5], [0, 0, 0]]))
    mixed = combine_models(params, alpha)
    expect = 0.5 * (np.asarray(params["fc2"][0])
                    + np.asarray(params["fc2"][1]))
    np.testing.assert_allclose(np.asarray(mixed["fc2"][2]), expect,
                               atol=1e-6)


def test_apply_transfer_keeps_sources():
    params = init_client_params(3, jax.random.PRNGKey(0),
                                shared_init=False)
    psi = np.array([0.0, 0.0, 1.0])
    alpha = np.zeros((3, 3))
    alpha[0, 2] = 1.0
    out = apply_transfer(params, jnp.asarray(alpha), jnp.asarray(psi))
    np.testing.assert_allclose(np.asarray(out["fc2"][0]),
                               np.asarray(params["fc2"][0]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["fc2"][2]),
                               np.asarray(params["fc2"][0]), atol=1e-5)


@pytest.mark.slow
def test_full_round_integration():
    devs = build_network("M//MM", num_devices=5, samples_per_device=50,
                         seed=0, label_subset=[0, 1, 2])
    state = prepare_round(devs, jax.random.PRNGKey(0), train_iters=60,
                          div_tau=2, div_T=10)
    res = run_stlf(state, max_outer=3, inner_steps=300)
    assert set(np.unique(res.psi)) <= {0.0, 1.0}
    assert np.any(res.psi == 0.0)
    if np.any(res.psi == 1.0):
        assert np.isfinite(res.target_acc)
        assert 0.0 <= res.target_acc <= 1.0
    assert res.energy >= 0.0
