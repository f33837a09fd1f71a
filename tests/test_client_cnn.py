"""The paper's CNN through the client-model interface keeps its
arithmetic: local training, the accuracy sweep and Algorithm 1's pair
values equal, bit for bit, the same computations written directly
against ``repro.fl.cnn`` (the form they had before the interface)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.partition import build_network
from repro.fl import cnn
from repro.fl.client import (_sgd_scan, init_client_params, stack_clients,
                             train_sources, true_accuracies)
from repro.fl.divergence import pairwise_divergence_values


@functools.partial(jax.jit, static_argnames=("iters", "batch", "lr"))
def _direct_train(params_stack, clients, keys, *, iters, batch, lr):
    def one(p, x, y, labeled, valid, key):
        sel = jnp.where(jnp.any(labeled), labeled.astype(jnp.float32),
                        valid.astype(jnp.float32))
        return _sgd_scan(p, x, jnp.maximum(y, 0), sel, key, iters=iters,
                         batch=batch, lr=lr, loss_fn=cnn.xent_loss)

    return jax.vmap(one)(params_stack, clients.x, clients.y,
                         clients.labeled, clients.valid, keys)


@jax.jit
def _direct_accuracies(params_stack, clients):
    return jax.vmap(lambda p, x, ty, v: cnn.accuracy(p, x, ty, mask=v))(
        params_stack, clients.x, clients.true_y, clients.valid)


@functools.partial(jax.jit, static_argnames=("tau", "T", "batch", "lr"))
def _direct_pairs(h0, clients, pair_i, pair_j, keys, *, tau, T, batch, lr):
    n_max = clients.x.shape[1]
    flat_x = jnp.reshape(clients.x, (-1,) + clients.x.shape[2:])

    def one_pair(i, j, k):
        def step(carry, inputs):
            hi, hj = carry
            t, kt = inputs
            ki, kj = jax.random.split(kt)
            xi = flat_x[i * n_max + jax.random.randint(
                ki, (batch,), 0, clients.counts[i])]
            xj = flat_x[j * n_max + jax.random.randint(
                kj, (batch,), 0, clients.counts[j])]
            gi = jax.grad(cnn.xent_loss)(hi, xi, jnp.zeros(batch, jnp.int32))
            gj = jax.grad(cnn.xent_loss)(hj, xj, jnp.ones(batch, jnp.int32))
            hi = jax.tree_util.tree_map(lambda a, g: a - lr * g, hi, gi)
            hj = jax.tree_util.tree_map(lambda a, g: a - lr * g, hj, gj)
            sync = (t + 1) % T == 0
            avg = jax.tree_util.tree_map(lambda a, b: 0.5 * (a + b), hi, hj)
            hi = jax.tree_util.tree_map(
                lambda a, m: jnp.where(sync, m, a), hi, avg)
            hj = jax.tree_util.tree_map(
                lambda a, m: jnp.where(sync, m, a), hj, avg)
            return (hi, hj), None

        (hi, hj), _ = jax.lax.scan(step, (h0, h0), (
            jnp.arange(tau * T), jax.random.split(k, tau * T)))
        hbar = jax.tree_util.tree_map(lambda a, b: 0.5 * (a + b), hi, hj)
        row = jnp.arange(n_max)

        def dev_err(d, lab):
            pred = jnp.argmax(cnn.cnn_forward(hbar, flat_x[d * n_max + row]),
                              axis=-1)
            valid = row < clients.counts[d]
            return jnp.sum(jnp.logical_and(valid, pred != lab)
                           .astype(jnp.float32)), \
                jnp.sum(valid.astype(jnp.float32))

        wi, ni = dev_err(i, 0)
        wj, nj = dev_err(j, 1)
        eps = (wi + wj) / jnp.maximum(ni + nj, 1.0)
        return jnp.clip(2.0 * (1.0 - 2.0 * eps), 0.0, 2.0)

    return jax.vmap(one_pair)(pair_i, pair_j, keys)


@pytest.fixture(scope="module")
def net():
    clients = stack_clients(build_network("M//MM", num_devices=6,
                                          samples_per_device=30, seed=4))
    params = init_client_params(6, jax.random.PRNGKey(4))
    return clients, params


def _same(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_training_and_accuracies_are_the_direct_cnn_bit_for_bit(net):
    clients, params = net
    keys = jax.random.split(jax.random.PRNGKey(8), 6)
    got = train_sources(params, clients, keys, iters=5, batch=10, lr=0.01)
    _same(got, _direct_train(params, clients, keys, iters=5, batch=10,
                             lr=0.01))
    _same(true_accuracies(got, clients), _direct_accuracies(got, clients))


def test_pair_values_are_the_direct_cnn_bit_for_bit(net):
    clients, _ = net
    h0 = cnn.cnn_init(jax.random.PRNGKey(2), num_classes=2)
    pi, pj = jnp.array([0, 1, 2, 0]), jnp.array([3, 4, 5, 5])
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    _same(pairwise_divergence_values(h0, clients, pi, pj, keys, tau=1, T=4,
                                     batch=10, lr=0.01),
          _direct_pairs(h0, clients, pi, pj, keys, tau=1, T=4, batch=10,
                        lr=0.01))
