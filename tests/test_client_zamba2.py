"""The Zamba2 client against the plain reference (bench/reference/zamba2.py,
loaded by path: the repository has one reference), at a small size with
the published structure: 6 layers with hybrids at 2 and 5 (both shared
blocks used), ngroups 2, SSD chunks of 16, on seeded random weights.

Two precisions of the program are compared: float32 matmul inputs, which
must reproduce the reference's arithmetic up to summation order, and the
deployed bfloat16 inputs, held to the rounding those inputs allow."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (HybridConfig, ModelConfig, SSMConfig,
                                register)
from repro.fl.client import (PairInputs, ZambaClient, stack_clients,
                             train_sources)
from repro.fl.divergence import pairwise_divergence_values
from repro.data.lm_stream import build_token_network
from repro.sim.engine import SimConfig, SimulationEngine
from repro.sim.metrics import NONDETERMINISTIC_FIELDS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = register(ModelConfig(
    name="zamba2-tiny", arch_type="hybrid", num_layers=6, d_model=64,
    num_heads=4, num_kv_heads=4, head_dim=32, d_ff=128, vocab_size=512,
    mlp_activation="gelu",
    ssm=SSMConfig(state_dim=16, head_dim=16, expand=2, conv_width=4,
                  chunk=16, ngroups=2),
    hybrid=HybridConfig(layer_ids=(2, 5), num_shared_blocks=2,
                        adapter_rank=8)))
SEQ = 32
SHAPE = dict(vocab=512, d=64, layers=6, hybrid_ids=(2, 5), shared_blocks=2,
             heads=4, head_dim=32, ff=128, rank=8, expand=2,
             ssm_head_dim=16, state=16, groups=2, conv=4, chunk=16)


@pytest.fixture(scope="module", autouse=True)
def cheap_compiles():
    """This module's time is the small model's compiles, not its runs:
    they skip XLA's costly backend passes (the operations are the same),
    and nothing compiled so outlives the module."""
    was = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", True)
    yield
    jax.config.update("jax_disable_most_optimizations", was)
    jax.clear_caches()


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "zamba2_reference", os.path.join(ROOT, "bench", "reference",
                                         "zamba2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.configure(**SHAPE)
    yield mod
    mod.configure()


@pytest.fixture(scope="module")
def setup(ref):
    key = jax.random.PRNGKey(5)
    devices = build_token_network(4, 2, SEQ, vocab_size=512, seed=5)
    p_ref = ref.init(key, 10)
    return key, devices, p_ref


class F32Client(ZambaClient):
    """The client with float32 matmul inputs."""
    matmul_dtype = jnp.float32


def _client(cls=F32Client):
    return cls(cfg=TINY, seq_len=SEQ)


def test_leaves_and_logits_match_the_reference(ref, setup):
    key, devices, p_ref = setup
    x = jnp.asarray(devices[0].x)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref._logits, static_argnums=3)(
            ref._FROZEN["backbone"], p_ref, x, jnp.float32)
    for cls, tol in ((F32Client, 1e-4), (ZambaClient, 0.05)):
        c = _client(cls)
        p = c.init(key)
        # the same per-device leaves, drawn from the same key
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda a, b: bool(jnp.array_equal(a, b)), p, p_ref))
        with jax.default_matmul_precision("highest"):
            got = jax.jit(c.logits)(c.init_frozen(key), p, x)
        # float32 inputs: summation order only; bfloat16 inputs: each
        # product's operands rounded to 8 bits (~0.4%), through 6 layers
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(got - want))) <= tol * scale, cls


def test_one_sgd_step_matches_the_reference(ref, setup):
    key, devices, p_ref = setup
    c = _client()
    n = len(devices)
    st = stack_clients(devices)
    p0 = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (n,) + a.shape),
                                c.init(key))
    keys = jax.random.split(jax.random.PRNGKey(9), n)
    with jax.default_matmul_precision("highest"):
        got = train_sources(p0, st, keys, iters=1, batch=1, lr=0.5,
                            client=c, frozen=c.init_frozen(key))
        data = ref.stack(devices, jnp.float32)
        want = ref.train(p0, data["x"], data["y"], data["labeled"],
                         data["valid"], keys, jnp.ones(n, bool), iters=1,
                         batch=1, lr=0.5, dtype=jnp.float32)
    # the leaves' change, not the leaves: float32 summation order only
    for g, w, s in zip(jax.tree_util.tree_leaves(got),
                       jax.tree_util.tree_leaves(want),
                       jax.tree_util.tree_leaves(p0)):
        dw = np.asarray(w - s)
        assert np.abs(dw).max() > 0
        np.testing.assert_allclose(np.asarray(g - s), dw, rtol=0,
                                   atol=1e-4 * np.abs(dw).max())


def test_algorithm_1_on_features_matches_the_reference(ref, runs):
    eng, _ = runs[0]
    # the rows the engine keeps: the backbone's bfloat16 features of every
    # device's tokens under the shared initial adapters, computed once
    rows = eng.pair_inputs(eng.state.clients)
    assert isinstance(rows, PairInputs)
    assert rows.x.shape == (4, 2 * SEQ, 64) and rows.x.dtype == jnp.bfloat16
    np.testing.assert_array_equal(rows.counts, [2 * SEQ] * 4)
    c = _client()
    with jax.default_matmul_precision("highest"):
        h0 = c.pair_init(jax.random.PRNGKey(11))
        pi, pj = np.array([0, 0, 1, 2]), np.array([1, 3, 2, 3])
        keys = jax.random.split(jax.random.PRNGKey(12), len(pi))
        got = pairwise_divergence_values(
            h0, rows, jnp.asarray(pi), jnp.asarray(pj), keys, tau=1, T=4,
            batch=1, lr=0.5, client=c)
        h0r = ref.init(jax.random.PRNGKey(11), 2)
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (len(pi),) + a.shape), h0r)
        n = jnp.full(len(pi), 2 * SEQ)
        # the reference's heads on the same features, a step on one
        # sequence's worth of token rows
        want = ref._pair_heads(stacked, rows.x, jnp.asarray(pi), n,
                               jnp.asarray(pj), n, keys, tau=1, T=4,
                               rows=SEQ, lr=0.5, dtype=jnp.float32)
    # d = 2 (1 - 2 eps) over 2 * 64 rows: one row classified otherwise
    # (a near-tie under float32 summation order) moves d by 4 / 128
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=4 / 128 + 1e-6)


def _engine(mesh, **kw):
    return SimulationEngine(SimConfig(
        client="zamba2-tiny", setting="T1//T2//T3", seq_len=SEQ, devices=4,
        samples_per_device=2,
        rounds=2, seed=21, train_iters=2, batch=1, lr=0.05, mesh=mesh,
        resolve_threshold=1e9, **kw))


@pytest.fixture(scope="module")
def runs():
    out = {}
    for mesh in (0, 1):
        eng = _engine(mesh)
        rows = [eng.step(t) for t in range(2)]
        out[mesh] = (eng, rows)
    return out


def test_transfer_matches_the_references_mixture(ref, runs):
    eng, _ = runs[1]
    st = eng.state
    alpha = np.zeros((4, 4))
    alpha[0, 2], alpha[1, 2], alpha[0, 3] = 0.25, 0.75, 1.0
    psi = np.array([0.0, 0.0, 1.0, 1.0])
    got = eng.pool.transfer(st.params, alpha, psi)
    want = ref.mix_targets(st.params, alpha, psi, jnp.float32)
    # the Pallas combine at HIGHEST against float64 sums on the host
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-7)


def test_two_rounds_agree_on_both_pools(runs):
    (e0, rows0), (e1, rows1) = runs[0], runs[1]
    assert rows0[0]["n_featurized"] == 4 and rows0[1]["n_featurized"] == 0
    assert rows0[0]["train_tokens"] == \
        rows0[0]["n_trained"] * 2 * 1 * SEQ
    assert rows0[0]["eval_tokens"] == 3 * 4 * 2 * SEQ
    for r0, r1 in zip(rows0, rows1):
        for k, v in r0.items():
            if k not in NONDETERMINISTIC_FIELDS and k != "drift":
                assert r1[k] == v, k
    # the pools differ only in the combine (XLA against Pallas)
    for a, b in zip(jax.tree_util.tree_leaves(e0.state.params),
                    jax.tree_util.tree_leaves(e1.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
    np.testing.assert_array_equal(e0.state.div_hat, e1.state.div_hat)


def test_token_data_refuses_drift_and_spares(runs):
    eng, _ = runs[0]
    with pytest.raises(NotImplementedError, match="token data"):
        eng.drift_features(0, 0.5)
    with pytest.raises(ValueError, match="spare"):
        _engine(0, scenario="device-churn")
    with pytest.raises(ValueError, match="split setting"):
        SimulationEngine(SimConfig(client="zamba2-tiny", setting="M",
                                   seq_len=SEQ, devices=4))
