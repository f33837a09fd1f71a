"""Sharded device-pool coverage (repro.sim.shard): mesh construction
through the extended launch.mesh factory, shard_map op parity at
mesh-of-1, golden-pinned end-to-end parity of the sharded pipeline
(mesh-of-1 in-process; emulated mesh-of-8 in a subprocess, since
XLA_FLAGS must be set before the first jax import), the async
subset-gather training path against its masked reference, and the
gossip topology registry.

Field-for-field golden comparisons treat the documented
NONDETERMINISTIC_FIELDS (wall clocks) as exempt; everything else must
match the single-host LocalPool trajectory exactly — the pool backend
changes WHERE lanes run, never what they compute.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl.client import init_client_params, stack_clients
from repro.fl.divergence import update_divergences
from repro.fl.transfer import apply_transfer
from repro.launch.mesh import make_local_mesh
from repro.sim.engine import SimConfig, SimulationEngine
from repro.sim.metrics import NONDETERMINISTIC_FIELDS
from repro.sim.shard import (DEVICE_AXIS, LocalPool, ShardedPool,
                             make_pool, make_pool_mesh)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOKE = dict(samples_per_device=40, train_iters=8, div_tau=1, div_T=6,
             solver_max_outer=3, solver_inner_steps=200)
#: the exact config tests/golden/sim_async-gossip.jsonl was captured
#: with (single host, subset-gather default on) — covers a cold solve
#: and a staleness-triggered warm re-solve in 4 ticks
ASYNC_GOLDEN = dict(scenario="async-gossip", engine="async-gossip",
                    devices=8, rounds=4, seed=0, resolve_threshold=0.5,
                    resolve_patience=3, **SMOKE)
STATIC_GOLDEN = dict(scenario="static", devices=8, rounds=3, seed=0,
                     reseed_on_rejoin=False, **SMOKE)
#: feature drift over a pool of 8 with drifts in every tick
DRIFT_RESTACK = dict(scenario="feature-drift", devices=8, rounds=3, seed=1,
                     feature_drift_p=0.9, samples_per_device=8,
                     train_iters=2, div_tau=1, div_T=2, batch=4,
                     solver_max_outer=2, solver_inner_steps=120)


def _golden(name):
    with open(os.path.join(GOLDEN_DIR, f"sim_{name}.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def _assert_rows_match(golden, rows, tag):
    assert len(rows) == len(golden), tag
    for g, r in zip(golden, rows):
        for k, v in g.items():
            if k in NONDETERMINISTIC_FIELDS:
                continue
            ok = r[k] == v or (isinstance(v, float)
                               and np.isnan(v) and np.isnan(r[k]))
            assert ok, (tag, g["round"], k, v, r[k])


# ------------------------------------------------------- mesh factories
def test_make_local_mesh_axis_names_and_cap():
    mesh = make_local_mesh(1, axis_names=("devices", "model"),
                           max_devices=1)
    assert mesh.axis_names == ("devices", "model")
    assert mesh.shape["devices"] == 1 and mesh.shape["model"] == 1
    with pytest.raises(RuntimeError):
        make_local_mesh(len(jax.devices()) + 1)


def test_make_pool_mesh_single_and_oversubscribed():
    mesh = make_pool_mesh(1)
    assert mesh.shape[DEVICE_AXIS] == 1
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_pool_mesh(len(jax.devices()) + 7)
    with pytest.raises(ValueError):
        make_pool_mesh(0)


def test_make_pool_selects_backend():
    cfg = SimConfig(scenario="static", devices=4, rounds=1, **SMOKE)
    eng = SimulationEngine(cfg)
    assert isinstance(eng.pool, LocalPool) and eng.pool.name == "local"
    cfg1 = SimConfig(scenario="static", devices=4, rounds=1, mesh=1,
                     **SMOKE)
    eng1 = SimulationEngine(cfg1)
    assert isinstance(eng1.pool, ShardedPool)
    assert eng1.pool.name == "sharded-1"


# ------------------------------------------- shard_map op parity (mesh-1)
def _tiny_engine(**kw):
    cfg = SimConfig(scenario="static", devices=5, rounds=1,
                    samples_per_device=20, train_iters=4, div_tau=1,
                    div_T=4, batch=5, solver_max_outer=2,
                    solver_inner_steps=100, **kw)
    return SimulationEngine(cfg)


def test_sharded_transfer_matches_apply_transfer():
    eng = _tiny_engine(mesh=1)
    params = init_client_params(5, jax.random.PRNGKey(3),
                                shared_init=False)
    psi = np.array([0.0, 0.0, 0.0, 1.0, 1.0])
    rng = np.random.default_rng(2)
    alpha = np.zeros((5, 5))
    for j in (3, 4):
        w = rng.random(3)
        alpha[:3, j] = w / w.sum()
    ref = apply_transfer(params, jnp.asarray(alpha), jnp.asarray(psi))
    out = eng.pool.transfer(params, alpha, psi)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(ref[k]))


def test_sharded_pair_values_match_local():
    eng = _tiny_engine(mesh=1)
    clients = eng.state.clients
    key = jax.random.PRNGKey(11)
    pairs = np.array([[0, 3], [1, 2], [2, 4]], np.int32)
    kw = dict(tau=1, T=4, batch=5, lr=0.01)
    ref = update_divergences(np.zeros((5, 5)), clients, key, pairs, **kw)
    out = update_divergences(np.zeros((5, 5)), clients, key, pairs,
                             values_fn=eng.pool._values_fn(), **kw)
    np.testing.assert_array_equal(out, ref)


def test_sharded_train_matches_local_pool():
    eng = _tiny_engine(mesh=1)
    loc = LocalPool(eng)
    st = eng.state
    key = jax.random.PRNGKey(5)
    p_ref, eps_ref, acc_ref = loc.train(st.params, st.clients, key,
                                        st.active)
    p_sh, eps_sh, acc_sh = eng.pool.train(st.params, st.clients, key,
                                          st.active)
    for k in p_ref:
        np.testing.assert_array_equal(np.asarray(p_sh[k]),
                                      np.asarray(p_ref[k]))
    np.testing.assert_array_equal(np.asarray(eps_sh), np.asarray(eps_ref))
    np.testing.assert_array_equal(np.asarray(acc_sh), np.asarray(acc_ref))


def test_sharded_pool_keeps_clients_on_its_mesh():
    eng = _tiny_engine(mesh=1)
    x = eng.state.clients.x
    assert x.sharding.mesh.devices.size == eng.pool.mesh.devices.size
    assert x.sharding.spec[0] == DEVICE_AXIS
    local = _tiny_engine()
    assert local.pool.place_clients(local.state.clients) \
        is local.state.clients


def test_sharded_pool_pads_non_dividing_pool():
    """mesh-of-1 never pads; fake a 2-shard pool boundary by checking
    the padding helpers directly (a real 2-shard mesh needs 2 devices)."""
    eng = _tiny_engine(mesh=1)
    pool = eng.pool
    assert pool._pad(5) == 0            # 1 shard: everything divides
    pool.n_shards = 4                   # exercise the helpers alone
    assert pool._pad(5) == 3
    padded = pool._pad_tree(jnp.arange(10.0).reshape(5, 2), 3)
    assert padded.shape == (8, 2)
    np.testing.assert_array_equal(np.asarray(padded[5:]),
                                  np.asarray(padded[4:5]).repeat(3, 0))
    mask = pool._pad_mask(np.ones(5, bool), 3)
    assert mask.sum() == 5 and not mask[5:].any()


# ------------------------------------------------- subset-gather training
def test_subset_gather_matches_masked_training():
    """Satellite: the compact gathered async step must reproduce the
    masked full-pool step's trained params AND metrics exactly."""
    kw = dict(scenario="stragglers", engine="async-gossip", devices=6,
              rounds=3, seed=0, samples_per_device=20, train_iters=4,
              div_tau=1, div_T=4, batch=5, solver_max_outer=2,
              solver_inner_steps=100, resolve_threshold=0.5,
              resolve_patience=4)
    eng_g = SimulationEngine(SimConfig(train_gather=True, **kw))
    eng_m = SimulationEngine(SimConfig(train_gather=False, **kw))
    rows_g = eng_g.run()
    rows_m = eng_m.run()
    canon = lambda rows: json.dumps(                       # noqa: E731
        [{k: v for k, v in r.items() if k not in NONDETERMINISTIC_FIELDS}
         for r in rows], default=float)
    assert canon(rows_g) == canon(rows_m)
    for k in eng_g.state.params:
        np.testing.assert_array_equal(
            np.asarray(eng_g.state.params[k]),
            np.asarray(eng_m.state.params[k]))
    np.testing.assert_array_equal(eng_g.state.eps_hat, eng_m.state.eps_hat)


def test_bucket_widths():
    from repro.sim.shard.pool import _bucket
    assert _bucket(1, 64) == 4
    assert _bucket(4, 64) == 4
    assert _bucket(5, 64) == 8
    assert _bucket(33, 64) == 64
    assert _bucket(50, 64) == 64
    assert _bucket(3, 2) == 2           # capped at the pool size


# ------------------------------------------------------ gossip topologies
def _topo_engine(topology, **kw):
    cfg = SimConfig(scenario="async-gossip", engine="async-gossip",
                    devices=8, rounds=2, seed=0, gossip_topology=topology,
                    samples_per_device=20, train_iters=4, div_tau=1,
                    div_T=4, batch=5, solver_max_outer=2,
                    solver_inner_steps=100, resolve_threshold=0.5,
                    resolve_patience=4, **kw)
    return SimulationEngine(cfg)


def test_ring_topology_pairs_are_ring_adjacent():
    eng = _topo_engine("ring")
    ring = list(eng.executor._ring)
    pos = {d: i for i, d in enumerate(ring)}
    rows = eng.run()
    n = len(ring)
    for r in rows:
        assert r["gossip_topology"] == "ring"
        flat = [d for pair in r["gossip"] for d in pair]
        assert len(flat) == len(set(flat))          # disjoint
        for i, j in r["gossip"]:
            assert (pos[j] - pos[i]) % n in (1, n - 1)


def test_k_regular_topology_edges_within_degree():
    eng = _topo_engine("k-regular", gossip_degree=4)
    ring = list(eng.executor._ring)
    pos = {d: i for i, d in enumerate(ring)}
    rows = eng.run()
    n = len(ring)
    for r in rows:
        assert r["gossip_topology"] == "k-regular"
        flat = [d for pair in r["gossip"] for d in pair]
        assert len(flat) == len(set(flat))
        for i, j in r["gossip"]:
            hop = min((pos[j] - pos[i]) % n, (pos[i] - pos[j]) % n)
            assert 1 <= hop <= 2                    # degree 4 -> 2 hops


def test_topology_deterministic_and_validated():
    a = _topo_engine("ring").run()
    b = _topo_engine("ring").run()
    assert [r["gossip"] for r in a] == [r["gossip"] for r in b]
    with pytest.raises(ValueError, match="gossip_topology"):
        _topo_engine("smallworld")


def test_uniform_topology_keeps_historical_stream():
    """Building the (unused) ring must not perturb 'uniform' runs: the
    gossip draws come from the same dedicated stream as before."""
    eng = _topo_engine("uniform")
    rng = np.random.default_rng(eng.cfg.seed + 3)
    a = eng.state.active_idx
    g = max(len(a) // 4, 1)
    perm = rng.permutation(a)
    expect = [[int(perm[2 * k]), int(perm[2 * k + 1])] for k in range(g)]
    rows = eng.run()
    assert rows[0]["gossip"] == expect


# --------------------------------------------------- golden parity (mesh)
def test_async_golden_matches_current_local_run():
    """Guards the committed async golden: the single-host LocalPool run
    (subset-gather default) must still produce it."""
    rows = SimulationEngine(SimConfig(**ASYNC_GOLDEN)).run()
    _assert_rows_match(_golden("async-gossip"), rows, "local-async")
    reasons = [r["resolve_reason"] for r in rows]
    assert "cold" in reasons and "staleness" in reasons


def test_mesh1_static_reproduces_golden():
    rows = SimulationEngine(SimConfig(mesh=1, **STATIC_GOLDEN)).run()
    _assert_rows_match(_golden("static"), rows, "mesh1-static")


def test_mesh1_async_reproduces_golden():
    rows = SimulationEngine(SimConfig(mesh=1, **ASYNC_GOLDEN)).run()
    _assert_rows_match(_golden("async-gossip"), rows, "mesh1-async")


def test_mesh8_emulated_reproduces_goldens():
    """Satellite acceptance: an emulated 8-shard mesh (8 host-platform
    devices forced BEFORE jax import, hence the subprocess) must
    reproduce the single-host goldens field-for-field for both the
    static (sync) and async-gossip scenarios, and keep the drifted
    client stack equal to the pool's in its sharding."""
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=8 "
            + os.environ.get("XLA_FLAGS", ""))
        import json
        import numpy as np
        from repro.sim.engine import SimConfig, SimulationEngine
        from repro.sim.metrics import NONDETERMINISTIC_FIELDS

        def check(golden_path, cfg_kw, tag):
            with open(golden_path) as f:
                golden = [json.loads(l) for l in f if l.strip()]
            rows = SimulationEngine(SimConfig(mesh=8, **cfg_kw)).run()
            assert len(rows) == len(golden), tag
            for g, r in zip(golden, rows):
                for k, v in g.items():
                    if k in NONDETERMINISTIC_FIELDS:
                        continue
                    ok = r[k] == v or (isinstance(v, float)
                                       and np.isnan(v)
                                       and np.isnan(r[k]))
                    assert ok, (tag, g["round"], k, v, r[k])
            print(tag, "OK", flush=True)

        check({os.path.join(GOLDEN_DIR, "sim_static.jsonl")!r},
              {STATIC_GOLDEN!r}, "mesh8-static")
        check({os.path.join(GOLDEN_DIR, "sim_async-gossip.jsonl")!r},
              {ASYNC_GOLDEN!r}, "mesh8-async")

        # drift writes changed rows into the 8-way sharded client stack:
        # after every tick it is the pool's stack, in its placed sharding
        import jax
        from repro.fl.client import stack_clients
        eng = SimulationEngine(SimConfig(mesh=8, **{DRIFT_RESTACK!r}))
        shardings = lambda c: jax.tree_util.tree_map(  # noqa: E731
            lambda a: a.sharding, c)
        placed = shardings(eng.state.clients)
        written = 0
        for t in range(eng.cfg.rounds):
            written += eng.step(t)["restack_rows"]
            eng.state.round = t + 1
            want = stack_clients(eng.state.pool)
            for a, b in zip(jax.tree_util.tree_leaves(eng.state.clients),
                            jax.tree_util.tree_leaves(want)):
                assert np.array_equal(np.asarray(a), np.asarray(b)), t
            assert shardings(eng.state.clients) == placed, t
        assert written > 0
        print("mesh8-drift-restack OK", flush=True)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          cwd=REPO_ROOT, capture_output=True, text=True,
                          timeout=1800)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "mesh8-static OK" in proc.stdout
    assert "mesh8-async OK" in proc.stdout
    assert "mesh8-drift-restack OK" in proc.stdout
