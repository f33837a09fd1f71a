"""Feature-drift scenario suite + budgeted divergence re-estimation:
the domain-interpolation data primitive, engine.drift_features, dirty-
pair tracking in NetworkState, the budget_pairs schedule, row-targeted
refresh parity on both pool backends, scenario-registry round-trip for
EVERY registered scenario, the new drift metrics fields through the
JSONL round-trip, and golden-parity spot checks that pre-drift
scenarios are untouched with the tracking compiled in.
"""
import json

import jax
import numpy as np
import pytest

from repro.data.digits import DOMAINS, render_images
from repro.data.partition import build_network, interpolate_features
from repro.fl.divergence import budget_pairs, update_divergences
from repro.sim.engine import SimConfig, SimulationEngine
from repro.sim.metrics import strip_nondeterministic
from repro.sim.scenarios import SCENARIOS

# lean settings: registry round-trip instantiates every scenario once
TINY = dict(samples_per_device=20, train_iters=4, div_tau=1, div_T=4,
            batch=5, solver_max_outer=2, solver_inner_steps=100,
            resolve_patience=4)
#: scenarios that only mutate device clocks — meaningful under async
CLOCK_SCENARIOS = {"async-gossip", "stragglers", "feature-drift-async"}

DRIFT = dict(scenario="feature-drift", devices=6, rounds=3, seed=0,
             feature_drift_p=0.9, feature_drift_step=0.4,
             resolve_threshold=0.05, **TINY)


def _canon(rows):
    return json.dumps(strip_nondeterministic(rows), default=float)


# ------------------------------------------------- data-layer primitive
def test_render_images_deterministic_and_aligned():
    labels = np.array([3, 1, 4, 1, 5], np.int32)
    a = render_images(labels, "MM", seed=42)
    b = render_images(labels, "MM", seed=42)
    assert a.shape == (5, 28, 28, 3) and a.dtype == np.float32
    np.testing.assert_array_equal(a, b)          # same seed, same styles
    c = render_images(labels, "MM", seed=43)
    assert not np.array_equal(a, c)


def test_interpolate_features_endpoints_and_payload():
    dev = build_network("M//MM", num_devices=2, samples_per_device=10,
                        seed=0)[0]
    alt = render_images(dev.true_labels, "U", seed=7)
    at0 = interpolate_features(dev, alt, 0.0)
    at1 = interpolate_features(dev, alt, 1.0)
    half = interpolate_features(dev, alt, 0.5)
    np.testing.assert_array_equal(at0.images, dev.images)
    np.testing.assert_allclose(at1.images, alt, atol=1e-6)
    np.testing.assert_allclose(half.images,
                               0.5 * dev.images + 0.5 * alt, atol=1e-6)
    for d in (at0, at1, half):                   # only features drift
        np.testing.assert_array_equal(d.labels, dev.labels)
        np.testing.assert_array_equal(d.labeled_mask, dev.labeled_mask)
        np.testing.assert_array_equal(d.true_labels, dev.true_labels)
    assert interpolate_features(dev, alt, 2.0).images == pytest.approx(
        at1.images)                              # mix clipped to [0, 1]
    with pytest.raises(ValueError, match="shape"):
        interpolate_features(dev, alt[:-1], 0.5)


# --------------------------------------------------- engine mutation API
def test_drift_features_caches_dirties_and_is_absolute():
    eng = SimulationEngine(SimConfig(scenario="static", devices=5,
                                     rounds=1, **TINY))
    st = eng.state
    base = st.pool[2].images.copy()
    dom = eng.drift_features(2, 0.5)
    assert dom in DOMAINS
    assert st.div_dirty[2, :].sum() == st.pool_size - 1   # row dirtied
    assert st.div_dirty[:, 2].sum() == st.pool_size - 1
    assert not st.div_dirty[2, 2]
    assert eng._dirty_clients == {2}             # its stack row is due
    drifted = st.pool[2].images.copy()
    assert not np.array_equal(drifted, base)
    # absolute mix: re-blending at the same mix reproduces, not compounds
    eng.drift_features(2, 0.5)
    np.testing.assert_array_equal(st.pool[2].images, drifted)
    # mix 0 restores the pristine original exactly
    eng.drift_features(2, 0.0)
    np.testing.assert_array_equal(st.pool[2].images, base)
    # the alt domain is cached on first call; later hints are ignored
    assert eng.drift_features(2, 0.3, domain="M") == dom


def test_drift_features_preserves_labels_revealed_after_first_drift():
    """Composing mutations: a label reveal BETWEEN two drift steps must
    survive the second re-blend (only features drift — the engine must
    carry the device's current label state, not the cached pristine
    one)."""
    eng = SimulationEngine(SimConfig(scenario="static", devices=5,
                                     rounds=1, **TINY))
    st = eng.state
    j = 2
    eng.drift_features(j, 0.3)
    before = st.pool[j].n_labeled
    eng.reveal_labels(j, 1.0, np.random.default_rng(0))
    revealed = st.pool[j].n_labeled
    assert revealed > before
    eng.drift_features(j, 0.6)
    assert st.pool[j].n_labeled == revealed     # reveal survives
    np.testing.assert_array_equal(
        st.pool[j].labels,
        np.where(st.pool[j].labeled_mask, st.pool[j].true_labels, -1))


def test_budget_pairs_stalest_first_and_truncation():
    tick = np.full((6, 6), -1, int)
    tick[0, 1] = tick[1, 0] = 5
    tick[2, 3] = tick[3, 2] = 1
    pairs = np.array([[0, 1], [2, 3], [4, 5]], np.int32)
    out = budget_pairs(pairs, tick, 0)           # unbounded, rank order
    assert out.tolist() == [[4, 5], [2, 3], [0, 1]]   # -1 < 1 < 5
    assert budget_pairs(pairs, tick, 2).tolist() == [[4, 5], [2, 3]]
    assert budget_pairs(np.zeros((0, 2)), tick, 4).shape == (0, 2)
    # ties break on (i, j): deterministic without RNG
    out = budget_pairs(np.array([[1, 4], [0, 2]]), np.full((6, 6), 3),
                       1)
    assert out.tolist() == [[0, 2]]


# ----------------------------------------- row-targeted refresh parity
@pytest.mark.parametrize("mesh", [0, 1])
def test_targeted_refresh_matches_full_path(mesh):
    eng = SimulationEngine(SimConfig(scenario="static", devices=6,
                                     rounds=1, mesh=mesh, **TINY))
    key = jax.random.PRNGKey(11)
    pairs = np.array([[0, 3], [1, 4], [3, 5]], np.int32)
    kw = dict(tau=1, T=4, batch=5, lr=0.01)
    ref = update_divergences(np.zeros((6, 6)), eng.state.clients, key,
                             pairs, **kw)
    out = update_divergences(np.zeros((6, 6)), eng.state.clients, key,
                             pairs, values_fn=eng.pool._targeted_values_fn(),
                             **kw)
    np.testing.assert_array_equal(out, ref)
    # the pool-level entry point applies the same values + EMA merge
    old = np.full((6, 6), 0.5)
    np.fill_diagonal(old, 0.0)
    merged = eng.pool.refresh_divergences(old, eng.state.clients, key,
                                          pairs, ema=1.0)
    np.testing.assert_allclose(merged, old)      # ema=1 keeps old values


@pytest.mark.parametrize("mesh", [0, 1])
def test_refresh_below_the_widths_run_compiles_nothing(mesh, compiles):
    """The last few dirty pairs of a drift reuse the programs of the
    refreshes before them: their keys, rows and lanes take widths
    already run, and each pair's value is the one it had there."""
    eng = SimulationEngine(SimConfig(scenario="static", devices=8,
                                     rounds=1, mesh=mesh, **TINY))
    ex, st = eng.executor, eng.state

    def refresh(pairs):
        return eng.pool.refresh_divergences(
            np.zeros((8, 8)), st.clients, None, pairs,
            keys=ex._pair_content_keys(pairs), h0=ex._refresh_h0())

    ii, jj = np.triu_indices(8, k=1)
    pairs = np.stack([ii, jj], axis=1).astype(np.int32)[:20]
    full = refresh(pairs)
    few = pairs[[3, 11, 17]]
    with compiles() as compiled:
        out = refresh(few)
    assert compiled.n == 0
    np.testing.assert_array_equal(out[few[:, 0], few[:, 1]],
                                  full[few[:, 0], few[:, 1]])


# ------------------------------------------- scenario registry round-trip
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_registry_round_trip_construct_and_tick(scenario):
    """Every registered scenario constructs and completes one tick under
    its natural engine (clock scenarios under async-gossip)."""
    engine = "async-gossip" if scenario in CLOCK_SCENARIOS else "sync"
    cfg = SimConfig(scenario=scenario, engine=engine, devices=5,
                    rounds=1, seed=0, **TINY)
    rows = SimulationEngine(cfg).run()
    assert len(rows) == 1
    r = rows[0]
    assert r["scenario"] == scenario and r["engine"] == engine
    assert r["resolved"] and r["resolve_reason"] == "cold"
    assert r["n_reestimated"] >= 0 and r["n_dirty_pairs"] >= 0


# --------------------------------------------- feature-drift end-to-end
def test_feature_drift_budget_respected_and_drift_resolves():
    cfg = SimConfig(**{**DRIFT, "div_budget": 4})
    rows = SimulationEngine(cfg).run()
    assert any(r["n_drifted"] > 0 for r in rows)
    assert any(r["n_reestimated"] > 0 for r in rows[1:])
    assert all(r["n_reestimated"] <= 4 for r in rows[1:])
    assert any(r["resolve_reason"] == "drift" for r in rows[1:]), \
        "sustained feature drift must trip the drift gate"
    # drift-triggered re-solves are warm continuations
    assert all(r["warm"] for r in rows[1:] if r["resolved"])


def test_feature_drift_deterministic_and_seed_sensitive():
    a = _canon(SimulationEngine(SimConfig(**DRIFT)).run())
    b = _canon(SimulationEngine(SimConfig(**DRIFT)).run())
    c = _canon(SimulationEngine(SimConfig(**{**DRIFT, "seed": 1})).run())
    assert a == b
    assert a != c


def test_feature_drift_jsonl_round_trip(tmp_path):
    out = str(tmp_path / "drift.jsonl")
    cfg = SimConfig(**{**DRIFT, "rounds": 2}, log_path=out)
    rows = SimulationEngine(cfg).run()
    from repro.sim.metrics import read_jsonl
    back = read_jsonl(out)
    assert strip_nondeterministic(back) == strip_nondeterministic(rows)
    for r in back:                    # drift fields survive the JSONL trip
        assert isinstance(r["n_drifted"], int)
        assert isinstance(r["n_dirty_pairs"], int)
        assert isinstance(r["n_reestimated"], int)
        for e in r["events"]:
            if e["event"] == "feature_drift":
                assert 0.0 < e["mix"] <= 1.0 and e["domain"] in DOMAINS


def test_all_refresh_mode_remeasures_every_pair():
    cfg = SimConfig(**{**DRIFT, "rounds": 2, "div_refresh": "all"})
    rows = SimulationEngine(cfg).run()
    n = cfg.devices
    # round 0's bootstrap already measured everything this tick; from
    # round 1 the naive policy re-measures all active pairs
    assert rows[0]["n_reestimated"] == 0
    assert rows[1]["n_reestimated"] == n * (n - 1) // 2
    with pytest.raises(ValueError, match="div_refresh"):
        SimulationEngine(SimConfig(**{**DRIFT, "div_refresh": "most"}))


# ----------------------------------------- content-addressed measurement
def test_content_keys_make_remeasurement_idempotent():
    """Under div_key_mode='content', re-measuring an UNCHANGED pair
    reproduces its value exactly, and the value is independent of which
    batch the scheduler put the pair in."""
    eng = SimulationEngine(SimConfig(scenario="static", devices=6,
                                     rounds=1, div_key_mode="content",
                                     **TINY))
    ex, st = eng.executor, eng.state
    pairs = np.array([[0, 3], [1, 4], [2, 5]], np.int32)
    kw = lambda p: dict(keys=ex._pair_content_keys(p),    # noqa: E731
                        h0=ex._refresh_h0())
    a = eng.pool.refresh_divergences(np.zeros((6, 6)), st.clients, None,
                                     pairs, **kw(pairs))
    b = eng.pool.refresh_divergences(np.zeros((6, 6)), st.clients, None,
                                     pairs, **kw(pairs))
    np.testing.assert_array_equal(a, b)          # idempotent re-measure
    solo = pairs[1:2]                            # different batch shape
    c = eng.pool.refresh_divergences(np.zeros((6, 6)), st.clients, None,
                                     solo, **kw(solo))
    assert c[1, 4] == a[1, 4]                    # batch-independent
    # keys are symmetric in the pair
    np.testing.assert_array_equal(
        np.asarray(ex._pair_content_keys(np.array([[4, 1]]))),
        np.asarray(ex._pair_content_keys(np.array([[1, 4]]))))


def test_content_mode_run_is_deterministic_and_distinct():
    kw = {**DRIFT, "div_key_mode": "content"}
    a = _canon(SimulationEngine(SimConfig(**kw)).run())
    b = _canon(SimulationEngine(SimConfig(**kw)).run())
    assert a == b
    assert a != _canon(SimulationEngine(SimConfig(**DRIFT)).run())
    with pytest.raises(ValueError, match="div_key_mode"):
        SimulationEngine(SimConfig(**{**DRIFT, "div_key_mode": "hash"}))


# ------------------------------------- pre-drift scenarios stay pinned
def test_tracking_is_inert_without_feature_drift():
    """With dirty-pair tracking compiled in, scenarios that never drift
    features emit all-zero drift fields and never spend refresh work
    (the full field-for-field golden pins live in test_sim.py /
    test_sim_shard.py; this asserts the mechanism that keeps them
    green)."""
    cfg = SimConfig(scenario="channel-drift", devices=5, rounds=2,
                    seed=0, **TINY)
    eng = SimulationEngine(cfg)
    rows = eng.run()
    assert all(r["n_drifted"] == 0 and r["n_dirty_pairs"] == 0
               and r["n_reestimated"] == 0 for r in rows)
    assert not eng.state.div_dirty.any()
