"""repro.sim: executor-layer coverage — SyncExecutor parity against
pre-refactor golden output, async-gossip execution (clocks, gossip,
staleness-gated re-solves), engine determinism, warm-started re-solves,
churn-robust re-seeding, and the transfer-path coverage that rides along
(pallas/xla parity, apply_transfer invariance, column_normalize rescue).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bounds import BoundTerms
from repro.core.energy import EnergyModel
from repro.core.problem import STLFProblem
from repro.core.solver import solve_stlf
from repro.fl.client import init_client_params, stack_clients
from repro.fl.divergence import update_divergences
from repro.fl.transfer import apply_transfer, column_normalize, \
    combine_models
from repro.sim.clock import DeviceClocks
from repro.sim.engine import SimConfig, SimulationEngine
from repro.sim.executors import EXECUTORS, get_executor
from repro.sim.metrics import NONDETERMINISTIC_FIELDS, \
    strip_nondeterministic
from repro.sim.scenarios import SCENARIOS

SMOKE = dict(samples_per_device=40, train_iters=8, div_tau=1, div_T=6,
             solver_max_outer=3, solver_inner_steps=200)
CLASSIC = ["channel-drift", "device-churn", "label-arrival", "static"]
GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")

# lean async settings: 64 devices stay CPU-affordable because gossip
# refreshes 4 pairs/tick instead of all 2016 upfront
ASYNC64 = dict(samples_per_device=20, train_iters=4, div_tau=1, div_T=4,
               batch=5, gossip_pairs=4, solver_max_outer=2,
               solver_inner_steps=120, resolve_threshold=0.5,
               resolve_patience=8)


def _run(scenario, devices=8, rounds=3, seed=0, **kw):
    cfg = SimConfig(scenario=scenario, devices=devices, rounds=rounds,
                    seed=seed, **{**SMOKE, **kw})
    return SimulationEngine(cfg).run()


# The golden-parity runs double as the smoke runs: one execution per
# scenario serves both tests.  reseed_on_rejoin is pinned off because the
# goldens were captured before churn-robust re-seeding existed (the one
# intentional, flag-gated behavior change of the executor refactor).
_PARITY_CACHE = {}


def _run_classic(scenario):
    if scenario not in _PARITY_CACHE:
        _PARITY_CACHE[scenario] = _run(scenario, reseed_on_rejoin=False)
    return _PARITY_CACHE[scenario]


def test_scenario_registry_complete():
    assert {"static", "channel-drift", "device-churn", "label-arrival",
            "async-gossip", "stragglers"} <= set(SCENARIOS)


def test_executor_registry():
    assert {"sync", "async-gossip"} <= set(EXECUTORS)
    assert get_executor("sync") is EXECUTORS["sync"]
    with pytest.raises(KeyError):
        get_executor("half-sync")


@pytest.mark.parametrize("scenario", CLASSIC)
def test_scenario_smoke_8_devices_3_rounds(scenario):
    rows = _run_classic(scenario)
    assert len(rows) == 3
    for r in rows:
        assert r["scenario"] == scenario
        assert r["engine"] == "sync"
        assert r["n_active"] >= 3
        assert 0 < r["n_trained"] <= r["n_active"]
        assert r["n_sources"] + r["n_targets"] == r["n_active"]
        assert r["n_sources"] >= 1
        assert r["energy"] >= 0.0
        assert 0.0 <= r["link_churn"] <= 1.0
        if r["n_targets"]:
            assert 0.0 <= r["mean_target_acc"] <= 1.0
    assert rows[0]["resolved"]                 # round 0 always solves
    assert rows[0]["resolved"] and not rows[0]["warm"]
    assert rows[0]["resolve_reason"] == "cold"


@pytest.mark.parametrize("scenario", CLASSIC)
def test_sync_parity_with_pre_refactor_golden(scenario):
    """The SyncExecutor must reproduce the pre-refactor engine's round
    metrics exactly (modulo the documented wall-clock fields; fields the
    refactor ADDED are allowed, fields that existed must match)."""
    with open(os.path.join(GOLDEN_DIR, f"sim_{scenario}.jsonl")) as f:
        golden = [json.loads(line) for line in f if line.strip()]
    rows = _run_classic(scenario)
    assert len(rows) == len(golden)
    for g, r in zip(golden, rows):
        for k, v in g.items():
            if k in NONDETERMINISTIC_FIELDS:
                continue
            ok = r[k] == v or (isinstance(v, float)
                               and np.isnan(v) and np.isnan(r[k]))
            assert ok, (scenario, g["round"], k, v, r[k])


def test_static_scenario_solves_once_under_high_threshold():
    # continued local training legitimately moves eps_hat (drift), so pin
    # the threshold high to isolate the gating logic itself
    rows = _run("static", resolve_threshold=10.0)
    assert [r["resolved"] for r in rows] == [True, False, False]
    assert [r["resolve_reason"] for r in rows] == ["cold", None, None]


def test_resolves_after_round_zero_are_warm():
    rows = _run("channel-drift", rounds=4)
    later = [r for r in rows[1:] if r["resolved"]]
    assert later, "drift scenario should trigger at least one re-solve"
    assert all(r["warm"] for r in later)


def _canon(rows):
    """NaN-aware comparable form: a tick with no targets (or no sources)
    reports a NaN mean accuracy, and NaN != NaN breaks dict equality;
    JSON renders every NaN alike."""
    return json.dumps(strip_nondeterministic(rows), default=float)


def test_engine_deterministic_per_seed():
    a = _canon(_run("channel-drift", devices=6, rounds=2))
    b = _canon(_run("channel-drift", devices=6, rounds=2))
    assert a == b


def test_engine_seed_changes_trajectory():
    a = strip_nondeterministic(_run("device-churn", devices=6, rounds=3,
                                    seed=0))
    b = strip_nondeterministic(_run("device-churn", devices=6, rounds=3,
                                    seed=1))
    assert a != b


def test_metrics_jsonl_written(tmp_path):
    out = str(tmp_path / "log.jsonl")
    cfg = SimConfig(scenario="static", devices=6, rounds=2,
                    log_path=out, **SMOKE)
    rows = SimulationEngine(cfg).run()
    from repro.sim.metrics import read_jsonl
    assert _canon(read_jsonl(out)) == _canon(rows)


# --------------------------------------------------------- device clocks
def test_clock_sampling_and_eligibility():
    rng = np.random.default_rng(0)
    clocks = DeviceClocks.sample(64, (1, 2, 4), rng)
    assert set(np.unique(clocks.period)) <= {1, 2, 4}
    assert np.all(clocks.phase < clocks.period)
    assert np.all(clocks.phase >= 0)
    # a device with period p fires exactly every p ticks
    fires = np.stack([clocks.eligible(t) for t in range(8)])   # (T, P)
    assert np.array_equal(fires.sum(axis=0) * clocks.period,
                          np.full(64, 8))
    # period-1 devices fire every tick
    assert fires[:, clocks.period == 1].all()


def test_clock_set_period_and_staleness():
    clocks = DeviceClocks(period=np.array([1, 2]),
                          phase=np.array([0, 1]),
                          last_train=np.array([-1, -1]))
    clocks.set_period(1, 5)
    assert clocks.period[1] == 5 and clocks.phase[1] == 1
    with pytest.raises(ValueError):
        clocks.set_period(0, 0)
    clocks.mark_trained(np.array([0]), 3)
    assert list(clocks.staleness(5)) == [2, 6]   # never-trained: t + 1
    with pytest.raises(ValueError):
        DeviceClocks.sample(4, (), np.random.default_rng(0))


# ----------------------------------------------------------- async-gossip
def _run_async(scenario="async-gossip", devices=8, rounds=6, seed=0, **kw):
    cfg = SimConfig(scenario=scenario, engine="async-gossip",
                    devices=devices, rounds=rounds, seed=seed,
                    **{**SMOKE, "resolve_threshold": 0.5,
                       "resolve_patience": 4, **kw})
    return SimulationEngine(cfg).run()


def test_async_gossip_smoke():
    rows = _run_async()
    assert len(rows) == 6
    total_trained = 0
    for r in rows:
        assert r["engine"] == "async-gossip"
        assert r["n_trained"] == len(r["trained"])
        assert set(r["trained"]) <= set(range(8))
        flat = [d for pair in r["gossip"] for d in pair]
        assert len(flat) == len(set(flat))       # disjoint meetings
        assert r["mean_staleness"] >= 0.0
        assert r["max_staleness"] >= r["mean_staleness"]
        total_trained += r["n_trained"]
    # heterogeneous clocks: strictly fewer device-steps than sync lockstep
    assert total_trained < 8 * 6
    assert rows[0]["resolve_reason"] == "cold"


def test_async_deterministic_per_seed_and_seed_sensitivity():
    a = _canon(_run_async("stragglers", rounds=4))
    b = _canon(_run_async("stragglers", rounds=4))
    c = _canon(_run_async("stragglers", rounds=4, seed=1))
    assert a == b
    assert a != c


def test_stragglers_scenario_slows_clocks_and_recovery_restores():
    cfg = SimConfig(scenario="stragglers", engine="async-gossip",
                    devices=8, rounds=2, straggler_p_swap=1.0, **SMOKE)
    eng = SimulationEngine(cfg)
    assert (eng.state.clocks.period >=
            cfg.straggler_period).sum() >= 1
    orig = dict(eng.scenario._orig_period)    # sampled pre-straggle rates
    rows = eng.run()
    recovers = [e for r in rows for e in r["events"]
                if e["event"] == "recover"]
    assert recovers, "p_swap=1.0 must rotate the straggler set"
    for e in recovers:
        if e["device"] in orig:               # initial-set stragglers
            assert e["period"] == orig[e["device"]]


def test_async_64_devices_40_ticks_staleness_resolve():
    """Acceptance: 64 devices x 40 ticks on CPU, with the staleness bound
    (not drift) triggering at least one warm re-solve."""
    cfg = SimConfig(scenario="async-gossip", engine="async-gossip",
                    devices=64, rounds=40, seed=0, **ASYNC64)
    rows = SimulationEngine(cfg).run()
    assert len(rows) == 40
    assert all(r["n_active"] == 64 for r in rows)
    # local clocks: every tick trains a strict subset, never the lockstep
    # (a tick CAN train nobody if no labeled device's clock fires)
    assert all(r["n_trained"] < 64 for r in rows)
    assert sum(r["n_trained"] for r in rows) > 0
    # gossip refreshes pair divergences incrementally
    assert all(len(r["gossip"]) == 4 for r in rows)
    stale = [r for r in rows if r["resolve_reason"] == "staleness"]
    assert stale, "expected at least one staleness-triggered re-solve"
    assert all(r["warm"] for r in stale)
    assert all(r["solve_age"] >= cfg.resolve_patience for r in stale)


# ------------------------------------------------- churn-robust re-seeding
def test_rejoining_device_reseeded_from_source_mixture():
    # 8 devices: the smallest smoke network whose round-0 solve picks
    # targets (at 6 every device stays a source)
    cfg = SimConfig(scenario="static", devices=8, rounds=1, **SMOKE)
    eng = SimulationEngine(cfg)
    eng.step(0)                                   # install a solution
    st = eng.state
    j = int(st.active_idx[-1])
    eng.set_active(j, False)
    before = {k: np.asarray(v).copy() for k, v in st.params.items()}
    eng.set_active(j, True)
    # expected: consensus source mixture of the solved assignment,
    # applied to the params as they were at rejoin time
    sa = np.asarray(st.solve_active)
    tgts = sa[st.psi[sa] == 1.0]
    assert len(tgts), "smoke config should produce at least one target"
    w = st.alpha[:, tgts].mean(axis=1)
    w = w / w.sum()
    for k, v in st.params.items():
        got = np.asarray(v)[j]
        expect = np.tensordot(w.astype(np.float32), before[k],
                              axes=(0, 0))
        np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-5)
    assert any(not np.allclose(np.asarray(st.params[k])[j],
                               before[k][j]) for k in st.params)


def test_rejoin_keeps_stale_params_when_reseed_disabled():
    cfg = SimConfig(scenario="static", devices=6, rounds=1,
                    reseed_on_rejoin=False, **SMOKE)
    eng = SimulationEngine(cfg)
    eng.step(0)
    st = eng.state
    j = int(st.active_idx[-1])
    stale = {k: np.asarray(v)[j].copy() for k, v in st.params.items()}
    eng.set_active(j, False)
    eng.set_active(j, True)
    for k in st.params:
        np.testing.assert_array_equal(np.asarray(st.params[k])[j],
                                      stale[k])


# --------------------------------------------------- link_thresh plumbing
def test_link_thresh_threads_through_metrics():
    rows = _run("static", devices=8, rounds=1, link_thresh=10.0)
    assert rows[0]["transmissions"] == 0
    assert rows[0]["link_churn"] == 0.0
    assert rows[0]["links"] == []
    base = _run("static", devices=8, rounds=1)
    assert base[0]["transmissions"] == len(base[0]["links"]) > 0
    assert {t for _, t in base[0]["links"]} <= set(base[0]["targets"])


# --------------------------------------------- unknown-divergence prior
def test_unknown_pairs_get_pessimistic_prior_in_solver_view():
    cfg = SimConfig(scenario="async-gossip", engine="async-gossip",
                    devices=5, rounds=1, div_prior=1.2, **SMOKE)
    eng = SimulationEngine(cfg)
    st = eng.state
    a = st.active_idx
    st.div_known[:] = np.eye(st.pool_size, dtype=bool)
    st.div_known[a[0], a[1]] = st.div_known[a[1], a[0]] = True
    st.div_hat[:] = 0.0
    st.div_hat[a[0], a[1]] = st.div_hat[a[1], a[0]] = 0.3
    view = eng._divergence_view()
    assert view[a[0], a[1]] == 0.3             # measured value kept
    assert view[a[0], a[2]] == 1.2             # unknown -> prior
    assert np.all(np.diag(view) == 0.0)        # self-pairs never primed
    eng.cfg.div_prior = 0.0                    # <= 0 disables
    assert eng._divergence_view()[a[0], a[2]] == 0.0
    # sync executors measure every active pair before any solve, so
    # their view is the raw matrix and the prior plays no role
    cfg2 = SimConfig(scenario="static", devices=5, rounds=1,
                     div_prior=1.2, **SMOKE)
    eng2 = SimulationEngine(cfg2)
    assert eng2._divergence_view() is eng2.state.div_hat


# ------------------------------------------------ divergence EMA merging
def test_update_divergences_ema_blends_old_and_fresh():
    from repro.data.partition import build_network
    clients = stack_clients(build_network("M//MM", num_devices=4,
                                          samples_per_device=20, seed=0))
    key = jax.random.PRNGKey(0)
    pairs = np.array([[0, 1], [2, 3]], np.int32)
    old = np.full((4, 4), 0.8)
    np.fill_diagonal(old, 0.0)
    kw = dict(tau=1, T=4, batch=5, lr=0.01)
    fresh = update_divergences(np.zeros((4, 4)), clients, key, pairs, **kw)
    kept = update_divergences(old, clients, key, pairs, ema=1.0, **kw)
    np.testing.assert_allclose(kept, old)
    half = update_divergences(old, clients, key, pairs, ema=0.5, **kw)
    for i, j in pairs:
        assert half[i, j] == pytest.approx(0.5 * old[i, j]
                                           + 0.5 * fresh[i, j])
        assert half[j, i] == half[i, j]
    # per-pair weights: first pair replaced, second kept
    mixed = update_divergences(old, clients, key, pairs,
                               ema=np.array([0.0, 1.0]), **kw)
    assert mixed[0, 1] == pytest.approx(fresh[0, 1])
    assert mixed[2, 3] == pytest.approx(old[2, 3])


# --------------------------------------------------------- warm re-solves
def _problem(n, rng, energy):
    eps = rng.uniform(0.05, 1.0, n)
    div = rng.uniform(0.1, 1.5, (n, n))
    div = 0.5 * (div + div.T)
    np.fill_diagonal(div, 0.0)
    return STLFProblem(BoundTerms(eps, np.full(n, 5000), div), energy)


def test_warm_started_resolve_uses_fewer_outer_iters():
    rng = np.random.default_rng(0)
    n = 8
    em = EnergyModel.sample(n, rng)
    prob = _problem(n, rng, em)
    first = solve_stlf(prob, max_outer=16, inner_steps=400)
    drifted = STLFProblem(prob.bounds, em.drift(rng, 0.15))
    cold = solve_stlf(drifted, max_outer=16, inner_steps=400)
    warm = solve_stlf(drifted, max_outer=16, inner_steps=400,
                      warm_start=first)
    assert warm.outer_iters < cold.outer_iters
    assert warm.converged


def test_warm_start_accepts_foreign_size_result():
    """Churn remap path: a warm result for a different nvars falls back to
    start_from instead of crashing."""
    rng = np.random.default_rng(1)
    em5 = EnergyModel.sample(5, rng)
    small = solve_stlf(_problem(5, rng, em5), max_outer=2, inner_steps=100)
    em6 = EnergyModel.sample(6, rng)
    prob6 = _problem(6, rng, em6)
    shell = type(small)(
        psi=np.zeros(6), alpha=np.zeros((6, 6)),
        psi_relaxed=np.full(6, 0.5), alpha_relaxed=np.full((6, 6), 0.1),
        objective_trace=[], objective_parts={}, converged=False,
        outer_iters=0, x_relaxed=small.x_relaxed)     # wrong-size x
    res = solve_stlf(prob6, max_outer=2, inner_steps=100, warm_start=shell)
    assert res.psi.shape == (6,)


# ------------------------------------------------------------ transfer
def test_combine_models_pallas_matches_xla():
    params = init_client_params(4, jax.random.PRNGKey(0),
                                shared_init=False)
    rng = np.random.default_rng(0)
    alpha = rng.random((4, 4)).astype(np.float32)
    out_x = combine_models(params, alpha, impl="xla")
    out_p = combine_models(params, alpha, impl="pallas")
    for k in out_x:
        np.testing.assert_allclose(np.asarray(out_p[k]),
                                   np.asarray(out_x[k]),
                                   rtol=2e-5, atol=2e-5)


def test_apply_transfer_source_rows_untouched_targets_exact_mixture():
    params = init_client_params(5, jax.random.PRNGKey(3),
                                shared_init=False)
    psi = np.array([0.0, 0.0, 0.0, 1.0, 1.0])
    rng = np.random.default_rng(2)
    alpha = np.zeros((5, 5))
    for j in (3, 4):
        w = rng.random(3)
        alpha[:3, j] = w / w.sum()
    out = apply_transfer(params, jnp.asarray(alpha), jnp.asarray(psi))
    for k in params:
        got = np.asarray(out[k])
        src = np.asarray(params[k])
        # sources untouched
        np.testing.assert_allclose(got[:3], src[:3], atol=1e-6)
        # targets are the exact alpha-mixtures
        for j in (3, 4):
            expect = np.tensordot(alpha[:3, j], src[:3], axes=(0, 0))
            np.testing.assert_allclose(got[j], expect, rtol=1e-5,
                                       atol=1e-5)


def test_column_normalize_dead_column_picks_min_energy_source():
    psi = np.array([0.0, 0.0, 0.0, 1.0])
    alpha = np.zeros((4, 4))                   # dead target column
    K = np.zeros((4, 4))
    K[:, 3] = [5.0, 0.1, 3.0, 0.0]             # source 1 cheapest
    out = column_normalize(alpha, psi, energy_K=K)
    assert out[1, 3] == 1.0 and out[:, 3].sum() == 1.0


def test_column_normalize_dead_column_falls_back_to_lowest_eps():
    psi = np.array([0.0, 0.0, 0.0, 1.0])
    alpha = np.zeros((4, 4))
    eps = np.array([0.5, 0.9, 0.05, 1.0])      # source 2 best
    out = column_normalize(alpha, psi, eps_hat=eps)
    assert out[2, 3] == 1.0


def test_column_normalize_dead_column_default_first_source():
    psi = np.array([0.0, 0.0, 1.0])
    out = column_normalize(np.zeros((3, 3)), psi)
    assert out[0, 2] == 1.0
