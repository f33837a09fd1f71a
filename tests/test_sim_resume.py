"""Crash-consistent checkpoint/resume + fault injection.

The contract under test: an interrupted-then-resumed run reproduces the
uninterrupted run's metrics FIELD-FOR-FIELD (modulo the documented
wall-clock/provenance fields) — for both executors and both pool
backends — and the fault-injection layer's failures are recovered, not
fatal, and replay identically across a resume.
"""
import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from repro.sim.engine import SimConfig, SimulationEngine
from repro.sim.faults import FaultInjector, PoolFaultError, with_retry
from repro.sim.metrics import read_jsonl, strip_nondeterministic
from repro.sim.snapshot import restore_run, save_run

SMOKE = dict(samples_per_device=40, train_iters=8, div_tau=1, div_T=6,
             solver_max_outer=3, solver_inner_steps=200)


def _canon(rows):
    """NaN-tolerant comparable form of a stripped row list."""
    return json.dumps(strip_nondeterministic(rows), sort_keys=True)


def _roundtrip(tmp_path, rounds=5, cut=2, **kw):
    """Run uninterrupted; run to ``cut`` rounds with checkpointing; run
    again with resume=True to the full horizon.  Returns (ref rows,
    resumed rows)."""
    ref = SimulationEngine(SimConfig(
        rounds=rounds, log_path=str(tmp_path / "ref.jsonl"),
        **SMOKE, **kw)).run()
    ck = str(tmp_path / "ck")
    SimulationEngine(SimConfig(
        rounds=cut, log_path=str(tmp_path / "res.jsonl"),
        checkpoint_every=1, ckpt_dir=ck, **SMOKE, **kw)).run()
    rows = SimulationEngine(SimConfig(
        rounds=rounds, log_path=str(tmp_path / "res.jsonl"),
        checkpoint_every=1, ckpt_dir=ck, resume=True,
        **SMOKE, **kw)).run()
    return ref, rows


# --------------------------------------------------- bit-for-bit resume
def test_sync_resume_matches_uninterrupted(tmp_path):
    ref, rows = _roundtrip(tmp_path, scenario="device-churn",
                           devices=6, seed=3)
    assert _canon(ref) == _canon(rows)
    assert all(r["resume_count"] == 1 for r in rows[2:])
    # the stitched on-disk log matches the uninterrupted one too
    assert _canon(read_jsonl(str(tmp_path / "ref.jsonl"))) == \
        _canon(read_jsonl(str(tmp_path / "res.jsonl")))


def test_async_faulty_resume_matches_uninterrupted(tmp_path):
    """Async executor + fault injection: clock/gossip RNG streams and
    the fault schedule all resume mid-stream."""
    ref, rows = _roundtrip(tmp_path, scenario="faulty",
                           engine="async-gossip", devices=8, seed=4,
                           fault_crash_p=0.5, fault_op_p=0.5,
                           fault_gossip_drop_p=0.5)
    assert _canon(ref) == _canon(rows)
    assert sum(r["n_faults"] for r in rows) > 0


def test_feature_drift_resume_matches_uninterrupted(tmp_path):
    """Dirty-pair tracking + the drift base caches survive a resume."""
    ref, rows = _roundtrip(tmp_path, scenario="feature-drift",
                           devices=6, seed=4, feature_drift_p=0.8)
    assert _canon(ref) == _canon(rows)
    assert sum(r["n_drifted"] for r in ref) > 0


def test_sharded_faulty_resume_and_shard_recovery(tmp_path):
    """ShardedPool (mesh=1): shard loss is detected and recovered via
    the churn/reseed path instead of dying, and the resumed trajectory
    still matches the uninterrupted one."""
    ref, rows = _roundtrip(tmp_path, scenario="faulty", devices=6,
                           seed=4, mesh=1, fault_shard_p=0.7,
                           fault_crash_p=0.0)
    assert _canon(ref) == _canon(rows)
    assert sum(r["n_recovered"] for r in rows) > 0


# --------------------------------------------------- state round-trip
def test_network_state_checkpoint_roundtrip(tmp_path):
    cfg = SimConfig(scenario="feature-drift", devices=6, rounds=2,
                    seed=5, feature_drift_p=1.0, ckpt_dir=str(tmp_path),
                    **SMOKE)
    eng = SimulationEngine(cfg)
    eng.run()
    eng.state.round = 2
    save_run(eng, 2)

    cfg2 = SimConfig(scenario="feature-drift", devices=6, rounds=2,
                     seed=5, feature_drift_p=1.0,
                     ckpt_dir=str(tmp_path), resume=True, **SMOKE)
    eng2 = SimulationEngine(cfg2)
    a, b = eng.state, eng2.state
    assert b.round == 2
    assert np.array_equal(a.active, b.active)
    assert np.array_equal(a.eps_hat, b.eps_hat)
    assert np.array_equal(a.div_hat, b.div_hat)
    assert np.array_equal(a.div_known, b.div_known)
    # dirty-pair tracking survives exactly
    assert np.array_equal(a.div_dirty, b.div_dirty)
    assert np.array_equal(a.div_tick, b.div_tick)
    assert np.array_equal(a.psi, b.psi)
    assert np.allclose(a.alpha, b.alpha, rtol=0, atol=0)
    assert np.array_equal(np.asarray(a.energy.K), np.asarray(b.energy.K))
    import jax
    for x, y in zip(jax.tree_util.tree_leaves(a.params),
                    jax.tree_util.tree_leaves(b.params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    for j in range(a.pool_size):
        assert np.array_equal(a.pool[j].images, b.pool[j].images)
        assert np.array_equal(a.pool[j].labels, b.pool[j].labels)
    # solver warm state
    assert (a.solver is None) == (b.solver is None)
    if a.solver is not None:
        assert np.array_equal(a.solver.psi_relaxed, b.solver.psi_relaxed)
        assert np.array_equal(a.solve_active, b.solve_active)
    # feature-drift caches rebuilt to the same content
    assert set(eng._drift_base) == set(eng2._drift_base)
    for j in eng._drift_base:
        assert eng._drift_domain[j] == eng2._drift_domain[j]
        assert np.array_equal(eng._drift_alt[j], eng2._drift_alt[j])
        assert np.array_equal(eng._drift_base[j].images,
                              eng2._drift_base[j].images)
    # scenario + engine RNG streams restored to the same position
    assert eng.scenario.rng.bit_generator.state == \
        eng2.scenario.rng.bit_generator.state
    assert eng2._resume_count == 1


def test_drifted_device_restored_then_drifts_again(tmp_path):
    """A device that drifted before the checkpoint comes back in the
    restored stack, drifts again, and its rows are written into that
    stack: after every resumed tick it is the pool's stack."""
    import jax
    from repro.fl.client import stack_clients
    kw = dict(scenario="feature-drift", devices=6, seed=4,
              feature_drift_p=0.8, ckpt_dir=str(tmp_path), **SMOKE)
    SimulationEngine(SimConfig(rounds=2, checkpoint_every=1, **kw)).run()
    eng = SimulationEngine(SimConfig(rounds=5, resume=True, **kw))
    drifted = set(eng._drift_base)
    assert drifted and not eng._dirty_clients
    again = set()
    for t in range(eng.state.round, 5):
        row = eng.step(t)
        eng.state.round = t + 1
        touched = {e["device"] for e in row["events"]}
        assert row["restack_rows"] == len(touched)
        again |= touched & drifted
        want = stack_clients(eng.state.pool)
        for got, ref in zip(jax.tree_util.tree_leaves(eng.state.clients),
                            jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(ref))
    eng.logger.close()
    assert again, "no device drifted on both sides of the resume"


def test_resume_cfg_mismatch_raises(tmp_path):
    cfg = SimConfig(scenario="static", devices=6, rounds=1, seed=0,
                    ckpt_dir=str(tmp_path), checkpoint_every=1, **SMOKE)
    SimulationEngine(cfg).run()
    bad = dict(SMOKE, div_T=7)
    with pytest.raises(ValueError, match="div_T"):
        SimulationEngine(SimConfig(
            scenario="static", devices=6, rounds=2, seed=0,
            ckpt_dir=str(tmp_path), resume=True, **bad))
    # a larger horizon is fine — that's what resume is for
    eng = SimulationEngine(SimConfig(
        scenario="static", devices=6, rounds=3, seed=0,
        ckpt_dir=str(tmp_path), resume=True, **SMOKE))
    assert eng.state.round == 1


def test_resume_empty_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        SimulationEngine(SimConfig(
            scenario="static", devices=6, rounds=1, seed=0,
            ckpt_dir=str(tmp_path / "nothing"), resume=True, **SMOKE))


# ------------------------------------------------------- true SIGKILL
def test_kill_after_and_cli_resume(tmp_path):
    """A REAL hard kill: ``--kill-after`` SIGKILLs the process after
    checkpointing; ``--resume`` completes the run and the log matches
    the uninterrupted reference field-for-field."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    base = [sys.executable, "-m", "repro.sim.run", "--scenario",
            "static", "--devices", "6", "--rounds", "4", "--samples",
            "40", "--train-iters", "8", "--div-T", "6",
            "--solver-max-outer", "3", "--solver-inner-steps", "200",
            "--quiet"]
    ref = str(tmp_path / "ref.jsonl")
    out = str(tmp_path / "out.jsonl")
    subprocess.run(base + ["--out", ref], env=env, check=True)
    killed = subprocess.run(
        base + ["--out", out, "--checkpoint-every", "2",
                "--kill-after", "1"], env=env)
    assert killed.returncode == -signal.SIGKILL
    subprocess.run(base + ["--out", out, "--checkpoint-every", "2",
                           "--resume"], env=env, check=True)
    assert _canon(read_jsonl(ref)) == _canon(read_jsonl(out))


# ------------------------------------------------- fault-layer units
def test_with_retry_bounded():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise PoolFaultError("transient")
        return "ok"

    assert with_retry(flaky, retries=3) == "ok"
    assert len(calls) == 3
    with pytest.raises(PoolFaultError):
        with_retry(lambda: (_ for _ in ()).throw(PoolFaultError("x")),
                   retries=2)


def test_fault_injector_state_roundtrip():
    cfg = SimConfig(scenario="faulty", devices=8, rounds=1,
                    fault_crash_p=1.0, fault_op_p=1.0, **SMOKE)
    inj = FaultInjector(cfg, np.random.default_rng(7))
    inj.down = {3: 9}
    inj.pending_op_failures = 2
    state = json.loads(json.dumps(inj.state_dict()))   # JSON-safe
    inj2 = FaultInjector(cfg, np.random.default_rng(0))
    inj2.load_state_dict(state)
    assert inj2.down == {3: 9}
    assert inj2.pending_op_failures == 2
    assert inj.rng.random() == inj2.rng.random()       # same stream


# ------------------------------------------------- config validation
@pytest.mark.parametrize("bad,match", [
    (dict(devices=0), "devices"),
    (dict(rounds=-1), "rounds"),
    (dict(div_budget=-2), "div_budget"),
    (dict(div_refresh="sometimes"), "div_refresh"),
    (dict(div_key_mode="hashed"), "div_key_mode"),
    (dict(gossip_topology="mesh"), "gossip_topology"),
    (dict(checkpoint_every=0, ckpt_dir="x"), "checkpoint_every"),
    (dict(checkpoint_every=2), "ckpt_dir"),
    (dict(resume=True), "ckpt_dir"),
    (dict(ckpt_keep=0), "ckpt_keep"),
    (dict(fault_crash_p=1.5), "fault_crash_p"),
    (dict(fault_retries=-1), "fault_retries"),
])
def test_simconfig_rejects_bad_values(bad, match):
    with pytest.raises(ValueError, match=match):
        SimConfig(**bad)
