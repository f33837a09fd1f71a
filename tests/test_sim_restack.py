"""The client stack follows the pool: after every tick the placed stack
is bit for bit ``stack_clients(state.pool)`` in the sharding
``place_clients`` gave it, on both executors and both pool backends,
whether the restack wrote the changed devices' rows or the whole stack;
``restack_rows``/``restack_bytes`` count what was written, and the row
write compiles once."""
import jax
import numpy as np
import pytest

from repro.data.partition import make_device
from repro.fl.client import stack_clients
from repro.sim.engine import SimConfig, SimulationEngine
from repro.sim.shard.pool import ROW_BLOCK

SMOKE = dict(samples_per_device=8, train_iters=2, div_tau=1, div_T=2,
             batch=4, solver_max_outer=2, solver_inner_steps=120,
             resolve_threshold=10.0)
ROUNDS = 4
ENGINES = ("sync", "async-gossip")
POOLS = {"local": 0, "sharded": 1}
#: the scenarios whose events change a device's data, and the event
EVENTS = {"feature-drift": "feature_drift", "label-arrival": "labels"}


def _shardings(clients):
    return jax.tree_util.tree_map(lambda a: a.sharding, clients)


def _assert_is_stack_of_pool(eng):
    want = stack_clients(eng.state.pool)
    for got, ref in zip(jax.tree_util.tree_leaves(eng.state.clients),
                        jax.tree_util.tree_leaves(want)):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def _engine(scenario, engine="sync", pool="local", **kw):
    cfg = dict(dict(devices=8, rounds=ROUNDS, seed=1, verbose=False,
                    **SMOKE), **kw)
    return SimulationEngine(SimConfig(scenario=scenario, engine=engine,
                                      mesh=POOLS[pool], **cfg))


@pytest.fixture(scope="module")
def ticks(compiles):
    """Per (engine, pool, scenario): each tick's row, the row write's
    compiles in it, and whether the stack matched the pool in the
    sharding ``place_clients`` gave it."""
    cache = {}

    def get(engine, pool, scenario):
        if (engine, pool, scenario) not in cache:
            eng = _engine(scenario, engine, pool)
            placed = _shardings(eng.state.clients)
            out = []
            for t in range(ROUNDS):
                with compiles("set_client_rows") as compiled:
                    row = eng.step(t)
                eng.state.round = t + 1
                _assert_is_stack_of_pool(eng)
                out.append((row, compiled.n,
                            _shardings(eng.state.clients) == placed))
            eng.logger.close()
            cache[engine, pool, scenario] = out
        return cache[engine, pool, scenario]
    return get


@pytest.mark.parametrize("scenario", sorted(EVENTS))
@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("engine", ENGINES)
def test_stack_is_the_pool_after_every_tick(ticks, engine, pool,
                                            scenario):
    run = ticks(engine, pool, scenario)          # equality checked there
    assert all(same for _, _, same in run)
    assert any(row["restack_rows"] for row, _, _ in run)


@pytest.mark.parametrize("scenario", sorted(EVENTS))
@pytest.mark.parametrize("engine", ENGINES)
def test_restack_rows_count_the_devices_touched(ticks, engine, scenario):
    row_bytes = None
    for row, _, _ in ticks(engine, "local", scenario):
        touched = {e["device"] for e in row["events"]
                   if e.get("event") == EVENTS[scenario]}
        assert row["restack_rows"] == len(touched)
        if touched:
            row_bytes = row_bytes or \
                row["restack_bytes"] // row["restack_rows"]
            assert row["restack_bytes"] == row_bytes * len(touched)
        else:
            assert row["restack_bytes"] == 0
    assert row_bytes == 8 * (28 * 28 * 3 * 4 + 4 + 1 + 1 + 4) + 4


@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("engine", ENGINES)
def test_static_traffic_writes_no_row(engine, pool):
    eng = _engine("static", engine, pool, rounds=2)
    rows = eng.run()
    assert [(r["restack_rows"], r["restack_bytes"]) for r in rows] == \
        [(0, 0)] * 2
    assert eng.pool._row_write is None          # never even built


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_row_write_compiles_once_across_row_counts(ticks, pool):
    run = ticks("sync", pool, "label-arrival")
    counts = [row["restack_rows"] for row, _, _ in run]
    assert counts[0] and len(set(counts) - {0}) > 1, counts
    # the first write may find the program compiled by an earlier engine
    assert [n for _, n, _ in run] in ([1] + [0] * (ROUNDS - 1),
                                      [0] * ROUNDS)


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_rows_past_one_block_go_in_padded_blocks(pool, compiles):
    eng = _engine("static", pool=pool, devices=12)
    placed = _shardings(eng.state.clients)
    rows = list(range(ROW_BLOCK + 3))
    for j in rows:
        eng.drift_features(j, 0.5)
    with compiles("set_client_rows") as compiled:
        assert eng._restack() == (
            len(rows) * (8 * (28 * 28 * 3 * 4 + 10) + 4), len(rows))
    assert compiled.n <= 1                      # two blocks, one program
    assert not eng._dirty_clients
    _assert_is_stack_of_pool(eng)
    assert _shardings(eng.state.clients) == placed


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_longer_device_restacks_the_whole_pool(pool):
    eng = _engine("static", pool=pool, devices=6)
    placed = _shardings(eng.state.clients)
    eng.state.pool[2] = make_device("M//MM", 11, seed=5, labeled_ratio=1.0,
                                    rng=np.random.default_rng(0))
    eng._dirty_clients.add(2)
    assert eng._restack() == (6 * (11 * (28 * 28 * 3 * 4 + 10) + 4), 6)
    assert eng.state.clients.x.shape[1] == 11
    _assert_is_stack_of_pool(eng)
    assert _shardings(eng.state.clients) == placed
    assert eng.pool._row_write is None
