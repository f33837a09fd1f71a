"""Trace subsystem: recorder semantics, every phase a profiler span on
the recorder's clock, phases that tile the round, the counters it
explains the host with, the layer names the device programs carry, and
golden parity with tracing enabled."""
import dataclasses
import glob
import json
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from repro.data.partition import build_network
from repro.fl import cnn
from repro.fl.client import init_client_params, stack_clients
from repro.fl.divergence import pairwise_divergence_values
from repro.fl.transfer import apply_transfer
from repro.kernels.alpha_combine.ops import alpha_combine_slab
from repro.sim.engine import SimConfig, SimulationEngine
from repro.sim.metrics import (NONDETERMINISTIC_FIELDS, RoundRecord,
                               strip_nondeterministic)
from repro.sim.trace import events as events_mod
from repro.sim.trace.events import (PHASES, SPAN_PREFIX, WALL_FIELDS,
                                    TraceRecorder)
from repro.sim.training import network_step

#: small-but-real engine settings (the LEAN profile of benchmarks)
SMOKE = dict(samples_per_device=8, train_iters=2, div_tau=1, div_T=2,
             batch=4, solver_max_outer=2, solver_inner_steps=120,
             resolve_threshold=10.0)
ROUNDS = 4
#: under feature-drift, seed 1's first 4 rounds at N=8 hold first
#: drifts, a repeat drift and a round without any
SEED = 1
ENGINES = ("sync", "async-gossip")
RUNS = [(e, s) for e in ENGINES for s in ("static", "feature-drift")]


def _rec(trace=True, trace_path=None, mesh=0):
    cfg = types.SimpleNamespace(trace=trace, trace_path=trace_path,
                                mesh=mesh)
    return TraceRecorder(cfg)


def _clock(monkeypatch, *stamps_ns):
    """Feed the recorder these ``perf_counter_ns`` readings in turn."""
    it = iter(stamps_ns)
    monkeypatch.setattr(events_mod, "time", types.SimpleNamespace(
        perf_counter_ns=lambda: next(it)))


def _sim_spans(trace_dir):
    """(start_ns, duration_ns, name) of every ``sim.`` host span in the
    profile under ``trace_dir``."""
    from jax.profiler import ProfileData
    out = []
    for path in glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True):
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                out.extend((ev.start_ns, ev.duration_ns, ev.name)
                           for line in plane.lines for ev in line.events
                           if ev.name.startswith(SPAN_PREFIX))
    return sorted(out)


def _profiled_run(engine, scenario, trace_dir):
    """ROUNDS traced ticks at N=8 under the profiler: the recorder's
    events, the rows, each step's (start, end) ns, the rows and bytes
    handed to ``write_client_rows`` per tick, and the profile's ``sim.``
    spans."""
    eng = SimulationEngine(SimConfig(
        scenario=scenario, engine=engine, devices=8, rounds=ROUNDS,
        seed=SEED, trace=True, verbose=False, **SMOKE))
    written = {}
    write = eng.pool.write_client_rows

    def spy(clients, rows, block):
        written[eng.trace.tick] = (len(rows), sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(block)))
        return write(clients, rows, block)

    eng.pool.write_client_rows = spy
    rows, steps = [], {}
    jax.profiler.start_trace(str(trace_dir))
    try:
        for t in range(ROUNDS):
            t0 = time.perf_counter_ns()
            rows.append(eng.step(t))
            steps[t] = (t0, time.perf_counter_ns())
    finally:
        jax.profiler.stop_trace()
    eng.logger.close()
    eng.trace.close()
    return types.SimpleNamespace(
        events=sorted(eng.trace.events, key=lambda e: e["t0_ns"]),
        rows=rows, steps=steps, written=written,
        spans=_sim_spans(trace_dir), rendered=len(eng._drift_alt))


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    cache = {}

    def get(engine, scenario):
        if (engine, scenario) not in cache:
            cache[engine, scenario] = _profiled_run(
                engine, scenario, tmp_path_factory.mktemp("xplane"))
        return cache[engine, scenario]
    return get


# ------------------------------------------------------------- recorder
def test_recorder_disabled_is_noop():
    before = list(monitoring.get_event_duration_listeners())
    rec = _rec(trace=False)
    assert rec.start("train") is None
    rec.stop(None, block=object(), n_devices=8)   # no block, no record
    assert rec.events == []
    assert rec.tick_wall_fields() == {}       # fields keep 0.0 defaults
    assert rec.n_compiled == 0
    assert list(monitoring.get_event_duration_listeners()) == before


def test_recorder_accumulates_and_pops_per_tick(monkeypatch):
    _clock(monkeypatch, 0, 500_000_000, 600_000_000, 850_000_000,
           900_000_000, 1_000_000_000)
    rec = _rec()
    rec.begin_tick(0)
    for phase, ctx in (("train", {"n_devices": 8}),
                       ("train", {"n_devices": 8}),
                       ("divergence", {"n_pairs": 28})):
        rec.stop(rec.start(phase), **ctx)
    rec.close()
    fields = rec.tick_wall_fields()
    assert fields["train_wall_s"] == pytest.approx(0.75)
    assert fields["div_wall_s"] == pytest.approx(0.1)
    assert fields["transfer_wall_s"] == 0.0
    # popped: the next tick starts clean
    assert rec.tick_wall_fields()["train_wall_s"] == 0.0
    assert [e["phase"] for e in rec.events] == ["train", "train",
                                                "divergence"]
    assert [e["t0_ns"] for e in rec.events] == [0, 600_000_000,
                                                900_000_000]
    assert rec.events[2]["n_pairs"] == 28 and rec.events[0]["tick"] == 0


def test_recorder_stop_timing_and_trace_file(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    rec = _rec(trace_path=path)
    span = rec.start("eval")
    assert span is not None
    rec.stop(span, n_devices=4)
    rec.close()
    with open(path) as f:
        back = [json.loads(line) for line in f]
    assert len(back) == 1 and back[0]["phase"] == "eval"
    assert back[0]["seconds"] >= 0.0 and back[0]["n_devices"] == 4
    assert isinstance(back[0]["t0_ns"], int)
    assert back == rec.events


def test_compile_listener_lives_until_close():
    before = len(monitoring.get_event_duration_listeners())
    rec = _rec()
    assert len(monitoring.get_event_duration_listeners()) == before + 1
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
    assert rec.n_compiled >= 1
    rec.close()
    rec.close()                               # closing twice is harmless
    assert len(monitoring.get_event_duration_listeners()) == before


def test_every_wall_field_phase_is_a_known_phase():
    assert set(WALL_FIELDS) < set(PHASES)
    assert {"solve", "log"} <= set(PHASES) - set(WALL_FIELDS)
    fields = {f.name for f in dataclasses.fields(RoundRecord)}
    assert set(WALL_FIELDS.values()) | {"n_compiled"} <= \
        fields & set(NONDETERMINISTIC_FIELDS)


def test_engine_cfg_validation():
    with pytest.raises(ValueError):
        SimConfig(devices=4, rounds=1, trace_path="x.jsonl")  # no trace
    with pytest.raises(ValueError):
        SimConfig(devices=4, rounds=1, train_gather_floor=0)


# ------------------------------------------------- spans on one clock
@pytest.mark.parametrize("engine,scenario", RUNS)
def test_each_event_is_one_profiler_span_on_the_same_clock(
        profiled, engine, scenario):
    run = profiled(engine, scenario)
    assert [name for _, _, name in run.spans] == \
        [SPAN_PREFIX + e["phase"] for e in run.events]
    offsets = []
    for (start, dur, _), e in zip(run.spans, run.events):
        assert abs(dur / 1e9 - e["seconds"]) <= 0.05 * e["seconds"] + 1e-4
        offsets.append(start - e["t0_ns"])
    assert max(offsets) - min(offsets) < 1_000_000


@pytest.mark.parametrize("engine,scenario", RUNS)
def test_phases_tile_the_round(profiled, engine, scenario):
    """Per round the phases are disjoint and inside the step, and the
    phases before the row is built fit in its ``wall_time_s``."""
    run = profiled(engine, scenario)
    for row in run.rows:
        t = row["round"]
        evs = [e for e in run.events if e["tick"] == t]
        end, hi = run.steps[t]
        for e in evs:
            assert e["t0_ns"] >= end, (t, e["phase"])
            end = e["t0_ns"] + round(e["seconds"] * 1e9)
        assert end <= hi
        body = sum(e["seconds"] for e in evs if e["phase"] != "log")
        assert body <= row["wall_time_s"]
        assert {"scenario", "train", "refresh_select", "eval",
                "log"} <= {e["phase"] for e in evs}


def test_log_phase_is_recorded_once_per_round_after_the_row(profiled):
    for engine in ENGINES:
        run = profiled(engine, "static")
        for row in run.rows:
            evs = [e for e in run.events if e["tick"] == row["round"]]
            assert [e["phase"] for e in evs].count("log") == 1
            assert evs[-1]["phase"] == "log"


def test_tracing_off_opens_no_span_and_registers_no_listener(tmp_path):
    before = list(monitoring.get_event_duration_listeners())
    eng = SimulationEngine(SimConfig(
        scenario="feature-drift", devices=8, rounds=2, seed=SEED,
        verbose=False, **SMOKE))
    jax.profiler.start_trace(str(tmp_path))
    try:
        rows = [eng.step(t) for t in range(2)]
    finally:
        jax.profiler.stop_trace()
    assert _sim_spans(tmp_path) == []
    assert list(monitoring.get_event_duration_listeners()) == before
    assert eng.trace.events == []
    assert all(r["n_compiled"] == 0 and r["scenario_wall_s"] == 0.0
               for r in rows)


# ------------------------------------------------------------ counters
@pytest.mark.parametrize("engine", ENGINES)
def test_restack_bytes_is_the_placed_stack(profiled, engine):
    """The restack writes only the drifted devices' rows into the
    placed stack, and ``restack_bytes`` counts those rows' bytes."""
    run = profiled(engine, "feature-drift")
    got = {r["round"]: (r["restack_rows"], r["restack_bytes"])
           for r in run.rows}
    assert got == {t: run.written.get(t, (0, 0)) for t in got}
    assert any(b for _, b in got.values())
    assert not all(b for _, b in got.values())
    # a row's size follows from its shapes: 8 samples of 28x28x3
    # float32, four int32/bool entries a sample, and one int32 count
    row_bytes = 8 * (28 * 28 * 3 * 4 + 4 + 1 + 1 + 4) + 4
    for row in run.rows:
        assert bool(row["restack_bytes"]) == (row["n_drifted"] > 0)
        assert row["restack_rows"] == row["n_drifted"]
        assert row["restack_bytes"] == row["restack_rows"] * row_bytes


@pytest.mark.parametrize("engine", ENGINES)
def test_n_rendered_counts_first_drift_renders(profiled, engine):
    run = profiled(engine, "feature-drift")
    seen, repeats = set(), 0
    for row in run.rows:
        devs = {e["device"] for e in row["events"]
                if e.get("event") == "feature_drift"}
        assert row["n_rendered"] == len(devs - seen)
        repeats += len(devs & seen)
        seen |= devs
    assert repeats, "no device drifted twice: nothing tells them apart"
    assert sum(r["n_rendered"] for r in run.rows) == run.rendered > 0


def test_n_compiled_counts_the_compiles_of_the_round():
    # shapes no other test compiles, so round 0 compiles its programs
    rows = SimulationEngine(SimConfig(
        scenario="static", devices=7, rounds=3, seed=0, trace=True,
        verbose=False, **dict(SMOKE, samples_per_device=11))).run()
    assert rows[0]["n_compiled"] > 0
    assert [r["n_compiled"] for r in rows[1:]] == [0, 0]


# ------------------------------------------- layer names in the programs
def _clients(n=2, samples=4):
    return stack_clients(build_network("M//MM", num_devices=n,
                                       samples_per_device=samples, seed=0))


def _lower_train():
    clients = _clients()
    return network_step.lower(
        init_client_params(2, jax.random.PRNGKey(0)), clients,
        jax.random.PRNGKey(1), np.ones(2, bool), iters=2, batch=4,
        lr=0.01)


def _lower_pairs():
    h0 = cnn.cnn_init(jax.random.PRNGKey(0), num_classes=2)
    idx = jnp.zeros(1, jnp.int32)
    return pairwise_divergence_values.lower(
        h0, _clients(), idx, idx + 1, jax.random.split(
            jax.random.PRNGKey(1), 1), tau=1, T=2, batch=4, lr=0.01)


def _lower_combine_xla():
    params = init_client_params(3, jax.random.PRNGKey(0))
    return jax.jit(apply_transfer).lower(params, jnp.eye(3),
                                         jnp.ones(3))


def _lower_combine_pallas():
    return jax.jit(alpha_combine_slab).lower(jnp.ones((8, 256)),
                                             jnp.ones((8, 8)))


@pytest.mark.parametrize("scope,lower", [
    ("train_scan", _lower_train), ("pair_scan", _lower_pairs),
    ("transfer_combine", _lower_combine_xla),
    ("transfer_combine", _lower_combine_pallas)],
    ids=["train", "pairs", "combine-xla", "combine-pallas"])
def test_device_programs_carry_their_layer_name(scope, lower):
    assert f"/{scope}/" in lower().as_text(debug_info=True)


# ---------------------------------------------------------- golden parity
def test_trace_on_off_golden_parity(tmp_path):
    """The recorder consumes no PRNG: deterministic fields are
    byte-identical with tracing on vs off (sync engine)."""
    kw = dict(scenario="channel-drift", devices=6, rounds=2, seed=0,
              verbose=False, **SMOKE)
    runs = []
    for trace in (False, True):
        eng = SimulationEngine(SimConfig(trace=trace, **kw))
        rows = eng.run()
        runs.append(strip_nondeterministic(rows))
        if trace:
            assert eng.trace.events, "tracing on but no events recorded"
            walls = [r for r in rows if r["train_wall_s"] > 0]
            assert walls, "traced run has no train wall clocks"
    assert json.dumps(runs[0], sort_keys=True) == \
        json.dumps(runs[1], sort_keys=True)


# ------------------------------------------------------- bench artifacts
def test_save_rows_stamped_and_load_rows_tolerant(tmp_path, monkeypatch):
    import benchmarks.common as common
    monkeypatch.setattr(common, "RESULTS_DIR", str(tmp_path))
    rows = [{"n": 8, "s": 1.0}]
    common.save_rows("probe", rows)
    path = str(tmp_path / "probe.json")
    with open(path) as f:
        obj = json.load(f)
    assert obj["benchmark"] == "probe" and obj["rows"] == rows
    fp = obj["host_fingerprint"]
    assert fp["jax"] and fp["device_count"] >= 1
    assert common.load_rows(path) == rows
    # old bare-list artifacts still load
    bare = str(tmp_path / "old.json")
    with open(bare, "w") as f:
        json.dump(rows, f)
    assert common.load_rows(bare) == rows
