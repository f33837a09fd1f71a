"""One run of one cell: set-up, the measured window, the correctness
check, the result line.

Set-up builds ``SimulationEngine(SimConfig(...))`` from the cell's
configuration and traffic files with the run's seed, and drives the
warm-up rounds the traffic names through ``engine.step`` (the call the
window makes), keeping what the correctness check needs.  The window
then drives ``engine.step`` round after round until ``seconds`` have
passed; the round running at the deadline finishes and counts.  Each
round is timed on the harness's clock around ``step``; every round ends
in a host sync (the accuracies come back as numpy).  With ``trace`` the
program's phase recorder is on (it blocks at the end of each phase), the
profiler records the window, and the harness annotates each round and
each call into a layer.  After the window the device's peak memory is
read, the engine is freed, and the plain reference follows the warm-up
rounds (``replay``).
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Dict, List, Optional

from harness import device as devlib
from harness import flops as flopslib
from harness import profile as proflib
from harness import replay
from harness.spec import Cell, metric_readers, reference_module, \
    sim_config_kwargs

#: layer calls the traced run annotates: (object attribute path, span)
SPANS = (("pool.train", "train"),
         ("pool.update_divergences", "divergence"),
         ("pool.refresh_divergences", "divergence"),
         ("pool.transfer", "transfer"), ("pool.accuracies", "eval"),
         ("pool.place_clients", "restack"),
         ("executor._run_solve", "solve"),
         ("scenario.step", "scenario"), ("logger.log", "log"))

@dataclasses.dataclass
class Window:
    """What a per-layer metric reader gets: the window's rounds, the
    program's phase events in it, the device trace's summary, the run's
    simulator settings, the chip's peaks and the client's costs
    (``costs(sim)`` of the configuration's reference file)."""
    rounds: List[dict]              # {"tick", "wall", "row"}
    seconds: float
    phases: Dict[int, Dict[str, float]]
    profile: Optional[proflib.Summary]
    sim: dict
    peaks: Optional[dict]
    costs: Optional[dict] = None

    @property
    def ticks(self) -> List[int]:
        return [r["tick"] for r in self.rounds]

    def phase_total(self, *names: str) -> float:
        return sum(self.phases.get(t, {}).get(n, 0.0)
                   for t in self.ticks for n in names)

    def model_flops(self) -> float:
        return sum(flopslib.round_flops(r["row"], self.sim, self.costs)
                   for r in self.rounds)


def _annotate(engine, jax):
    """Wrap each layer call in a profiler span; returns an undo."""
    undo = []
    for path, span in SPANS:
        owner_name, attr = path.split(".")
        owner = getattr(engine, owner_name)
        fn = getattr(owner, attr, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _span="bench." + span, **kw):
            with jax.profiler.TraceAnnotation(_span):
                return _fn(*a, **kw)

        setattr(owner, attr, wrapped)
        undo.append((owner, attr))
    return lambda: [owner.__dict__.pop(attr) for owner, attr in undo]


def _phases(events, ticks) -> Dict[int, Dict[str, float]]:
    want = set(ticks)
    out: Dict[int, Dict[str, float]] = {}
    for ev in events:
        if ev["tick"] in want:
            d = out.setdefault(ev["tick"], {})
            d[ev["phase"]] = d.get(ev["phase"], 0.0) + ev["seconds"]
    return out


def _p90(values: List[float]) -> float:
    """Nearest-rank 90th percentile."""
    s = sorted(values)
    return s[max(0, math.ceil(0.9 * len(s)) - 1)]


def _finite_row(row: dict) -> bool:
    for key, count in (("mean_target_acc", "n_targets"),
                       ("mean_source_acc", "n_sources")):
        v = row[key]
        if row[count] and not (math.isfinite(v) and 0.0 <= v <= 1.0):
            return False
    return True


class GcClock:
    """Seconds the interpreter's garbage collector runs while installed
    (``gc.callbacks``), to tell a collection from other host stalls."""

    def __init__(self):
        self.seconds = 0.0
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t0

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def _slow_rounds(rounds: List[dict], factor: float = 2.0) -> List[str]:
    """One line per round slower than ``factor`` times the median: its
    wall, the program's own round wall (which leaves out the log's
    fsync), the solve's wall and the collector's seconds."""
    med = statistics.median(r["wall"] for r in rounds)
    return [f"tick {r['tick']}: wall {r['wall']:.6f} s, program "
            f"{r['row']['wall_time_s']:.6f} s, solve "
            f"{r['row']['solver_wall_s']:.6f} s "
            f"({r['row']['resolve_reason']}), gc {r['gc']:.6f} s"
            for r in rounds if r["wall"] > factor * med]


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        *, chip: bool = True) -> int:
    """One run; prints the result line and returns the exit code.
    ``chip=False`` skips the look for a TPU, the compile cache and the
    peaks (for tests on the CPU); nothing else changes."""
    import jax
    if chip:
        dev = devlib.require_tpu(jax, cell.chips)
        cache = devlib.enable_compile_cache(jax, cell.root)
        with open(os.path.join(cell.root, "bench", "peaks.json")) as fh:
            peaks_all = json.load(fh)["devices"]
        if dev.device_kind not in peaks_all:
            print(f"bench: no peaks for device kind {dev.device_kind!r} "
                  f"in bench/peaks.json", file=sys.stderr)
            return 3
        peaks = peaks_all[dev.device_kind]
    else:
        dev, cache, peaks = jax.devices()[0], None, None
    scratch = tempfile.mkdtemp(prefix="bench-")
    stats = devlib.CompileStats().__enter__()
    try:
        engine, cap, end, sim = setup(
            cell, seed, trace, os.path.join(scratch, "rounds.jsonl"))
        jax.effects_barrier()
        setup_s = time.perf_counter() - t_start
        c0 = stats.snapshot()
        log(f"set-up {setup_s:.3f} s ({len(cap.ticks)} warm-up rounds); "
            f"compile cache {cache}; compile so far {c0[0]:.3f} s over "
            f"{c0[1]} programs, hits {c0[2]}, misses {c0[3]}")

        undo = None
        trace_dir = os.path.join(scratch, "trace")
        if trace:
            undo = _annotate(engine, jax)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        rounds: List[dict] = []
        t = engine.state.round
        w0 = time.perf_counter()
        deadline = w0 + seconds
        with jax.profiler.TraceAnnotation(proflib.WINDOW_SPAN), \
                GcClock() as gc_clock:
            while True:
                with jax.profiler.TraceAnnotation(
                        f"{proflib.ROUND_SPAN} {t}"):
                    g0 = gc_clock.seconds
                    r0 = time.perf_counter()
                    row = engine.step(t)
                    engine.state.round = t + 1
                    r1 = time.perf_counter()
                rounds.append({"tick": t, "wall": r1 - r0, "row": row,
                               "gc": gc_clock.seconds - g0})
                t += 1
                if r1 >= deadline:
                    break
        window_s = r1 - w0
        if trace:
            jax.profiler.stop_trace()
            undo()
        c1 = stats.snapshot()
        in_window = [b - a for a, b in zip(c0, c1)]
        memory_peak = devlib.memory_peak_bytes(jax)
        walls = [r["wall"] for r in rounds]
        log(f"window {window_s:.6f} s, {len(rounds)} rounds (ticks "
            f"{rounds[0]['tick']}..{rounds[-1]['tick']}); compiles in the "
            f"window: {in_window[1]} programs, {in_window[0]:.6f} s, cache "
            f"hits {in_window[2]}, misses {in_window[3]}")
        p90 = _p90(walls)
        reest = [r["row"]["n_reestimated"] for r in rounds]
        log(f"round walls: median {statistics.median(walls):.6f} s, p90 "
            f"{p90:.6f} s, max {max(walls):.6f} s, rounds beyond p90 "
            f"{sum(w > p90 for w in walls)}; re-solves "
            f"{sum(bool(r['row']['resolved']) for r in rounds)}; pairs "
            f"re-estimated a round {min(reest)}..{max(reest)}; collector "
            f"{gc_clock.seconds:.6f} s")
        for line in _slow_rounds(rounds):
            log("slow round " + line)

        win = Window(rounds=rounds, seconds=window_s,
                     phases=_phases(engine.trace.events,
                                    [r["tick"] for r in rounds]),
                     profile=None, sim=sim, peaks=peaks,
                     costs=reference_module(cell).costs(sim))
        failed = sum(not _finite_row(r["row"]) for r in rounds)
        engine.logger.close()
        cap.engine = None
        del engine, row
        gc.collect()

        result = {"correct": None, "attempted": len(rounds),
                  "failed": failed, "metrics": {}}
        if trace:
            ops, spans = proflib.load(trace_dir)
            win.profile = proflib.summarize(ops, spans)
            starts = [e[0] for chip_ops in ops for e in chip_ops]
            log(f"trace: {sum(map(len, ops))} device ops on {len(ops)} "
                f"chips from {min(starts, default=0):.0f} to "
                f"{max(starts, default=0):.0f} ns; {len(spans)} host "
                f"spans; window span {win.profile.window_ns:.0f} ns")
            traced_walls = statistics.median(walls)
            log(f"traced round wall median {traced_walls:.6f} s (compare "
                f"the untraced run's median for the tracing overhead); "
                f"device busy {win.profile.busy_ns / 1e9:.6f} s of "
                f"{win.profile.window_ns / 1e9:.6f} s")
            for name, read in metric_readers(cell).items():
                unit = next(m["unit"] for m in cell.per_layer
                            if m["name"] == name)
                value = read(win)
                if value is not None:
                    result["metrics"][name] = {"value": value,
                                               "unit": unit}
            result["breakdown"] = proflib.breakdown(win.profile)
        else:
            e2e = {"round_s": window_s / len(rounds), "round_p90_s": p90,
                   "setup_s": setup_s}
            for m in cell.end_to_end:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}

        # ---- the correctness check, after the window and the free
        r0 = time.perf_counter()
        checks = check(cell, seed, sim, cap.ticks, end)
        log(f"reference check {time.perf_counter() - r0:.3f} s")
        correct = failed == 0 and all(
            c["value"] <= c["limit"] for c in checks.values())
        result["correct"] = bool(correct)
        dblock = devlib.device_block(jax, dev)
        dblock["memory_peak_bytes"] = memory_peak
        if trace:
            dblock["busy_s"] = win.profile.busy_ns / 1e9
            dblock["window_s"] = win.profile.window_ns / 1e9
        result["device"] = dblock
        result["checks"] = checks
        for name, c in checks.items():
            print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
                  file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
        return 0
    finally:
        stats.__exit__(None, None, None)
        shutil.rmtree(scratch, ignore_errors=True)


def setup(cell: Cell, seed: int, trace: bool, log_path: Optional[str]):
    """Build the engine and drive the warm-up rounds under a capture,
    then the warm-up calls the traffic names.  Returns (engine, capture,
    the program's end state, the run's SimConfig as a dict)."""
    from repro.sim.engine import SimConfig, SimulationEngine
    from harness.capture import Capture
    cfg = SimConfig(**sim_config_kwargs(cell), seed=seed, rounds=1 << 30,
                    trace=trace, log_path=log_path)
    engine = SimulationEngine(cfg)
    cap = Capture(engine)
    for t in range(int(cell.traffic["warmup_rounds"])):
        cap.step(t)
    end = cap.finish()
    for call in cell.traffic.get("warm_extra", []):
        _warm_extra(engine, call)
    return engine, cap, end, dataclasses.asdict(cfg)


def _warm_extra(engine, call: dict):
    """Compile programs the window may use that the warm-up rounds need
    not have reached, through the program's own calls; results are
    dropped and the state is left as it was.

      warm_solve     one warm re-solve of the current network
      refresh_rows   the budgeted drift refresh at full budget, once for
                     each width in ``rows`` of the compact row gather
                     (the program buckets the rows a refresh touches to
                     a power of two; which buckets a round needs depends
                     on which pairs are stalest)
    """
    import numpy as np
    st, cfg = engine.state, engine.cfg
    kind = call["call"]
    if kind == "warm_solve":
        engine._solve(st.active_idx)
    elif kind == "refresh_rows":
        ex = engine.executor
        a = st.active_idx
        budget = len(a) if cfg.div_budget < 0 else cfg.div_budget
        for r in (r for r in call["rows"] if r <= len(a)):
            # the first ``budget`` pairs over r devices touch all r
            ii, jj = np.triu_indices(r, k=1)
            pairs = np.stack([a[ii], a[jj]], axis=1)[:budget]
            pairs = pairs.astype(np.int32)
            engine.pool.refresh_divergences(
                np.array(st.div_hat), st.clients, None, pairs,
                ema=np.zeros(len(pairs)),
                keys=ex._pair_content_keys(pairs), h0=ex._refresh_h0())
    else:
        raise ValueError(f"unknown warm-up call {kind!r}")
    # drop any phase time the call left in the program's recorder
    engine.trace.tick_wall_fields()


def check(cell: Cell, seed: int, sim: dict, ticks, end: dict,
          control: bool = False) -> Dict[str, dict]:
    """The numbers compared, each with its limit: the program's answers
    against the float32 reference, or with ``control`` the bfloat16
    reference's in the program's place."""
    import jax.numpy as jnp
    ref = reference_module(cell)
    pairs = replay.sample_pairs(ticks, seed,
                                int(cell.traffic["check_pairs_per_call"]))
    classes = int(cell.config["model"]["classes"])
    ref_side = replay.follow(
        replay.Follower(ref, sim, seed, jnp.float32, classes), ticks, pairs)
    if control:
        side = replay.follow(
            replay.Follower(ref, sim, seed, jnp.bfloat16, classes), ticks,
            pairs)
    else:
        side = replay.program_side(ticks, end, pairs)
    got = replay.compare(side, ref_side, ticks)
    if not control:
        got["solve_faults"] = replay.solve_faults(ticks,
                                                  sim["link_thresh"])
    for name in sorted(set(got) - set(cell.limits)):
        log(f"check: {name} {got[name]!r} (read, not compared)")
    vals = list(ref_side["div"].values())
    log(f"check{' (control)' if control else ''}: {len(ticks)} warm-up "
        f"rounds, {len(pairs)} sampled pairs (reference d from "
        f"{min(vals, default=0):.4f} to {max(vals, default=0):.4f}), "
        f"{sum(len(t['row']['targets']) for t in ticks)} target-rounds")
    return {name: {"value": got[name], "limit": cell.limits[name]}
            for name in cell.limits if name in got}
