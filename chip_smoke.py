#!/usr/bin/env python3
"""Drive the simulator once on a TPU and check what it produces.

    python3 chip_smoke.py               # one chip: phases a, b, c
    python3 chip_smoke.py --four-chips  # four chips: --mesh 4 vs --mesh 1

Run it from the root of a checkout.  Every phase calls the simulator's
command-line entry point, ``repro.sim.run.main``, in this one process (a
chip serves one process at a time) and reads back the JSONL metrics log
the phase wrote under ``--out-dir`` (default ``results/chip_smoke/``).
The client model is the paper's CNN at its published widths (48,158
parameters) on the default data (100 samples of 28x28x3 per device).

One chip:
  a  sync ``static``, 64 devices, 3 rounds, single-host pool (XLA
     transfer).  Round 0 estimates all 2,016 pair divergences and runs
     the cold solve.
  b  the same run with ``--mesh 1``: the sharded pool, whose transfer is
     the compiled Pallas ``alpha_combine`` kernel.
  c  ``async-gossip``, 256 devices, 5 ticks.
Four chips (``--four-chips``), and nothing else:
  mesh4  ``async-gossip`` with the pool sharded over 4 chips
  mesh1  the same run on a one-chip pool mesh

Checks, each of which fails the script: the platform is a TPU; every
round's accuracies are finite and in [0, 1]; every run installs targets
and transmits in some round; the compared runs take the same solve
decisions (target set and link set) in every round.  Earlier lines give
per-phase walls, compile seconds, persistent-cache hits and the pool and
transfer path; the last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "results", "chip_smoke")

#: the run every phase shares: seed 0, the paper's M//MM split setting
BASE = ["--seed", "0", "--setting", "M//MM", "--trace", "--quiet"]
SYNC64 = ["--scenario", "static", "--devices", "64", "--rounds", "3"]
#: async phases gossip over the seeded ring, every device meeting a ring
#: neighbour each tick, and re-solve on staleness after 2 ticks.  Gossip
#: measures only the pairs that meet, and the solve prices unmeasured
#: pairs at the pessimistic prior, so links form between pairs that have
#: met; on the ring those pairs meet again, and a target pulls its
#: source's model (a transmission) within a few ticks
ASYNC = ["--engine", "async-gossip", "--scenario", "async-gossip",
         "--gossip-topology", "ring", "--resolve-patience", "2"]


def async_run(devices, ticks):
    return ASYNC + ["--devices", str(devices), "--rounds", str(ticks),
                    "--gossip-pairs", str(devices // 2)]


#: (name, argv) of each phase, and (run, reference) pairs to compare
ONE_CHIP = [("a", SYNC64), ("b", SYNC64 + ["--mesh", "1"]),
            ("c", async_run(256, 5))]
ONE_CHIP_COMPARE = [("b", "a")]
FOUR_N = 1024
FOUR_CHIP = [("mesh4", async_run(FOUR_N, 5) + ["--mesh", "4"]),
             ("mesh1", async_run(FOUR_N, 5) + ["--mesh", "1"])]
FOUR_CHIP_COMPARE = [("mesh4", "mesh1")]

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


def fail(msg):
    """A check did not hold: exit non-zero with the reason."""
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


class CompileStats:
    """Backend compile seconds (a persistent-cache hit counts its load)
    and cache hits and misses, from JAX's monitoring events."""

    def __init__(self):
        self.secs = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0

    def on_duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.secs += secs
            self.programs += 1

    def on_event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1
        elif event == CACHE_MISS:
            self.misses += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self.on_duration)
        jax.monitoring.unregister_event_listener(self.on_event)

    def snapshot(self):
        return (self.secs, self.programs, self.hits, self.misses)


def require_tpu(jax):
    """The device JAX found; fails unless it is a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU, but JAX's first device is "
             f"{dev.platform} ({dev.device_kind})")
    return dev


def run_phase(name, argv, stats, out_dir):
    """One ``repro.sim.run.main`` call; returns its logged rows."""
    from repro.sim import run
    from repro.sim.metrics import read_jsonl
    out = os.path.join(out_dir, f"{name}.jsonl")
    before = stats.snapshot()
    t0 = time.perf_counter()
    rc = run.main(BASE + argv + ["--out", out])
    wall = time.perf_counter() - t0
    if rc != 0:
        fail(f"phase {name}: repro.sim.run exited {rc}")
    after = stats.snapshot()
    secs, programs, hits, misses = (y - x for x, y in zip(before, after))
    print(f"[chip_smoke] phase {name}: wall {wall:.3f} s, compile "
          f"{secs:.3f} s over {programs} programs, cache hits {hits}, "
          f"misses {misses}; argv {' '.join(argv)}", flush=True)
    return read_jsonl(out)


def check_rows(name, rows):
    """Accuracies finite in [0, 1]; targets and transmissions in some
    round (so the transfer ran)."""
    if not rows:
        fail(f"phase {name}: no rounds logged")
    for r in rows:
        for key, count in (("mean_target_acc", "n_targets"),
                           ("mean_source_acc", "n_sources")):
            v = r[key]
            if r[count] == 0:
                continue            # NaN by definition: no such devices
            if not (math.isfinite(v) and 0.0 <= v <= 1.0):
                fail(f"phase {name} round {r['round']}: {key}={v}")
    if not any(r["n_targets"] > 0 for r in rows):
        fail(f"phase {name}: no round installed a target")
    if not any(r["transmissions"] > 0 for r in rows):
        fail(f"phase {name}: no round transmitted a model")
    walls = {k: round(sum(r[k] for r in rows), 6) for k in (
        "train_wall_s", "div_wall_s", "transfer_wall_s", "eval_wall_s",
        "solver_wall_s")}
    print(f"[chip_smoke] phase {name}: targets per round "
          f"{[r['n_targets'] for r in rows]}, transmissions "
          f"{[r['transmissions'] for r in rows]}, phase walls {walls}",
          flush=True)


def largest_difference(rows, ref):
    """(|difference|, field, round) of the numeric field that differs
    most between two runs, wall clocks excluded; 0.0 where equal."""
    from repro.sim.metrics import strip_nondeterministic
    best = (0.0, None, None)
    for r, g in zip(strip_nondeterministic(rows),
                    strip_nondeterministic(ref)):
        for k, v in r.items():
            w = g[k]
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            if math.isnan(v) and math.isnan(w):
                continue
            d = abs(v - w) if not (math.isnan(v) or math.isnan(w)) \
                else math.inf
            if d > best[0]:
                best = (d, k, r["round"])
    return best


def compare(name, rows, ref_name, ref):
    """Same targets and links in every round; print the largest field
    difference and whether the runs matched exactly."""
    from repro.sim.metrics import strip_nondeterministic
    if len(rows) != len(ref):
        fail(f"{name} logged {len(rows)} rounds, {ref_name} "
             f"{len(ref)}")
    for r, g in zip(rows, ref):
        for key in ("targets", "links"):
            if r[key] != g[key]:
                fail(f"{name} vs {ref_name} round {r['round']}: "
                     f"{key} differ: {r[key]} != {g[key]}")
    exact = json.dumps(strip_nondeterministic(rows), default=float) == \
        json.dumps(strip_nondeterministic(ref), default=float)
    diff, field, rnd = largest_difference(rows, ref)
    print(f"[chip_smoke] {name} vs {ref_name}: targets and links agree in "
          f"all {len(rows)} rounds; exact match {exact}; largest field "
          f"difference {diff!r} ({field}, round {rnd})", flush=True)


def transfer_path(n_devices, mesh_shards):
    """Compile the sharded pool's transfer program at the run's shapes
    and report whether the Pallas kernel is in it as a TPU custom call
    (interpret mode would lower to plain XLA ops instead)."""
    import jax
    import jax.numpy as jnp
    from repro.fl.client import init_client_params
    from repro.sim.shard import make_pool_mesh
    from repro.sim.shard.ops import build_transfer
    params = init_client_params(n_devices, jax.random.PRNGKey(0),
                                shared_init=False)
    fn = build_transfer(make_pool_mesh(mesh_shards))
    text = fn.lower(params, jnp.zeros((n_devices, n_devices), jnp.float32),
                    jnp.zeros((n_devices,), jnp.float32)).compile().as_text()
    return "tpu_custom_call" in text


def pool_placement(n_shards):
    """(pool mesh size, device ids holding the client stack, device ids
    holding the parameter stack after one sharded training step) for a
    small sharded pool: a real pool mesh spreads both over every
    shard's chip instead of keeping them on the default device."""
    import jax
    from repro.sim.engine import SimConfig, SimulationEngine
    eng = SimulationEngine(SimConfig(
        scenario="async-gossip", engine="async-gossip",
        devices=4 * n_shards, rounds=1, mesh=n_shards, verbose=False))
    st = eng.state
    params, _, _ = eng.pool.train(st.params, st.clients,
                                  jax.random.PRNGKey(0), st.active)

    def ids(tree):
        return sorted({s.device.id for leaf in jax.tree_util.tree_leaves(
            tree) for s in leaf.addressable_shards})

    return eng.pool.mesh.devices.size, ids(st.clients), ids(params)


def run(phases, comparisons, stats, out_dir, *, four_chips=False):
    """Every phase, its checks, then the comparisons; returns the rows
    by phase name."""
    rows = {}
    for name, argv in phases:
        rows[name] = run_phase(name, argv, stats, out_dir)
        check_rows(name, rows[name])
    for name, ref_name in comparisons:
        compare(name, rows[name], ref_name, rows[ref_name])
    if four_chips:
        size, data_ids, param_ids = pool_placement(4)
        print(f"[chip_smoke] pool mesh spans {size} devices; the client "
              f"stack sits on device ids {data_ids}, the trained "
              f"parameter stack on {param_ids}", flush=True)
        if size != 4 or len(data_ids) != 4 or len(param_ids) != 4:
            fail(f"pool not spread over 4 chips: mesh {size}, clients "
                 f"on {data_ids}, params on {param_ids}")
    else:
        n = int(SYNC64[SYNC64.index("--devices") + 1])
        custom = transfer_path(n, 1)
        print(f"[chip_smoke] pools: a=local (XLA einsum transfer), "
              f"b=sharded-1 (Pallas alpha_combine transfer, "
              f"tpu_custom_call in its HLO: {custom}), c=local "
              f"(gossip exchanges)", flush=True)
        if not custom:
            fail("phase b's transfer is not the compiled Pallas "
                 "kernel")
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 4-chip --mesh 4 vs --mesh 1 "
                        "comparison")
    p.add_argument("--out-dir", default=OUT_DIR,
                   help="where each phase writes its JSONL metrics log")
    args = p.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro", "sim")):
        print(f"chip_smoke: no simulator under {src}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    from repro.sim.compile_cache import enable_compile_cache
    dev = require_tpu(jax)
    cache_dir = enable_compile_cache()
    print(f"[chip_smoke] device {dev.platform} {dev.device_kind} x "
          f"{len(jax.devices())}; jax {jax.__version__}; compile cache "
          f"{cache_dir}", flush=True)
    os.makedirs(args.out_dir, exist_ok=True)
    t0 = time.perf_counter()
    with CompileStats() as stats:
        if args.four_chips:
            run(FOUR_CHIP, FOUR_CHIP_COMPARE, stats, args.out_dir,
                four_chips=True)
        else:
            run(ONE_CHIP, ONE_CHIP_COMPARE, stats, args.out_dir)
    print(f"[chip_smoke] total wall {time.perf_counter() - t0:.3f} s; "
          f"compile {stats.secs:.3f} s over {stats.programs} programs; "
          f"persistent cache hits {stats.hits}, misses {stats.misses}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
