"""N-scaling of the sharded device pool: per-round wall clock + parity.

Runs the ``static`` (sync) scenario at N in {64, 256} twice — pool
sharded over a mesh-of-1 and over every local jax device (8 on the
reference box via ``--xla_force_host_platform_device_count=8``) — and
asserts the two metric trajectories match FIELD-FOR-FIELD (minus the
documented wall-clock fields): the mesh changes where lanes run, never
what they compute.  Round 0 carries the all-pairs Algorithm-1 bootstrap
and the cold (P) solve; later rounds are the steady train+transfer path.

N=1024 is measured DRY: phase-level timings on the sharded pool (local
training, Pallas-kernel transfer, accuracy sweep, and a 64-pair sharded
Algorithm-1 batch) without the 523k-pair bootstrap / 1024-device solve
a full round would pay — the per-phase numbers are exactly what a pod
deployment shards, the bootstrap cost is reported as an extrapolation.

Note the reference box has 2 physical cores: an emulated 8-shard mesh
demonstrates the collective program and its parity, not a speedup —
the shards time-slice the same silicon.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
          PYTHONPATH=src python -m benchmarks.sim_scale [--full]
          [--write-bench]
CI:   XLA_FLAGS=... python -m benchmarks.sim_scale --ci
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

try:
    from benchmarks.common import save_rows
except ModuleNotFoundError:          # invoked as a script, not a module
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.common import save_rows

import jax

from repro.sim.engine import SimConfig, SimulationEngine
from repro.sim.metrics import strip_nondeterministic

# lean enough that the N=256 all-pairs bootstrap (32640 pair
# classifiers) stays tractable on the 2-core box; resolve_threshold is
# pinned high so rounds after the cold solve time the steady path
LEAN = dict(samples_per_device=8, train_iters=2, div_tau=1, div_T=2,
            batch=4, solver_max_outer=2, solver_inner_steps=120,
            resolve_threshold=10.0)


def run_static(n: int, rounds: int, mesh: int, seed: int = 0):
    cfg = SimConfig(scenario="static", devices=n, rounds=rounds,
                    seed=seed, mesh=mesh, **LEAN)
    eng = SimulationEngine(cfg)
    rows, walls = [], []
    try:
        for t in range(rounds):
            t0 = time.time()
            rows.append(eng.step(t))
            walls.append(time.time() - t0)
    finally:
        eng.logger.close()
    return rows, walls


def _parity(rows_a, rows_b, tag: str) -> bool:
    a = json.dumps(strip_nondeterministic(rows_a), default=float)
    b = json.dumps(strip_nondeterministic(rows_b), default=float)
    if a != b:
        for ra, rb in zip(strip_nondeterministic(rows_a),
                          strip_nondeterministic(rows_b)):
            for k, v in ra.items():
                vb = rb[k]
                same = v == vb or (isinstance(v, float)
                                   and np.isnan(v) and np.isnan(vb))
                if not same:
                    print(f"[sim_scale] {tag} MISMATCH round "
                          f"{ra['round']} {k}: {v!r} != {vb!r}")
        return False
    print(f"[sim_scale] {tag}: field-for-field parity OK")
    return True


def dry_1024(mesh: int, n: int = 1024, reps: int = 2):
    """Phase-level sharded-pool timings at N devices (no bootstrap/solve).
    Each phase is called ``reps``+1 times; the first call (jit compile)
    is reported separately from the steady mean."""
    cfg = SimConfig(scenario="static", devices=n, rounds=1, seed=0,
                    mesh=mesh, **LEAN)
    t0 = time.time()
    eng = SimulationEngine(cfg)
    build_s = time.time() - t0
    st, pool = eng.state, eng.pool
    key = jax.random.PRNGKey(1)
    psi = np.zeros(n)
    psi[n // 2:] = 1.0                  # half targets, uniform mixtures
    alpha = np.zeros((n, n))
    alpha[:n // 2, n // 2:] = 1.0 / (n // 2)
    pairs = np.stack([np.arange(64), np.arange(64) + n // 2], 1)

    def phase(name, fn):
        times = []
        for _ in range(reps + 1):
            t0 = time.time()
            fn()
            times.append(time.time() - t0)
        return dict(n=n, mesh=mesh, dry=True, phase=name,
                    compile_s=times[0],
                    steady_s=float(np.mean(times[1:])))

    out = [dict(n=n, mesh=mesh, dry=True, phase="build_network",
                compile_s=build_s, steady_s=build_s)]
    out.append(phase("train", lambda: jax.block_until_ready(
        jax.tree_util.tree_leaves(pool.train(
            st.params, st.clients, key, st.active)[0]))))
    out.append(phase("transfer", lambda: jax.block_until_ready(
        jax.tree_util.tree_leaves(pool.transfer(st.params, alpha, psi)))))
    out.append(phase("accuracies", lambda: np.asarray(
        pool.accuracies(st.params, st.clients))))
    out.append(phase("divergence_64pairs", lambda: pool.update_divergences(
        st.div_hat, st.clients, key, pairs)))
    pair_s = out[-1]["steady_s"] / 64
    total_pairs = n * (n - 1) // 2
    out.append(dict(n=n, mesh=mesh, dry=True, phase="bootstrap_extrap",
                    compile_s=0.0, steady_s=pair_s * total_pairs))
    for r in out:
        print(f"[sim_scale] dry n={n} mesh={mesh} {r['phase']}: "
              f"compile {r['compile_s']:.1f}s steady {r['steady_s']:.2f}s")
    return out


def main(quick: bool = True, *, write_bench: bool = False):
    mesh_n = len(jax.devices())
    if mesh_n == 1:
        if jax.default_backend() != "cpu":
            raise SystemExit(
                "[sim_scale] FAIL: the mesh comparison needs more than "
                f"one {jax.default_backend()} device; this host has 1")
        print("[sim_scale] WARNING: only 1 jax device — set XLA_FLAGS="
              "--xla_force_host_platform_device_count=8 before running "
              "for a real mesh comparison")
    sizes = [(16, 3)] if quick else [(64, 3), (256, 3)]
    rows, summary = [], []
    parity_ok = True
    for n, rounds in sizes:
        per_mesh = {}
        for mesh in sorted({1, mesh_n}):
            t0 = time.time()
            mrows, walls = run_static(n, rounds, mesh)
            per_mesh[mesh] = mrows
            for t, w in enumerate(walls):
                rows.append(dict(n=n, mesh=mesh, round=t, wall_s=w,
                                 resolved=mrows[t]["resolved"],
                                 dry=False))
            steady = float(np.mean(walls[1:])) if len(walls) > 1 else 0.0
            summary.append(dict(n=n, mesh=mesh, round0_s=walls[0],
                                steady_mean_s=steady,
                                total_s=time.time() - t0))
            print(f"[sim_scale] n={n} mesh={mesh}: round0 "
                  f"{walls[0]:.1f}s, steady {steady:.2f}s/round")
        if len(per_mesh) == 2:
            parity_ok &= _parity(per_mesh[1], per_mesh[mesh_n],
                                 f"n={n} mesh1-vs-mesh{mesh_n}")
    dry = [] if quick else dry_1024(mesh_n)
    rows += dry
    if not parity_ok:
        raise SystemExit("[sim_scale] FAIL: sharded trajectory diverged "
                         "from mesh-of-1")
    if write_bench:
        from benchmarks.common import host_fingerprint
        bench = dict(
            benchmark="benchmarks/sim_scale.py",
            host="2-core reference box (see ROADMAP); mesh emulated via "
                 "--xla_force_host_platform_device_count",
            host_fingerprint=host_fingerprint(),
            settings=dict(scenario="static", seed=0, **LEAN),
            parity="mesh-of-1 vs mesh-of-%d: field-for-field OK" % mesh_n,
            summary=summary, rows=rows)
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "BENCH_scale.json"),
                "w") as f:
            json.dump(bench, f, indent=2, default=float)
        print("[sim_scale] wrote BENCH_scale.json")
    return rows


def ci_gate(n: int = 16, rounds: int = 2) -> int:
    """Parity gate: the local pool vs the sharded pool over every
    available device must agree field-for-field."""
    mesh_n = len(jax.devices())
    local_rows, _ = run_static(n, rounds, mesh=0)
    shard_rows, _ = run_static(n, rounds, mesh=mesh_n)
    if not _parity(local_rows, shard_rows,
                   f"--ci local-vs-mesh{mesh_n} n={n}"):
        return 1
    print(f"[sim_scale --ci] OK (n={n}, {mesh_n} shard(s))")
    return 0


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--full", action="store_true",
                   help="N in {64, 256} + the 1024-dry phases (tens of "
                        "minutes on the reference box); default is the "
                        "quick N=16 parity run")
    p.add_argument("--ci", action="store_true")
    p.add_argument("--write-bench", action="store_true")
    a = p.parse_args()
    if a.ci:
        raise SystemExit(ci_gate())
    save_rows("sim_scale", main(quick=not a.full,
                                write_bench=a.write_bench))
