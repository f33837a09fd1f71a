"""Feature-drift end-to-end: domain shift over time with budgeted,
drift-aware divergence re-estimation — run on the single-host pool
(LocalPool), then replayed on a sharded device pool and compared
field-for-field.

8 devices under the `feature-drift` scenario: half the network's
feature distributions slide toward a foreign domain, each drift step
dirties the device's Algorithm-1 pairs, and every round the engine
re-measures only a budgeted stalest-first subset of the dirty pairs
(`div_budget`) instead of all N(N-1)/2 — the moved estimates trip
`resolve_reason="drift"` warm re-solves.

    PYTHONPATH=src python examples/sim_drift.py

Everything runs in this one process, which keeps the accelerator to
itself.  The replay shards the pool over 2 devices: on the CPU
(``JAX_PLATFORMS=cpu``) two host-platform devices are emulated, which
must be requested before JAX is imported; on an accelerator the mesh
takes as many of the 2 as the host has.
"""
import json
import os
import sys

if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                               + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.sim import SimConfig, SimulationEngine  # noqa: E402
from repro.sim.metrics import strip_nondeterministic  # noqa: E402

CFG = dict(scenario="feature-drift", devices=8, rounds=4, seed=0,
           samples_per_device=40, train_iters=8, div_tau=1, div_T=6,
           batch=10, solver_max_outer=3, solver_inner_steps=200,
           feature_drift_p=0.6, feature_drift_step=0.3,
           resolve_threshold=0.05, div_budget=6)
LOCAL_LOG = "results/sim/example_drift.jsonl"
MESH_LOG = "results/sim/example_drift_mesh.jsonl"

# ---- single-host run --------------------------------------------------
rows = SimulationEngine(SimConfig(log_path=LOCAL_LOG, verbose=True,
                                  **CFG)).run()

resolves = [r for r in rows if r["resolved"]]
print(f"\n{len(resolves)} solves over {len(rows)} rounds; reasons:",
      [r["resolve_reason"] for r in resolves])
print("per-round drifted devices:", [r["n_drifted"] for r in rows])
print("per-round dirty pairs:    ", [r["n_dirty_pairs"] for r in rows])
print("per-round re-estimated:   ", [r["n_reestimated"] for r in rows],
      f"(budget {CFG['div_budget']}, all-pairs would be "
      f"{CFG['devices'] * (CFG['devices'] - 1) // 2})")
print("target accuracy trajectory:",
      np.round([r["mean_target_acc"] for r in rows], 3).tolist())

# ---- sharded-pool replay ----------------------------------------------
shards = min(2, len(jax.devices()))
print(f"\nreplaying on a {shards}-shard device mesh "
      f"({jax.devices()[0].platform}) ...")
mesh_rows = SimulationEngine(SimConfig(mesh=shards, log_path=MESH_LOG,
                                       **CFG)).run()

match = json.dumps(strip_nondeterministic(rows), default=float) == \
    json.dumps(strip_nondeterministic(mesh_rows), default=float)
print(f"mesh-of-{shards} parity vs single host: "
      f"{'field-for-field OK' if match else 'MISMATCH'}")
if not match:
    sys.exit(1)
