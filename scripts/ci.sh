#!/usr/bin/env bash
# Tier-1 CI: the pytest suite, then a simulator smoke run so the repro.sim
# subsystem (engine + scenarios + solver warm-start path + JSONL metrics)
# is exercised end-to-end on every PR.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

python -m pytest -x -q

# packing-regression gate: vectorized packer parity + speed at N=32
python -m benchmarks.solver_scaling --ci

python -m repro.sim.run --scenario channel-drift --devices 8 --rounds 2 \
    --samples 40 --train-iters 10 --quiet \
    --out "${REPRO_SIM_LOG:-results/sim/ci_smoke.jsonl}"

# async-gossip execution-layer smoke: local clocks + stragglers +
# staleness-gated warm re-solves, end-to-end through the CLI
python -m repro.sim.run --engine async-gossip --scenario stragglers \
    --devices 8 --rounds 4 --samples 40 --train-iters 8 --div-T 6 \
    --solver-max-outer 3 --solver-inner-steps 200 --resolve-patience 3 \
    --quiet --out "${REPRO_SIM_LOG_ASYNC:-results/sim/ci_async_smoke.jsonl}"

# feature-drift smoke, both engines: domain shift dirties Algorithm-1
# pairs, the budgeted stalest-first refresh re-measures them through the
# row-targeted pool path, and drift-reason warm re-solves fire
python -m repro.sim.run --scenario feature-drift --devices 8 --rounds 3 \
    --samples 40 --train-iters 8 --div-T 6 --solver-max-outer 3 \
    --solver-inner-steps 200 --div-budget 6 --drift-p 0.6 \
    --drift-step 0.3 --quiet --out "results/sim/ci_drift_sync.jsonl"
python -m repro.sim.run --engine async-gossip \
    --scenario feature-drift-async --devices 8 --rounds 3 --samples 40 \
    --train-iters 8 --div-T 6 --solver-max-outer 3 \
    --solver-inner-steps 200 --resolve-patience 3 --div-budget 6 \
    --drift-p 0.6 --drift-step 0.3 --quiet \
    --out "results/sim/ci_drift_async.jsonl"

# docs-coverage gate: every SimConfig knob and metrics field must be
# documented in docs/metrics-schema.md
python scripts/check_docs.py

# emulated-mesh smoke gate: the sharded device pool on 8 forced
# host-platform devices (XLA_FLAGS must precede the first jax import,
# hence fresh processes), both engines end-to-end through the CLI, then
# the sim_scale parity gate (local pool vs 8-shard pool field-for-field)
MESH_FLAGS="--xla_force_host_platform_device_count=8"
XLA_FLAGS="$MESH_FLAGS${XLA_FLAGS:+ $XLA_FLAGS}" \
python -m repro.sim.run --mesh 8 --scenario static --devices 8 \
    --rounds 2 --samples 40 --train-iters 8 --div-T 6 \
    --solver-max-outer 3 --solver-inner-steps 200 \
    --quiet --out "results/sim/ci_mesh_sync.jsonl"
XLA_FLAGS="$MESH_FLAGS${XLA_FLAGS:+ $XLA_FLAGS}" \
python -m repro.sim.run --mesh 8 --engine async-gossip \
    --scenario async-gossip --devices 8 --rounds 3 --samples 40 \
    --train-iters 8 --div-T 6 --solver-max-outer 3 \
    --solver-inner-steps 200 --resolve-patience 3 \
    --gossip-topology ring \
    --quiet --out "results/sim/ci_mesh_async.jsonl"
XLA_FLAGS="$MESH_FLAGS${XLA_FLAGS:+ $XLA_FLAGS}" \
python -m benchmarks.sim_scale --ci

# kill-and-resume gate: run to completion for a reference, then the same
# config checkpointed + SIGKILLed mid-run (--kill-after hard-kills the
# process right after the round-3 checkpoint commits), resumed, and the
# stitched log diffed field-for-field against the uninterrupted one
RESUME_ARGS=(--scenario device-churn --devices 6 --rounds 6 --samples 40
    --train-iters 8 --div-T 6 --solver-max-outer 3
    --solver-inner-steps 200 --quiet)
python -m repro.sim.run "${RESUME_ARGS[@]}" \
    --out results/sim/ci_resume_ref.jsonl
rm -rf results/sim/ci_resume.jsonl.ckpt
if python -m repro.sim.run "${RESUME_ARGS[@]}" \
    --out results/sim/ci_resume.jsonl --checkpoint-every 3 --kill-after 2
then
    echo "ci.sh: --kill-after did not kill the run" >&2; exit 1
elif [ $? -ne 137 ]; then
    echo "ci.sh: expected SIGKILL exit 137 from --kill-after" >&2; exit 1
fi
python -m repro.sim.run "${RESUME_ARGS[@]}" \
    --out results/sim/ci_resume.jsonl --checkpoint-every 3 --resume
python - <<'PY'
from repro.sim.metrics import read_jsonl, strip_nondeterministic
import json
ref = strip_nondeterministic(read_jsonl("results/sim/ci_resume_ref.jsonl"))
res = strip_nondeterministic(read_jsonl("results/sim/ci_resume.jsonl"))
assert json.dumps(ref, sort_keys=True) == json.dumps(res, sort_keys=True), \
    "resumed run diverged from the uninterrupted reference"
print(f"ci.sh: kill-and-resume OK ({len(res)} rounds, field-for-field)")
PY

# shard-failure recovery smoke: fault injection on the emulated 8-device
# mesh — shard losses must be detected and recovered (churn/reseed), not
# fatal, and the run must complete with recoveries on record
XLA_FLAGS="$MESH_FLAGS${XLA_FLAGS:+ $XLA_FLAGS}" \
python -m repro.sim.run --mesh 8 --scenario faulty --devices 8 \
    --rounds 4 --samples 40 --train-iters 8 --div-T 6 \
    --solver-max-outer 3 --solver-inner-steps 200 --seed 4 \
    --fault-shard-p 0.7 --fault-crash-p 0.0 \
    --quiet --out "results/sim/ci_faulty_mesh.jsonl"
python - <<'PY'
from repro.sim.metrics import read_jsonl
rows = read_jsonl("results/sim/ci_faulty_mesh.jsonl")
assert len(rows) == 4, "faulty mesh run did not complete"
faults = sum(r["n_faults"] for r in rows)
recovered = sum(r["n_recovered"] for r in rows)
assert faults > 0, "fault injector injected nothing at fault_shard_p=0.7"
assert recovered > 0, "shard losses were never recovered"
print(f"ci.sh: shard-failure recovery OK "
      f"({faults} faults, {recovered} devices recovered)")
PY

# sync determinism gate: same seed twice -> identical deterministic fields
# (golden-file parity vs the pre-refactor engine runs in the pytest suite)
python - <<'PY'
from repro.sim.engine import SimConfig, SimulationEngine
from repro.sim.metrics import strip_nondeterministic
smoke = dict(samples_per_device=40, train_iters=8, div_tau=1, div_T=6,
             solver_max_outer=3, solver_inner_steps=200)
runs = [SimulationEngine(SimConfig(scenario="channel-drift", devices=6,
                                   rounds=2, seed=0, **smoke)).run()
        for _ in range(2)]
assert strip_nondeterministic(runs[0]) == strip_nondeterministic(runs[1]), \
    "sync engine lost per-seed determinism"
print("ci.sh: sync determinism OK")
PY

echo "ci.sh: all green"
