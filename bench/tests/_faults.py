"""A run of a cell at a size a CPU test can hold, and the faults the
correctness check has to catch, each planted under the timed path."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

import _bench_path
from harness import runner, spec
from repro.sim.shard.pool import ShardedPool

SEED = 2147483659
FAULTS = ("state_unchanged", "half_the_lanes", "transfer_left_out",
          "accuracy_altered", "divergence_altered")


def cell(workload, n=6, train_iters=2, samples=12):
    c = spec.load_cell(_bench_path.ROOT, workload)
    c.config.update(devices=n, train_iters=train_iters,
                    samples_per_device=samples)
    return c


def result(capsys, c):
    """The result line of a run of ``c`` with the look for a chip
    skipped."""
    rc = runner.run(c, SEED, 0.2, False, time.perf_counter(), chip=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def control_fails(c) -> bool:
    """Whether the bfloat16 reference in the program's place fails one
    of the cell's limits."""
    engine, cap, end, sim = runner.setup(c, SEED, False, None)
    got = runner.check(c, SEED, sim, cap.ticks, end, control=True)
    return any(v["value"] > v["limit"] for v in got.values())


def _unchanged(orig):
    def train(self, params, *a, **k):
        out = orig(self, params, *a, **k)
        return (params,) + tuple(out[1:])
    return train


def _half_lanes(orig):
    def train(self, params, clients, *a, **k):
        new, *rest = orig(self, params, clients, *a, **k)
        keep = np.arange(clients.n_devices) % 2 == 0
        new = jax.tree_util.tree_map(
            lambda n, o: jnp.where(jnp.asarray(keep).reshape(
                (-1,) + (1,) * (n.ndim - 1)), n, o), new, params)
        return (new, *rest)
    return train


def _shifted(orig):
    def values_fn(self):
        values = orig(self)
        return lambda *a, **k: values(*a, **k) + 0.5
    return values_fn


def plant(monkeypatch, fault: str):
    """Break the sharded pool's timed path with ``fault``."""
    if fault == "state_unchanged":
        monkeypatch.setattr(ShardedPool, "_train",
                            _unchanged(ShardedPool._train))
    elif fault == "half_the_lanes":
        monkeypatch.setattr(ShardedPool, "_train",
                            _half_lanes(ShardedPool._train))
    elif fault == "transfer_left_out":
        monkeypatch.setattr(ShardedPool, "_transfer",
                            lambda self, params, alpha, psi: params)
    elif fault == "accuracy_altered":
        orig = ShardedPool._accuracies
        monkeypatch.setattr(ShardedPool, "_accuracies",
                            lambda self, p, c: orig(self, p, c).at[1]
                            .add(0.25))
    elif fault == "divergence_altered":
        for name in ("_values_fn", "_targeted_values_fn"):
            monkeypatch.setattr(ShardedPool, name,
                                _shifted(getattr(ShardedPool, name)))
    else:
        raise ValueError(fault)
