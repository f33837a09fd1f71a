"""Shared benchmark plumbing: timed runs, CSV rows, round caching."""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List

import jax
import numpy as np

RESULTS_DIR = os.environ.get("REPRO_BENCH_DIR", "results/bench")


def host_fingerprint() -> dict:
    """Provenance stamp for benchmark artifacts: enough to tell whether
    two BENCH_*.json files were measured on comparable hosts (walls
    from one box must not be silently compared against walls from
    another)."""
    import platform
    devs = jax.devices()
    return {
        "platform": platform.platform(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "device_count": len(devs),
        "device_kind": devs[0].device_kind if devs else "none",
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "cpu_count": os.cpu_count(),
    }


def save_rows(name: str, rows: List[dict]):
    """Benchmark result artifact: since the trace PR a stamped dict
    ``{"benchmark", "host_fingerprint", "rows"}`` (read it back with
    ``load_rows``, which also accepts the older bare-list files)."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, f"{name}.json"), "w") as f:
        json.dump({"benchmark": name,
                   "host_fingerprint": host_fingerprint(),
                   "rows": rows}, f, indent=2, default=float)


def load_rows(path: str) -> List[dict]:
    """Rows from a benchmark artifact — stamped dict (new) or bare list
    (pre-fingerprint files still on disk / in git history)."""
    with open(path) as f:
        obj = json.load(f)
    return obj["rows"] if isinstance(obj, dict) else obj


def timed(fn: Callable, *args, **kw):
    t0 = time.time()
    out = fn(*args, **kw)
    return out, time.time() - t0


_ROUND_CACHE: Dict[tuple, object] = {}


def cached_round(setting: str, *, num_devices: int, samples: int,
                 seed: int, train_iters: int, div_tau: int, div_T: int,
                 label_subset=None):
    """prepare_round is the expensive part (local training + Algorithm 1);
    cache it per configuration so fig6/fig8/fig9/table2 share rounds."""
    from repro.data import build_network
    from repro.fl import prepare_round
    key = (setting, num_devices, samples, seed, train_iters, div_tau,
           div_T, tuple(label_subset or ()))
    if key not in _ROUND_CACHE:
        devs = build_network(setting, num_devices=num_devices,
                             samples_per_device=samples, seed=seed,
                             label_subset=label_subset)
        _ROUND_CACHE[key] = prepare_round(
            devs, jax.random.PRNGKey(seed), train_iters=train_iters,
            div_tau=div_tau, div_T=div_T, energy_seed=seed)
    return _ROUND_CACHE[key]


def quick_params(quick: bool):
    """Network sizes for quick (CI) vs full runs."""
    if quick:
        return dict(num_devices=6, samples=100, train_iters=150,
                    div_tau=2, div_T=12, seeds=[0])
    return dict(num_devices=10, samples=250, train_iters=300,
                div_tau=4, div_T=25, seeds=[0, 1, 2])
