"""Cells, traffic mixes and metrics are found by name, and the command
refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import _bench_path  # noqa: F401
from harness import spec

ROOT = _bench_path.ROOT


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        fh.write(text)


def test_files_dropped_in_become_a_cell_and_a_metric(tmp_path):
    root = str(tmp_path)
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps({
        "configs": [{"name": "cfg-x", "file": "bench/configs/cfg-x.json"}],
        "workloads": [{"name": "x-cell", "config": "cfg-x",
                       "traffic": "t-x", "chips": 1}],
        "end_to_end": [{"name": "round_s"},
                       {"name": "other_s", "workloads": ["y-cell"]}],
        "per_layer": [{"name": "m_x", "workloads": ["x-cell"]},
                      {"name": "m_y", "workloads": ["y-cell"]}]}))
    _write(os.path.join(root, "bench/configs/cfg-x.json"),
           json.dumps({"devices": 12, "engine": "sync", "label": "x"}))
    _write(os.path.join(root, "bench/traffic/t-x.json"),
           json.dumps({"scenario": "static", "warmup_rounds": 2}))
    _write(os.path.join(root, "bench/limits/x-cell.json"),
           json.dumps({"param_gap": 0.1}))
    _write(os.path.join(root, "bench/metrics/m_x.py"),
           "def read(run):\n    return 42.0\n")
    cell = spec.load_cell(root, "x-cell")
    assert cell.chips == 1 and cell.limits == {"param_gap": 0.1}
    assert [m["name"] for m in cell.end_to_end] == ["round_s"]
    readers = spec.metric_readers(cell)
    assert list(readers) == ["m_x"] and readers["m_x"](None) == 42.0
    kw = spec.sim_config_kwargs(cell)
    assert kw == {"devices": 12, "engine": "sync", "scenario": "static"}


def test_every_cell_of_the_benchmark_loads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for w in bench["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert cell.limits and spec.metric_readers(cell)
        assert spec.sim_config_kwargs(cell)["devices"] >= 1


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sync-static-n128",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_the_command_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run(ROOT, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_the_command_needs_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    r = _run(str(tmp_path), dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
