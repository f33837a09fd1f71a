"""chip_smoke.py at a tiny size on the CPU: its phases run through
``repro.sim.run.main`` and its checks hold the runs to targets,
transmissions, agreeing solve decisions and the last-line format.  The
TPU platform check is steered from here; the script itself has no way
around it."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ["--samples", "40", "--train-iters", "8", "--div-T", "6",
        "--solver-max-outer", "3", "--solver-inner-steps", "200"]
# the smallest networks whose runs install targets and transmit: the
# sync round-0 solve at 8 devices, and ring gossip at 8 devices (seed 0)
SYNC8 = ["--scenario", "static", "--devices", "8", "--rounds", "2"] + TINY
ASYNC8 = ["--engine", "async-gossip", "--scenario", "async-gossip",
          "--gossip-topology", "ring", "--resolve-patience", "2",
          "--devices", "8", "--rounds", "5"] + TINY


@pytest.fixture
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_tiny(smoke, monkeypatch, capsys, tmp_path):
    monkeypatch.setattr(smoke, "require_tpu", lambda jax: jax.devices()[0])
    # on the CPU the kernel runs interpreted, with no TPU custom call
    assert smoke.transfer_path(8, 1) is False
    monkeypatch.setattr(smoke, "transfer_path", lambda n, k: True)
    monkeypatch.setattr(smoke, "SYNC64", SYNC8)
    monkeypatch.setattr(smoke, "ONE_CHIP", [
        ("a", SYNC8), ("b", SYNC8 + ["--mesh", "1"]), ("c", ASYNC8)])
    assert smoke.main(["--out-dir", str(tmp_path)]) == 0
    assert sorted(os.listdir(tmp_path)) == ["a.jsonl", "b.jsonl", "c.jsonl"]
    out = capsys.readouterr().out.strip().splitlines()
    dev = jax.devices()[0]
    assert json.loads(out[-1]) == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}
    assert any("b vs a: targets and links agree" in ln for ln in out)
    for name in ("a", "b", "c"):
        assert any(ln.startswith(f"[chip_smoke] phase {name}: wall")
                   for ln in out)


def test_refuses_a_host_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit, match="needs a TPU"):
        smoke.main([])
    assert capsys.readouterr().out == ""


def test_checks_reject_runs_without_transfer(smoke):
    row = {"round": 0, "n_targets": 2, "n_sources": 6,
           "mean_target_acc": 0.5, "mean_source_acc": 0.5,
           "transmissions": 2, "targets": [1, 2], "links": [[0, 1], [0, 2]],
           "train_wall_s": 0.0, "div_wall_s": 0.0, "transfer_wall_s": 0.0,
           "eval_wall_s": 0.0, "solver_wall_s": 0.0}
    smoke.check_rows("x", [row])
    with pytest.raises(SystemExit, match="no round installed a target"):
        smoke.check_rows("x", [dict(row, n_targets=0,
                                    mean_target_acc=float("nan"))])
    with pytest.raises(SystemExit, match="no round transmitted"):
        smoke.check_rows("x", [dict(row, transmissions=0)])
    with pytest.raises(SystemExit, match="mean_source_acc"):
        smoke.check_rows("x", [dict(row, mean_source_acc=float("nan"))])
    with pytest.raises(SystemExit, match="links differ"):
        smoke.compare("y", [dict(row, links=[[0, 1]])], "x", [row])
    assert smoke.largest_difference([dict(row, energy=1.5)],
                                    [dict(row, energy=1.0)]) \
        == (0.5, "energy", 0)


def test_exits_nonzero_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
