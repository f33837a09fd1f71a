"""sim/metrics.py: RoundRecord -> JSONL -> read-back round-trip
(including the nondeterministic-field contract) and CLI smoke runs of
``python -m repro.sim.run`` for both execution modes."""
import dataclasses
import math
import os

from repro.sim.metrics import (MetricsLogger, NONDETERMINISTIC_FIELDS,
                               RoundRecord, read_jsonl,
                               strip_nondeterministic)
from repro.sim.run import main as run_main


def _record(t=0, **kw):
    base = dict(
        round=t, scenario="async-gossip", n_active=8, n_sources=5,
        n_targets=3, resolved=True, warm=True, solver_iters=2,
        solver_wall_s=0.25, drift=0.01, mean_target_acc=0.4,
        mean_source_acc=0.6, energy=0.002, energy_cum=0.01,
        transmissions=3, link_churn=0.5,
        events=[{"event": "retick", "device": 1, "period": 4}],
        wall_time_s=1.5, engine="async-gossip", n_trained=5,
        trained=[0, 1, 2, 5, 7], gossip=[[0, 3], [2, 6]],
        mean_staleness=1.25, max_staleness=4.0, solve_age=9,
        resolve_reason="staleness", n_drifted=2, n_dirty_pairs=9,
        n_reestimated=4)
    base.update(kw)
    return RoundRecord(**base)


def test_nondeterministic_fields_exist_on_record():
    names = {f.name for f in dataclasses.fields(RoundRecord)}
    assert set(NONDETERMINISTIC_FIELDS) <= names
    assert set(NONDETERMINISTIC_FIELDS) == {
        "wall_time_s", "solver_wall_s", "scenario_wall_s",
        "restack_wall_s", "features_wall_s", "train_wall_s", "div_wall_s",
        "refresh_select_wall_s", "transfer_wall_s", "eval_wall_s",
        "ckpt_wall_s", "n_compiled", "resume_count"}


def test_roundrecord_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "ticks.jsonl")
    logger = MetricsLogger(path)
    rows = [logger.log(_record(t)) for t in range(3)]
    logger.close()
    back = read_jsonl(path)
    assert back == rows
    assert back[0]["gossip"] == [[0, 3], [2, 6]]
    assert back[0]["resolve_reason"] == "staleness"
    assert back[0]["n_drifted"] == 2
    assert back[0]["n_dirty_pairs"] == 9 and back[0]["n_reestimated"] == 4
    stripped = strip_nondeterministic(back)
    for row in stripped:
        assert "wall_time_s" not in row and "solver_wall_s" not in row
    # stripping only removes the wall-clock fields, nothing else
    assert set(rows[0]) - set(stripped[0]) == set(NONDETERMINISTIC_FIELDS)


def test_roundtrip_preserves_nan_and_null_fields(tmp_path):
    path = str(tmp_path / "nan.jsonl")
    logger = MetricsLogger(path)
    logger.log(_record(0, mean_target_acc=float("nan"), trained=None,
                       gossip=None, resolve_reason=None))
    logger.close()
    # NaN serializes to the non-strict token python's json reads back
    assert "NaN" in open(path).read()
    row = read_jsonl(path)[0]
    assert math.isnan(row["mean_target_acc"])
    assert row["trained"] is None and row["gossip"] is None
    assert row["resolve_reason"] is None


def test_reader_drops_truncated_final_line(tmp_path):
    import pytest
    path = str(tmp_path / "trunc.jsonl")
    logger = MetricsLogger(path)
    rows = [logger.log(_record(t)) for t in range(3)]
    logger.close()
    with open(path, "a") as f:           # a crash mid-write
        f.write('{"round": 3, "scenario": "asy')
    with pytest.warns(UserWarning, match="truncated final line"):
        back = read_jsonl(path)
    assert back == rows                  # complete prefix intact


def test_reader_raises_on_mid_file_corruption(tmp_path):
    import pytest
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        f.write('{"round": 0}\n{"rou\n{"round": 2}\n')
    with pytest.raises(ValueError, match="line 2"):
        read_jsonl(path)


def test_logger_resume_reconciles_existing_log(tmp_path):
    path = str(tmp_path / "resume.jsonl")
    logger = MetricsLogger(path)
    for t in range(5):
        logger.log(_record(t))
    logger.close()
    with open(path, "a") as f:           # plus a torn final line
        f.write('{"round": 5, "scen')
    # resume at round 3: rounds 3+ will be re-executed and must go
    logger = MetricsLogger(path, resume_round=3)
    assert [r["round"] for r in logger.records] == [0, 1, 2]
    logger.log(_record(3))
    logger.log(_record(4))
    logger.close()
    assert [r["round"] for r in read_jsonl(path)] == [0, 1, 2, 3, 4]


def test_memory_only_logger_keeps_records():
    logger = MetricsLogger(None)
    logger.log(_record(0))
    logger.close()
    assert len(logger.records) == 1 and logger.records[0]["round"] == 0


# ------------------------------------------------------------- CLI smoke
def test_cli_smoke_sync(tmp_path, capsys):
    out = str(tmp_path / "cli.jsonl")
    rc = run_main(["--scenario", "static", "--devices", "6",
                   "--rounds", "1", "--samples", "40",
                   "--train-iters", "8", "--div-T", "6",
                   "--solver-max-outer", "3",
                   "--solver-inner-steps", "200",
                   "--quiet", "--out", out])
    assert rc == 0
    assert os.path.exists(out)
    rows = read_jsonl(out)
    assert len(rows) == 1
    assert rows[0]["engine"] == "sync"
    assert rows[0]["scenario"] == "static"
    assert "[sim] metrics log:" in capsys.readouterr().out


def test_cli_smoke_async_gossip(tmp_path, capsys):
    out = str(tmp_path / "cli_async.jsonl")
    rc = run_main(["--engine", "async-gossip", "--scenario",
                   "async-gossip", "--devices", "6", "--rounds", "2",
                   "--samples", "40", "--train-iters", "8",
                   "--div-T", "6", "--solver-max-outer", "3",
                   "--solver-inner-steps", "200",
                   "--resolve-patience", "4",
                   "--quiet", "--out", out])
    assert rc == 0
    rows = read_jsonl(out)
    assert len(rows) == 2
    assert all(r["engine"] == "async-gossip" for r in rows)
    assert all(r["n_trained"] == len(r["trained"]) for r in rows)
    assert "[sim] async:" in capsys.readouterr().out
