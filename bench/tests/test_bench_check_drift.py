"""The correctness check of the drift cell at a size a CPU test can
hold: drifted data, the budgeted refresh of dirty pairs and its merge
rule are followed by the reference; a sound run is correct, a run with
the timed path broken underneath is not, and the bfloat16 control fails
the limits."""
import pytest

import _faults

WORKLOAD = "sync-drift-n128"


def test_a_sound_drift_run_is_correct(capsys):
    res = _faults.result(capsys, _faults.cell(WORKLOAD))
    assert res["correct"] is True, res["checks"]
    assert "div_gap_mean" in res["checks"]


@pytest.mark.parametrize("fault", _faults.FAULTS)
def test_a_broken_drift_step_is_not_correct(capsys, monkeypatch, fault):
    _faults.plant(monkeypatch, fault)
    res = _faults.result(capsys, _faults.cell(WORKLOAD))
    assert res["correct"] is False, res["checks"]


def test_the_bfloat16_control_fails_the_limits():
    assert _faults.control_fails(_faults.cell(WORKLOAD))
