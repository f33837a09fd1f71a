"""The correctness check of the static cell at a size a CPU test can
hold: a sound run is correct, and a run with the timed path broken
underneath is not; the bfloat16 control fails the limits.  The look for
a chip is skipped; the rest of a run is as on the chip."""
import pytest

import _faults

WORKLOAD = "sync-static-n128"


def test_a_sound_sync_run_is_correct(capsys):
    res = _faults.result(capsys, _faults.cell(WORKLOAD))
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"


# the static cell's window estimates no pairs, so a pair estimate
# altered is the drift cell's fault (test_bench_check_drift.py)
@pytest.mark.parametrize("fault", [f for f in _faults.FAULTS
                                   if f != "divergence_altered"])
def test_a_broken_sync_step_is_not_correct(capsys, monkeypatch, fault):
    _faults.plant(monkeypatch, fault)
    res = _faults.result(capsys, _faults.cell(WORKLOAD))
    assert res["correct"] is False, res["checks"]


def test_the_bfloat16_control_fails_the_limits():
    # the cell's own training and data sizes on fewer devices: at two
    # SGD steps the bfloat16 parameters have too little to lose
    assert _faults.control_fails(_faults.cell(WORKLOAD, n=8, train_iters=30,
                                              samples=100))
