"""The per-layer phase readers on a recorded list of the program's
TraceRecorder events: the phases and the host's share add up to the
round walls."""
import pytest

import _bench_path  # noqa: F401
from harness import spec
from harness.runner import Window, _phases

EVENTS = [  # as TraceRecorder.events records them
    {"phase": "train", "tick": 3, "mesh": 1, "seconds": 0.10},
    {"phase": "divergence", "tick": 3, "mesh": 1, "seconds": 0.05},
    {"phase": "solve", "tick": 3, "mesh": 1, "seconds": 0.30},
    {"phase": "transfer", "tick": 3, "mesh": 1, "seconds": 0.02},
    {"phase": "eval", "tick": 3, "mesh": 1, "seconds": 0.01},
    {"phase": "train", "tick": 4, "mesh": 1, "seconds": 0.12},
    {"phase": "transfer", "tick": 4, "mesh": 1, "seconds": 0.02},
    {"phase": "eval", "tick": 4, "mesh": 1, "seconds": 0.01},
    {"phase": "train", "tick": 2, "mesh": 1, "seconds": 9.0},  # warm-up
]
WALLS = {3: 0.60, 4: 0.20}


def _window():
    rounds = [{"tick": t, "wall": w, "row": {}} for t, w in WALLS.items()]
    return Window(rounds=rounds, seconds=0.8,
                  phases=_phases(EVENTS, list(WALLS)), profile=None,
                  sim={}, peaks=None)


def _readers():
    cell = spec.Cell(root=_bench_path.ROOT, workload={"name": "x"},
                     config={}, traffic={}, limits={}, end_to_end=[],
                     per_layer=[{"name": n} for n in (
                         "host_s_per_round", "solve_s_per_round",
                         "train_s_per_round",
                         "transfer_eval_s_per_round")])
    return spec.metric_readers(cell)


def test_phases_plus_host_equal_the_round_walls():
    w = _window()
    r = {k: f(w) for k, f in _readers().items()}
    per_round = (r["host_s_per_round"] + r["solve_s_per_round"]
                 + r["train_s_per_round"] + r["transfer_eval_s_per_round"]
                 + w.phase_total("divergence") / len(w.rounds))
    assert per_round == pytest.approx(sum(WALLS.values()) / len(WALLS))
    assert r["train_s_per_round"] == pytest.approx(0.11)
    assert r["solve_s_per_round"] == pytest.approx(0.15)
    assert r["host_s_per_round"] == pytest.approx(
        (0.60 - 0.48 + 0.20 - 0.15) / 2)


def test_warm_up_events_are_left_out():
    assert set(_window().phases) == {3, 4}
