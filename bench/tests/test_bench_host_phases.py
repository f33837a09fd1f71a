"""The readers of the host phases the program names (scenario, restack,
refresh_select, log) and of the restack counter, on synthetic
TraceRecorder events and rows: what each reads, that each reads nothing
from a program without the phase, and that the phases with the host's
remainder still add up to the round walls."""
import pytest

import _bench_path  # noqa: F401
from harness import spec
from harness.runner import Window, _phases

NEW = ("scenario_s_per_round", "restack_s_per_round",
       "refresh_select_s_per_round", "log_s_per_round",
       "restack_mb_per_round")
OLD = ("host_s_per_round", "solve_s_per_round", "train_s_per_round",
       "div_s_per_round", "transfer_eval_s_per_round")


def _ev(phase, tick, seconds):
    return {"phase": phase, "tick": tick, "mesh": 1, "t0_ns": 0,
            "seconds": seconds}


EVENTS = [  # as TraceRecorder.events records them, two rounds
    _ev("scenario", 3, 0.04), _ev("restack", 3, 0.10),
    _ev("train", 3, 0.40), _ev("refresh_select", 3, 0.01),
    _ev("divergence", 3, 0.30), _ev("refresh_select", 3, 0.002),
    _ev("solve", 3, 0.03), _ev("transfer", 3, 0.004),
    _ev("eval", 3, 0.001), _ev("log", 3, 0.006),
    _ev("scenario", 4, 0.002), _ev("train", 4, 0.40),
    _ev("refresh_select", 4, 0.008), _ev("divergence", 4, 0.30),
    _ev("refresh_select", 4, 0.002), _ev("transfer", 4, 0.004),
    _ev("eval", 4, 0.001), _ev("log", 4, 0.004),
    _ev("restack", 2, 9.0), _ev("log", 2, 9.0),      # warm-up round
]
WALLS = {3: 0.92, 4: 0.74}
ROWS = {3: {"restack_bytes": 120_422_400, "scenario_wall_s": 0.04,
            "restack_wall_s": 0.10, "refresh_select_wall_s": 0.012},
        4: {"restack_bytes": 0, "scenario_wall_s": 0.002,
            "restack_wall_s": 0.0, "refresh_select_wall_s": 0.01}}


def _window(events=EVENTS, rows=ROWS):
    rounds = [{"tick": t, "wall": w, "row": rows[t]}
              for t, w in WALLS.items()]
    return Window(rounds=rounds, seconds=sum(WALLS.values()),
                  phases=_phases(events, list(WALLS)), profile=None,
                  sim={}, peaks=None)


def _readers(names):
    cell = spec.Cell(root=_bench_path.ROOT, workload={"name": "x"},
                     config={}, traffic={}, limits={}, end_to_end=[],
                     per_layer=[{"name": n} for n in names])
    return spec.metric_readers(cell)


@pytest.mark.parametrize("name,want", [
    ("scenario_s_per_round", (0.04 + 0.002) / 2),
    ("restack_s_per_round", 0.10 / 2),
    ("refresh_select_s_per_round", (0.012 + 0.01) / 2),
    ("log_s_per_round", (0.006 + 0.004) / 2),
    ("restack_mb_per_round", 120.4224 / 2)])
def test_reader_reads_its_phase_over_the_window(name, want):
    assert _readers([name])[name](_window()) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_from_a_program_without_the_phase(name):
    """A program older than these phases records none of them and fills
    none of their row fields: each reader returns None, and raises
    nothing."""
    old_events = [e for e in EVENTS if e["phase"] in (
        "train", "divergence", "solve", "transfer", "eval")]
    old_rows = {t: {} for t in WALLS}
    assert _readers([name])[name](_window(old_events, old_rows)) is None


def test_named_phases_plus_host_equal_the_round_walls():
    w = _window()
    r = {k: f(w) for k, f in _readers(NEW + OLD).items()}
    per_round = sum(r[n] for n in OLD) + sum(
        r[n] for n in NEW if n.endswith("_s_per_round"))
    assert per_round == pytest.approx(sum(WALLS.values()) / len(WALLS))
    # the host's remainder is what no phase names
    named = {t: sum(e["seconds"] for e in EVENTS if e["tick"] == t)
             for t in WALLS}
    assert r["host_s_per_round"] == pytest.approx(
        sum(WALLS[t] - named[t] for t in WALLS) / len(WALLS))
