"""Seconds per round the host spends outside the program's recorded
phases: the harness's round wall minus the sum of that round's
TraceRecorder phases (train, divergence, transfer, solve, eval), averaged
over the window's rounds.  Layer: executors and engine host control."""


def read(run):
    if not run.rounds:
        return None
    phases = sum(sum(run.phases.get(r["tick"], {}).values())
                 for r in run.rounds)
    return (sum(r["wall"] for r in run.rounds) - phases) / len(run.rounds)
