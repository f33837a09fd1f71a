"""Seconds per round writing the round's row to the JSONL log
(TraceRecorder ``log`` events: serialisation, write and fsync),
averaged over the window's rounds.  The phase runs after the row is
built, so no row field carries it; None from a program that records no
``log`` event in the window."""


def read(run):
    if not run.rounds or not any("log" in run.phases.get(t, {})
                                 for t in run.ticks):
        return None
    return run.phase_total("log") / len(run.rounds)
