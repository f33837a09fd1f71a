"""Seconds per round in the solver (TraceRecorder ``solve`` events, the
solver's own wall of each re-solve), averaged over the window's rounds."""


def read(run):
    return run.phase_total("solve") / len(run.rounds) if run.rounds \
        else None
