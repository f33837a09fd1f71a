"""Seconds per round in the scenario's mutation (TraceRecorder
``scenario`` events: drift blends and first-drift renders in the drift
cell), averaged over the window's rounds.  None from a program that
records no such phase (its rows have no ``scenario_wall_s``)."""


def read(run):
    if not run.rounds or "scenario_wall_s" not in run.rounds[0]["row"]:
        return None
    return run.phase_total("scenario") / len(run.rounds)
