"""Seconds per round in the budgeted drift refresh's host side around
its pool call (TraceRecorder ``refresh_select`` events: the dirty scan,
the pair budget, EMA weights and content keys before the call, marking
the pairs estimated after it), averaged over the window's rounds.  None
from a program that records no such phase (its rows have no
``refresh_select_wall_s``)."""


def read(run):
    if not run.rounds or \
            "refresh_select_wall_s" not in run.rounds[0]["row"]:
        return None
    return run.phase_total("refresh_select") / len(run.rounds)
