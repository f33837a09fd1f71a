"""Seconds per round re-stacking the pool's client data and placing it
on the device (TraceRecorder ``restack`` events, each blocked on the
placed stack; rounds without a restack count 0), averaged over the
window's rounds.  None from a program that records no such phase (its
rows have no ``restack_wall_s``)."""


def read(run):
    if not run.rounds or "restack_wall_s" not in run.rounds[0]["row"]:
        return None
    return run.phase_total("restack") / len(run.rounds)
