"""Seconds per round of pair estimation (Algorithm 1) through the device
pool: the bootstrap of never-estimated pairs and the budgeted refresh of
pairs that drift made dirty (TraceRecorder ``divergence`` events),
averaged over the window's rounds."""


def read(run):
    return run.phase_total("divergence") / len(run.rounds) \
        if run.rounds else None
