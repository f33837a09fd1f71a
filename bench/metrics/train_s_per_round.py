"""Seconds per round of local training through the device pool
(TraceRecorder ``train`` events), averaged over the window's rounds."""


def read(run):
    return run.phase_total("train") / len(run.rounds) if run.rounds \
        else None
