"""Seconds per round of the model transfer (the pool's alpha-mixture)
and the accuracy sweep (TraceRecorder ``transfer`` and ``eval``
events), averaged over the window's rounds."""


def read(run):
    return run.phase_total("transfer", "eval") / len(run.rounds) \
        if run.rounds else None
