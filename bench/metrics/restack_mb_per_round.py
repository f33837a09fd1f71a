"""Megabytes (1e6 bytes) of client stack re-stacked and placed on the
device per round: the rows' ``restack_bytes`` (every leaf of the
stack; 0 in rounds without a restack), averaged over the window's
rounds.  None from a program whose rows have no such counter."""


def read(run):
    if not run.rounds or "restack_bytes" not in run.rounds[0]["row"]:
        return None
    return sum(r["row"]["restack_bytes"] for r in run.rounds) \
        / 1e6 / len(run.rounds)
