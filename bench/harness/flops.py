"""Operations and bytes of a round, counted from its logged row and the
client's costs.

The round's structure is the simulator's; what each piece of work costs
is the client's, from ``costs(sim)`` of the reference file the
configuration names (``spec.py`` gives the contract): one training
sample, one measurement forward, one Algorithm-1 pair estimate, and the
width V of the per-device parameters the combine moves.
"""
from __future__ import annotations


def alpha_combine_cost(s: int, t: int, v: int, itemsize: int = 4):
    """(operations, bytes) of one out (T, V) = alpha^T (S, T) @ theta
    (S, V) call: 2 S T V operations; theta and alpha read once, the
    output written once."""
    return 2 * s * t * v, itemsize * (s * v + s * t + t * v)


def round_flops(row: dict, sim: dict, costs: dict) -> float:
    """Model operations of one logged sync round, from its counts:

      training      n_trained lanes * iters * batch training samples
      measurement   the trained step's error and accuracy sweeps (2
                    forwards per sample of every active device), and the
                    accuracy sweep after the transfer (1 forward per
                    sample of each active device)
      Algorithm 1   one pair estimate per re-estimated pair (every
                    active pair in round 0)
      transfer      the combine, 2 * P * P * V
    """
    n_act = row["n_active"]
    ops = row["n_trained"] * sim["train_iters"] * sim["batch"] * \
        costs["train_sample"]
    ops += 3 * n_act * sim["samples_per_device"] * costs["eval_sample"]
    pairs = row["n_reestimated"]
    if row["round"] == 0:
        pairs += n_act * (n_act - 1) // 2
    ops += pairs * costs["pair"]
    ops += alpha_combine_cost(n_act, n_act, costs["transfer_width"])[0]
    return float(ops)
