"""Operations and bytes, counted from shapes and from the round rows.

The client is the paper's CNN on 28x28x3 inputs.  Multiply-adds of one
forward sample, by layer:

  conv1  24*24 outputs * 10 maps * 5*5*3      = 432,000
  conv2   8*8  outputs * 20 maps * 5*5*10     = 320,000
  fc1    320 * 128                            =  40,960
  fc2    128 * classes                        =   1,280 (10 classes)

794,240 multiply-adds (1,588,480 operations) with 10 classes; the
Algorithm-1 domain classifier has 2.  A training sample costs three
forwards (forward, and a backward of twice the work).  Biases,
activations and pooling are not counted.
"""
from __future__ import annotations

TRAIN_FACTOR = 3


def cnn_forward_macs(num_classes: int = 10, hw: int = 28, in_ch: int = 3,
                     k: int = 5, maps=(10, 20), hidden: int = 128) -> int:
    o1 = hw - k + 1                         # 24
    p1 = o1 // 2                            # 12
    o2 = p1 - k + 1                         # 8
    p2 = o2 // 2                            # 4
    conv1 = o1 * o1 * maps[0] * k * k * in_ch
    conv2 = o2 * o2 * maps[1] * k * k * maps[0]
    flat = p2 * p2 * maps[1]
    return conv1 + conv2 + flat * hidden + hidden * num_classes


def cnn_forward_flops(num_classes: int = 10) -> int:
    return 2 * cnn_forward_macs(num_classes)


def cnn_params(num_classes: int = 10) -> int:
    return (5 * 5 * 3 * 10 + 10) + (5 * 5 * 10 * 20 + 20) + \
        (320 * 128 + 128) + (128 * num_classes + num_classes)


def alpha_combine_cost(s: int, t: int, v: int, itemsize: int = 4):
    """(operations, bytes) of one out (T, V) = alpha^T (S, T) @ theta
    (S, V) call: 2 S T V operations; theta and alpha read once, the
    output written once."""
    return 2 * s * t * v, itemsize * (s * v + s * t + t * v)


def round_flops(row: dict, sim: dict, samples: int) -> float:
    """Model operations of one logged sync round, from its counts:

      training      n_trained lanes * iters * batch samples, 3 forwards
      measurement   the trained step's error and accuracy sweeps (2
                    forwards per sample of every active device), and the
                    accuracy sweep after the transfer (1 forward per
                    sample of each active device)
      Algorithm 1   per re-estimated pair (every active pair in round
                    0): 2 classifiers * tau * T steps * batch, 3
                    forwards, then 2 * samples evaluation forwards
      transfer      the combine, 2 * P * P * V
    """
    f10 = cnn_forward_flops(10)
    f2 = cnn_forward_flops(2)
    n_act = row["n_active"]
    trained = row["n_trained"]
    ops = trained * sim["train_iters"] * sim["batch"] * TRAIN_FACTOR * f10
    ops += 3 * n_act * samples * f10
    steps = 2 * sim["div_tau"] * sim["div_T"] * sim["batch"]
    pairs = row["n_reestimated"]
    if row["round"] == 0:
        pairs += n_act * (n_act - 1) // 2
    ops += pairs * (steps * TRAIN_FACTOR + 2 * samples) * f2
    ops += alpha_combine_cost(n_act, n_act, cnn_params(10))[0]
    return float(ops)
