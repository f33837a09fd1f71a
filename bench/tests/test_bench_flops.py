"""Operation and byte counts the benchmark keeps, against hand counts:
the CNN's from its reference file's ``costs``, the round's and the
combine's from the model-free ``harness.flops``."""
import jax
import pytest

import _bench_path
from harness import flops, spec

CNN = spec.reference_module(spec.load_cell(_bench_path.ROOT,
                                           "sync-static-n128"))
SIM = {"train_iters": 30, "batch": 10, "div_tau": 1, "div_T": 8,
       "samples_per_device": 100}


def test_cnn_forward_multiply_adds():
    # conv1 432,000 + conv2 320,000 + fc1 40,960 + fc2 1,280
    assert CNN.cnn_forward_macs(10) == 794_240
    assert CNN.cnn_forward_flops(10) == 1_588_480
    assert CNN.cnn_forward_macs(2) == 794_240 - 128 * 8
    assert CNN.costs(SIM)["train_sample"] == 3 * 1_588_480
    assert CNN.costs(SIM)["eval_sample"] == 1_588_480


def test_cnn_parameter_count():
    assert CNN.cnn_params(10) == 48_158
    assert CNN.costs(SIM)["transfer_width"] == 48_158
    # the count is the reference's own leaves
    leaves = CNN.init(jax.random.PRNGKey(0), 10).values()
    assert sum(v.size for v in leaves) == 48_158


def test_alpha_combine_operations_and_bytes():
    ops, nbytes = flops.alpha_combine_cost(128, 128, 48_158)
    assert ops == 2 * 128 * 128 * 48_158
    assert nbytes == 4 * (128 * 48_158 + 128 * 128 + 128 * 48_158)


def _old_round_flops(row, sim, samples):
    """The count as it was made before the client's costs came from its
    reference file, by hand: 10 classes, the domain classifier's 2."""
    f10 = 2 * 794_240
    f2 = 2 * (794_240 - 128 * 8)
    n_act = row["n_active"]
    ops = row["n_trained"] * sim["train_iters"] * sim["batch"] * 3 * f10
    ops += 3 * n_act * samples * f10
    steps = 2 * sim["div_tau"] * sim["div_T"] * sim["batch"]
    pairs = row["n_reestimated"]
    if row["round"] == 0:
        pairs += n_act * (n_act - 1) // 2
    ops += pairs * (steps * 3 + 2 * samples) * f2
    ops += 2 * n_act * n_act * 48_158
    return float(ops)


@pytest.mark.parametrize("row", [
    # round 0: every active pair estimated
    {"round": 0, "n_active": 128, "n_trained": 64, "n_reestimated": 0},
    # static: no pair after round 0
    {"round": 7, "n_active": 128, "n_trained": 66, "n_reestimated": 0},
    # drift: dirty pairs re-estimated
    {"round": 9, "n_active": 128, "n_trained": 65, "n_reestimated": 128},
    {"round": 5, "n_active": 4, "n_trained": 2, "n_reestimated": 3}],
    ids=["round0", "static", "drift", "small"])
def test_round_flops_counts_training_measurement_pairs_and_combine(row):
    got = flops.round_flops(row, SIM, CNN.costs(SIM))
    assert got == _old_round_flops(row, SIM, 100)
    if row["n_active"] == 4:
        f10, f2 = CNN.cnn_forward_flops(10), CNN.cnn_forward_flops(2)
        assert got == (2 * 30 * 10 * 3 * f10 + (2 * 4 + 4) * 100 * f10
                       + 3 * (2 * 8 * 10 * 3 + 200) * f2
                       + 2 * 4 * 4 * CNN.cnn_params(10))
