"""Operation and byte counts the benchmark keeps, against hand counts."""
import _bench_path  # noqa: F401
from harness import flops


def test_cnn_forward_multiply_adds():
    # conv1 432,000 + conv2 320,000 + fc1 40,960 + fc2 1,280
    assert flops.cnn_forward_macs(10) == 794_240
    assert flops.cnn_forward_flops(10) == 1_588_480
    assert flops.cnn_forward_macs(2) == 794_240 - 128 * 8


def test_cnn_parameter_count():
    assert flops.cnn_params(10) == 48_158


def test_alpha_combine_operations_and_bytes():
    ops, nbytes = flops.alpha_combine_cost(128, 128, 48_158)
    assert ops == 2 * 128 * 128 * 48_158
    assert nbytes == 4 * (128 * 48_158 + 128 * 128 + 128 * 48_158)


def test_round_flops_counts_training_measurement_pairs_and_combine():
    sim = {"train_iters": 30, "batch": 10,
           "div_tau": 1, "div_T": 8}
    row = {"round": 5, "n_active": 4, "n_trained": 2, "n_reestimated": 3}
    f10, f2 = flops.cnn_forward_flops(10), flops.cnn_forward_flops(2)
    want = (2 * 30 * 10 * 3 * f10 + (2 * 4 + 4) * 100 * f10
            + 3 * (2 * 8 * 10 * 3 + 200) * f2
            + 2 * 4 * 4 * flops.cnn_params(10))
    assert flops.round_flops(row, sim, 100) == want
