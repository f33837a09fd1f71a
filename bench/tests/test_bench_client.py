"""The client model is the reference file's alone: the harness runs a
second client (``_token_client``: int32 token ids, a nested per-device
tree, a shared frozen embedding) through capture, follow, compare, the
round's operation count and the roofline reader with no edit of its
own, and names no model itself."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _bench_path
import _token_client as tok
from harness import flops, profile, replay, spec
from harness.capture import Capture
from harness.runner import Window

CLASSES = 3
SIM = {"train_iters": 2, "batch": 4, "lr": 0.1, "div_ema": 0.5,
       "div_tau": 1, "div_T": 2, "div_key_mode": "positional",
       "samples_per_device": 12, "devices": 4, "mesh": 1}
SEED = 2147483659
PATHS = {"adapter/down", "adapter/up", "head/b", "head/w"}


def _ticks(n=4):
    """Two rounds over token devices of different lengths: device 0 the
    source of target 1 in the second, pairs measured in each."""
    devices = tok.make_devices(SEED % 1000, n, (12, 9), CLASSES)
    alpha0, psi0 = np.zeros((n, n)), np.zeros(n)
    alpha1, psi1 = np.zeros((n, n)), np.zeros(n)
    alpha1[0, 1], psi1[1] = 1.0, 1.0
    pairs0 = np.array([[0, 1], [0, 2], [1, 3], [2, 3]], np.int32)
    return [{"tick": t, "data": devices, "active": np.ones(n, bool),
             "transfer": tr, "row": {"events": []},
             "div_calls": [("update", pr)]}
            for t, tr, pr in ((0, (alpha0, psi0), pairs0),
                              (1, (alpha1, psi1), pairs0[:2]))]


def _follow(dtype=jnp.float32):
    ticks = _ticks()
    pairs = replay.sample_pairs(ticks, SEED, 3)
    side = replay.follow(replay.Follower(tok, SIM, SEED, dtype, CLASSES),
                         ticks, pairs)
    return side, ticks


def test_the_token_client_is_followed_alike_twice():
    a, ticks = _follow()
    b, _ = _follow()
    assert set(a["params"]) == PATHS and set(a["init"]) == PATHS
    assert a["params"]["head/w"].shape == (4, tok.EMBED, CLASSES)
    assert a["div"]
    got = replay.compare(a, b, ticks)
    assert got == {k: 0.0 for k in got}
    # the leaves are compared by path: one nested leaf moved reads
    moved = dict(a["params"], **{"adapter/up": a["params"]["adapter/up"]
                                 + 1.0})
    assert replay.param_gap(moved, b["params"], b["init"]) > 0.5


def test_bfloat16_follower_keeps_token_ids_exact(monkeypatch):
    seen = []

    def spy(fn, *positions):
        def call(*a, **k):
            seen.extend(a[i] for i in positions)
            return fn(*a, **k)
        return call

    monkeypatch.setattr(tok, "train", spy(tok.train, 1))
    monkeypatch.setattr(tok, "pair_divergence",
                        spy(tok.pair_divergence, 1, 3))
    _follow(jnp.bfloat16)
    ticks = _ticks()
    want = np.asarray(tok.stack(ticks[0]["data"], jnp.float32)["x"])
    # ids that a cast to bfloat16 would change
    assert (np.asarray(jnp.asarray(want, jnp.bfloat16), np.int64)
            != want).any()
    assert len(seen) == 2 + 2
    for x in seen:
        assert x.dtype == jnp.int32
    for x in seen[:2]:                       # training: the devices' rows
        np.testing.assert_array_equal(np.asarray(x), want)
    rows = {tuple(r) for d in ticks[0]["data"] for r in d.tokens}
    for x in seen[2:]:                       # pairs: rows of real devices
        for lane in np.asarray(x)[:, :9]:
            assert all(tuple(r) in rows for r in lane)


class _Pool:
    def train(self, *a):
        return None

    update_divergences = refresh_divergences = transfer = accuracies = train


class _Engine:
    def __init__(self, params):
        self.pool = _Pool()
        self.state = type("State", (), {"params": params,
                                        "div_hat": np.zeros((2, 2))})()


def test_capture_keeps_the_parameters_by_path():
    params = tok.init(jax.random.PRNGKey(0), CLASSES)
    end = Capture(_Engine(params)).finish()
    assert set(end["params"]) == PATHS
    np.testing.assert_array_equal(end["params"]["head/w"],
                                  params["head"]["w"])
    cnn = spec.reference_module(spec.load_cell(_bench_path.ROOT,
                                               "sync-static-n128"))
    flat = cnn.init(jax.random.PRNGKey(0), 10)
    end = Capture(_Engine(flat)).finish()
    assert sorted(end["params"]) == sorted(flat) == [
        "b1", "b2", "conv1", "conv2", "fc1", "fc2", "fcb1", "fcb2"]


def _roofline_window(costs, n, ns, calls):
    ops = [(float(k) * ns, float(ns), "alpha_combine_flat.1")
           for k in range(calls)]
    summary = profile.Summary(window_ns=ns * calls * 2, busy_ns=ns * calls,
                              ops=ops, gaps=[])
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    return Window(rounds=[{"tick": 0, "wall": 1.0, "row": {
        "round": 3, "n_active": n, "n_trained": 2, "n_reestimated": 1}}],
        seconds=1.0, phases={}, profile=summary,
        sim=dict(SIM, devices=n), peaks=peaks, costs=costs)


def _readers():
    cell = spec.Cell(root=_bench_path.ROOT, workload={"name": "x"},
                     config={}, traffic={}, limits={}, end_to_end=[],
                     per_layer=[{"name": "alpha_combine_roofline"},
                                {"name": "mfu"}])
    return spec.metric_readers(cell)


def test_round_flops_and_readers_take_the_clients_costs():
    costs = tok.costs(SIM, CLASSES)
    v = 2 * 16 * 4 + 17 * 3
    assert costs["transfer_width"] == v == sum(
        x.size for x in jax.tree_util.tree_leaves(
            tok.init(jax.random.PRNGKey(0), CLASSES)))
    f, f2 = 2 * (128 + 48), 2 * (128 + 32)
    row = {"round": 3, "n_active": 4, "n_trained": 2, "n_reestimated": 1}
    want = (2 * 2 * 4 * 3 * f + 3 * 4 * 12 * f
            + 1 * (2 * 1 * 2 * 4 * 3 + 2 * 12) * f2 + 2 * 4 * 4 * v)
    assert flops.round_flops(row, SIM, costs) == want
    r = _readers()
    win = _roofline_window(costs, 4, 1000.0, 3)
    floor = max(2 * 4 * 4 * v / 197e12,
                4 * (4 * v + 4 * 4 + 4 * v) / 819e9)
    assert r["alpha_combine_roofline"](win) == pytest.approx(
        100 * floor * 3 / 3e-6, rel=1e-12)
    assert r["mfu"](win) == pytest.approx(100 * want / 197e12, rel=1e-12)


def test_the_cnn_roofline_counts_are_unchanged():
    """At S = T = 128 the combine reads 2 * 128 * 128 * 48,158
    operations and its bytes, the counts before the client's costs came
    from its reference file."""
    cnn = spec.reference_module(spec.load_cell(_bench_path.ROOT,
                                               "sync-static-n128"))
    win = _roofline_window(cnn.costs(SIM), 128, 2.5e6, 4)
    ops = 2 * 128 * 128 * 48_158
    nbytes = 4 * (128 * 48_158 + 128 * 128 + 128 * 48_158)
    want = 100.0 * max(ops / 197e12, nbytes / 819e9) * 4 / (4 * 2.5e6 / 1e9)
    assert _readers()["alpha_combine_roofline"](win) == want


@pytest.mark.parametrize("folder", ["harness", "metrics"])
def test_the_harness_names_no_model(folder):
    for path in glob.glob(os.path.join(_bench_path.BENCH, folder, "*.py")):
        with open(path) as fh:
            text = fh.read()
        assert not re.search(r"cnn|images", text, re.I), path
        # class counts come from the configuration, not the code
        assert not re.search(r"\binit\([^)]*,\s*\d+\)", text), path
