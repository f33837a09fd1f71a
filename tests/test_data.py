"""Synthetic domains + federated partitioning."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.data import (DOMAINS, NUM_CLASSES, build_network,
                        dirichlet_label_split, make_domain_dataset,
                        render_digit, LMStream, LMStreamConfig)


def test_render_shapes_and_range(rng):
    for dom in DOMAINS:
        img = render_digit(3, dom, rng)
        assert img.shape == (28, 28, 3)
        assert img.min() >= 0.0 and img.max() <= 1.0


def test_domains_are_visually_distinct(rng):
    """Mean inter-domain pixel distance far exceeds intra-domain."""
    sets = {d: np.stack([render_digit(5, d, rng) for _ in range(12)])
            for d in DOMAINS}
    intra = np.mean([np.abs(s[:6] - s[6:]).mean() for s in sets.values()])
    inter = np.abs(sets["M"].mean(0) - sets["MM"].mean(0)).mean()
    assert inter > intra * 0.5


def test_mm_is_colored_m_is_gray(rng):
    m = render_digit(2, "M", rng)
    mm = render_digit(2, "MM", rng)
    assert np.abs(m[..., 0] - m[..., 1]).max() < 1e-6       # grayscale
    assert np.abs(mm[..., 0] - mm[..., 1]).mean() > 0.02    # colored


@given(num_devices=st.integers(2, 8), alpha=st.floats(0.1, 10.0))
@settings(max_examples=15, deadline=None)
def test_dirichlet_split_is_partition(num_devices, alpha):
    rng = np.random.default_rng(7)
    labels = rng.integers(0, NUM_CLASSES, size=300)
    parts = dirichlet_label_split(labels, num_devices, alpha, rng)
    allidx = np.concatenate(parts)
    assert len(allidx) == 300
    assert len(np.unique(allidx)) == 300


def test_build_network_settings():
    for setting in ["M", "M+MM", "M//U"]:
        devs = build_network(setting, num_devices=4, samples_per_device=30,
                             seed=0)
        assert len(devs) == 4
        n_lab = [d.n_labeled for d in devs]
        assert sum(1 for x in n_lab if x == 0) >= 1   # some fully unlabeled
        for d in devs:
            assert np.all(d.labels[d.labeled_mask] ==
                          d.true_labels[d.labeled_mask])
            assert np.all(d.labels[~d.labeled_mask] == -1)


def test_split_network_devices_single_domain():
    devs = build_network("M//MM", num_devices=4, samples_per_device=20,
                         seed=1)
    for d in devs:
        assert len(np.unique(d.domain_ids)) == 1


def test_lm_stream_shapes_and_shift():
    st_ = LMStream(LMStreamConfig(vocab_size=256, num_topics=4,
                                  topic_vocab=32))
    t, l = st_.sample(3, 20, seed=5)
    assert t.shape == (3, 20) and l.shape == (3, 20)
    assert (t[:, 1:] == l[:, :-1]).all()
    assert t.max() < 256
    t2, _ = st_.sample(3, 20, seed=5)
    assert (t == t2).all()               # deterministic per seed
