"""Sec. IV-A bound terms: Massart, empirical errors, S_i / T_ij, Cor. 1."""
import math

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import bounds as B


def test_massart_constant():
    assert B.massart_rad_bound() == pytest.approx(math.sqrt(2 * math.log(2)))


def test_confidence_term_shrinks_with_n():
    assert B.confidence_term(10, 0.05) > B.confidence_term(1000, 0.05)
    assert B.confidence_term(1000, 0.05) > 0


def test_empirical_error_unlabeled_counted_as_one():
    correct = np.array([True, True, False, True])
    labeled = np.array([True, True, True, False])   # last datum unlabeled
    # 1 wrong labeled + 1 unlabeled = 2 of 4
    assert B.empirical_error(correct, labeled) == pytest.approx(0.5)


def test_empirical_error_all_unlabeled_is_one():
    correct = np.array([True, True])
    labeled = np.array([False, False])
    assert B.empirical_error(correct, labeled) == 1.0


def test_hypothesis_disagreement():
    a = np.array([0, 1, 1, 0])
    b = np.array([0, 1, 0, 1])
    assert B.hypothesis_disagreement(a, b) == pytest.approx(0.5)


def test_paper_constants_in_eq17_eq18():
    """Verbatim eq. (17)/(18) keep the Massart offsets."""
    s = B.source_term(0.1, 100, include_constants=True)
    t = B.target_term(0.1, 0.5, 100, 100, include_constants=True)
    assert s == pytest.approx(0.1 + 2 * B.SQRT_2LOG2
                              + B.confidence_term(100, 0.05))
    assert t > 10 * B.SQRT_2LOG2


def test_calibrated_surface_drops_offsets_from_T():
    bt = B.BoundTerms(eps_hat=np.array([0.1, 1.0]),
                      n_data=np.array([100, 100]),
                      div_hat=np.array([[0.0, 0.4], [0.4, 0.0]]))
    S = bt.S()
    T = bt.T()
    # S keeps Massart + confidence
    assert S[0] == pytest.approx(0.1 + 2 * B.SQRT_2LOG2
                                 + B.confidence_term(100, 0.05))
    # T keeps only the signal terms
    assert T[0, 1] == pytest.approx(0.1 + 0.2)
    assert T[1, 0] == pytest.approx(1.0 + 0.2)


@given(alpha=st.lists(st.floats(0.01, 1.0), min_size=2, max_size=6),
       eps=st.lists(st.floats(0.0, 1.0), min_size=6, max_size=6),
       div=st.lists(st.floats(0.0, 2.0), min_size=6, max_size=6))
@settings(max_examples=50, deadline=None)
def test_corollary1_rhs_monotone_in_eps_and_div(alpha, eps, div):
    """Cor. 1 RHS grows when any source error or divergence grows."""
    k = min(len(alpha), len(eps), len(div))
    a = np.array(alpha[:k])
    a = a / a.sum()
    e = np.array(eps[:k])
    d = np.array(div[:k])
    n_src = np.full(k, 200)
    base = B.corollary1_rhs(a, e, d, n_src, 200)
    bigger = B.corollary1_rhs(a, e + 0.1, d, n_src, 200)
    assert bigger >= base - 1e-12
    bigger_d = B.corollary1_rhs(a, e, d + 0.1, n_src, 200)
    assert bigger_d >= base - 1e-12


def test_theorem2_vs_corollary1_ordering():
    """Cor. 1 adds only nonnegative terms to Thm. 2 (Table II structure)."""
    a = np.array([0.5, 0.5])
    e = np.array([0.1, 0.2])
    d = np.array([0.3, 0.4])
    hyp = np.array([0.05, 0.05])
    t2 = B.theorem2_rhs(a, e, d, hyp)
    c1 = B.corollary1_rhs(a, e, d, np.array([100, 100]), 100,
                          hyp_noise=hyp)
    assert c1 > t2
