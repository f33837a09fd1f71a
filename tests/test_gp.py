"""GP machinery: the AGM monomial bound (Lemma 2) as a property test."""
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.gp import Monomial, Posynomial, pack_monomial, \
    pack_posynomial


@st.composite
def posynomials(draw, nvars=3, max_terms=4):
    n_terms = draw(st.integers(1, max_terms))
    terms = []
    for _ in range(n_terms):
        log_c = draw(st.floats(-2.0, 2.0))
        exps = {k: draw(st.floats(-2.0, 2.0)) for k in range(nvars)
                if draw(st.booleans())}
        terms.append(Monomial(log_c, exps))
    return Posynomial(terms)


@given(p=posynomials(), z0=st.lists(st.floats(-1.5, 1.5), min_size=3,
                                    max_size=3),
       z=st.lists(st.floats(-1.5, 1.5), min_size=3, max_size=3))
@settings(max_examples=200, deadline=None)
def test_agm_monomial_is_global_lower_bound(p, z0, z):
    """Lemma 2: g(y) >= g_hat(y) everywhere, tight at y0."""
    z0 = np.array(z0)
    z = np.array(z)
    m = p.agm_monomial(z0)
    g_z = p.value(z)
    ghat_z = np.exp(m.log_value(z))
    assert ghat_z <= g_z * (1 + 1e-6) + 1e-12
    # tightness at the expansion point
    g_z0 = p.value(z0)
    ghat_z0 = np.exp(m.log_value(z0))
    assert abs(ghat_z0 - g_z0) <= 1e-6 * max(1.0, g_z0)


def test_posynomial_algebra():
    p = Posynomial.const(2.0) + Posynomial.var(0, power=2.0)
    z = np.log(np.array([3.0]))
    assert np.isclose(p.value(z), 2.0 + 9.0)
    p2 = p.scale(0.5)
    assert np.isclose(p2.value(z), 0.5 * (2.0 + 9.0))


def test_pack_roundtrip():
    p = Posynomial.const(1.5) + Posynomial.var(1, power=-1.0, coeff=2.0)
    logc, E = pack_posynomial(p, 3)
    z = np.array([0.3, -0.2, 0.9])
    packed_val = np.sum(np.exp(logc + E @ z))
    assert np.isclose(packed_val, p.value(z))
    m = p.agm_monomial(z)
    lc, e = pack_monomial(m, 3)
    assert np.isclose(np.exp(lc + e @ z), np.exp(m.log_value(z)))
