"""Communication-energy model (eq. 14 + Sec. V determination)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.energy import EnergyModel, dbm_to_watts


def test_dbm_conversion():
    assert dbm_to_watts(30.0) == pytest.approx(1.0)
    assert dbm_to_watts(23.0) == pytest.approx(0.1995, rel=1e-3)


def test_sampled_model_ranges(rng):
    em = EnergyModel.sample(8, rng)
    off = ~np.eye(8, dtype=bool)
    k = em.K[off]
    # K = M/R * P * 1e-3 (kJ): bounds from P in [23,25] dBm, R in [63,85] Mbps
    lo = 1e9 / 85e6 * dbm_to_watts(23.0) * 1e-3
    hi = 1e9 / 63e6 * dbm_to_watts(25.0) * 1e-3
    assert np.all(k >= lo - 1e-9) and np.all(k <= hi + 1e-9)
    assert np.all(np.diag(em.K) == 0)


def test_energy_gate_behavior():
    em = EnergyModel(K=np.array([[0.0, 1.0], [1.0, 0.0]]), eps_e=1e-2)
    a = np.zeros((2, 2))
    assert em.energy(a) == 0.0
    a[0, 1] = 0.5
    # alpha/(alpha+eps) ~ 0.98: near-full link cost once active
    assert em.energy(a) == pytest.approx(0.5 / 0.51, rel=1e-6)
    a2 = np.zeros((2, 2))
    a2[0, 1] = 0.9
    # same link active at different weight: nearly the same energy (the
    # paper's discrete-threshold behavior)
    assert abs(em.energy(a2) - em.energy(a)) < 0.02


@given(st.integers(2, 8))
@settings(max_examples=20, deadline=None)
def test_transmissions_counts_active_offdiagonal(n):
    rng = np.random.default_rng(n)
    em = EnergyModel.sample(n, rng)
    a = np.zeros((n, n))
    a[0, n - 1] = 0.7
    assert em.transmissions(a) == 1
    np.fill_diagonal(a, 0.9)     # diagonal never counts
    assert em.transmissions(a) == 1


def test_tpu_link_adaptation():
    em = EnergyModel.for_tpu_links(4, model_bytes=4e9)
    assert em.K[0, 1] == pytest.approx(4e9 / 50e9)
    assert np.all(np.diag(em.K) == 0)
