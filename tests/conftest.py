"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches must
see the host's real (single) device; only launch/dryrun.py forces 512."""
import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


class _Compiles:
    """Counts JAX's backend compiles while open, of the functions whose
    name holds ``name`` (every function by default), in ``n``."""

    def __init__(self, name: str = ""):
        self.name, self.n = name, 0

    def __call__(self, event, duration, fun_name="", **kw):
        from repro.sim.trace.events import BACKEND_COMPILE
        self.n += event == BACKEND_COMPILE and self.name in fun_name

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


@pytest.fixture(scope="session")
def compiles():
    """``with compiles(name) as c:`` counts compiles in ``c.n``."""
    return _Compiles
