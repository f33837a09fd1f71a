"""Where ``repro.sim`` keeps JAX's persistent compilation cache."""
import os
import types

import pytest

from repro.sim import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fake_jax(monkeypatch):
    """A stand-in for the ``jax`` the helper sees: a chosen backend and a
    config that records updates instead of applying them."""
    calls = []
    fake = types.SimpleNamespace(
        backend="tpu", calls=calls,
        config=types.SimpleNamespace(
            update=lambda name, value: calls.append((name, value))))
    fake.default_backend = lambda: fake.backend
    monkeypatch.setattr(compile_cache, "jax", fake)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    return fake


def test_environment_directory_is_left_to_jax(fake_jax, monkeypatch,
                                              tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert fake_jax.calls == []


def test_accelerator_uses_fixed_directory_in_checkout(fake_jax):
    first = compile_cache.enable_compile_cache()
    assert first == os.path.join(ROOT, ".jax_cache")
    assert compile_cache.enable_compile_cache() == first
    assert fake_jax.calls == [("jax_compilation_cache_dir", first)] * 2
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cpu_backend_sets_no_cache(fake_jax):
    fake_jax.backend = "cpu"
    assert compile_cache.enable_compile_cache() is None
    assert fake_jax.calls == []
