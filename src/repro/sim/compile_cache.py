"""Where JAX keeps the simulator's persistent compilation cache.

Compiling the round's programs (training scan, Algorithm-1 pair chunk,
solver loop, transfer kernel) takes tens of seconds on a TPU, and a run
pays it again in every fresh process unless compiled programs are cached
on disk.  A run finds what an earlier run stored only if both use the
same directory, so it never depends on a temporary or per-process path:
it is either the one the environment names in
``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself) or a
fixed directory inside the checkout.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

#: the cache directory used when the environment names none
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on for an accelerator and return its
    directory (None on the CPU, whose compiles take milliseconds and
    whose cached entries load with host-feature warnings).  Call it
    before the first compilation: JAX opens the cache once per
    process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
