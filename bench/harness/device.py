"""The chip: the TPU check, the persistent compile cache, compile counts
and the device block of the result line.

``CompileStats`` and ``require_tpu`` are copied from the repository's
``chip_smoke.py``.
"""
from __future__ import annotations

import os
import sys

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"

#: the compile cache, at a fixed path inside the checkout (the path is
#: part of the cache key, so it never moves)
CACHE_SUBDIR = os.path.join("bench", ".jax_cache")


class NoChip(SystemExit):
    """Raised when the run finds no TPU, or fewer chips than the cell
    asks for: the run exits non-zero and prints no result."""


def require_tpu(jax, chips: int):
    """The first device; exits unless JAX sees at least ``chips`` TPUs."""
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"bench: needs a TPU, but JAX's first device is "
              f"{dev.platform} ({dev.device_kind})", file=sys.stderr)
        raise NoChip(2)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        raise NoChip(2)
    return dev


def enable_compile_cache(jax, root: str) -> str:
    """Cache every compiled program (no minimum compile time), in
    ``$JAX_COMPILATION_CACHE_DIR`` where set, else inside the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, CACHE_SUBDIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileStats:
    """Backend compile seconds (a persistent-cache hit counts its load)
    and cache hits and misses, from JAX's monitoring events."""

    def __init__(self):
        self.secs = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0

    def on_duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.secs += secs
            self.programs += 1

    def on_event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1
        elif event == CACHE_MISS:
            self.misses += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self.on_duration)
        jax.monitoring.unregister_event_listener(self.on_event)

    def snapshot(self):
        return (self.secs, self.programs, self.hits, self.misses)


def memory_peak_bytes(jax) -> int:
    """Peak bytes in use on the fullest device, as the backend reports."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.local_devices()]
    return max(peaks) if peaks else 0


def device_block(jax, dev) -> dict:
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
