"""TraceRecorder: the simulator's one span system.

Every stretch of a tick that costs time is a named phase.  The
executors, both pool backends and the engine bracket each one with
``start(phase)`` / ``stop(span, ...)``, and each completed phase becomes
one structured event::

    {"phase": "train", "tick": 3, "mesh": 0, "t0_ns": 81234567890123,
     "seconds": 0.398, "n_devices": 128}

The phases of a tick are top-level and disjoint, in the order they run:

  ``scenario``        the scenario's mutation (drift blends, first-drift
                      renders, churn, label reveals)
  ``restack``         writing changed devices' rows into the placed
                      client stack (or re-stacking it), after data
                      changed
  ``features``        a featurized client's Algorithm-1 rows: one
                      forward of every device's samples, in the first
                      tick
  ``train``           local training through the pool
  ``divergence``      Algorithm-1 pair estimation through the pool
                      (bootstrap, gossip meetings, budgeted refresh)
  ``refresh_select``  the budgeted refresh's host side around the pool
                      call: dirty scan, pair budget, EMA weights,
                      content keys, then marking the pairs estimated
                      (two events a tick)
  ``solve``           the re-solve of (P) and installing its answer
  ``transfer``        the alpha-mixture (sync) or gossip exchange (async)
  ``eval``            the accuracy sweep
  ``log``             writing the tick's row: serialisation, write and
                      fsync.  It runs after the row is built, so it has
                      no ``*_wall_s`` field; its events carry it.
  ``checkpoint``      a run checkpoint, taken after a tick's row, so it
                      lands in the NEXT tick's ``ckpt_wall_s``

Events serve two readers:

  - per-tick totals surface in the JSONL rows as the ``*_wall_s``
    RoundRecord fields (``tick_wall_fields``, popped by the executors'
    ``_emit``), with ``n_compiled``, the programs JAX compiled during
    the tick;
  - the raw event stream, in memory as ``events`` and optionally as a
    JSONL file (``SimConfig.trace_path``).

Enabled, each phase is also a ``jax.profiler.TraceAnnotation`` named
``sim.<phase>`` that covers exactly the interval its event's ``seconds``
measures, closed after the ``block_until_ready``.  ``t0_ns`` is
``time.perf_counter_ns()`` at the phase's start; the profiler stamps its
host spans on the same clock up to a constant, so one offset places
every event on a device trace.

Disabled (``SimConfig.trace=False``, the default), ``start`` returns
None and ``stop(None)`` returns at once: no annotation, no
``block_until_ready``, no compile listener, so dispatch and overlap are
those of an uninstrumented engine.  Recording reads only the clock and
consumes no PRNG, so traced runs are golden-parity with untraced ones.
"""
from __future__ import annotations

import json
import os
import time
from typing import IO, List, Optional

#: trace phase -> the RoundRecord wall field its per-tick total lands in
#: (``solve`` keeps the solver's own ``solver_wall_s`` in the row, and
#: ``log`` runs after the row is built, so neither has an entry)
WALL_FIELDS = {
    "scenario": "scenario_wall_s",
    "restack": "restack_wall_s",
    "features": "features_wall_s",
    "train": "train_wall_s",
    "divergence": "div_wall_s",
    "refresh_select": "refresh_select_wall_s",
    "transfer": "transfer_wall_s",
    "eval": "eval_wall_s",
    "checkpoint": "ckpt_wall_s",
}

PHASES = ("scenario", "restack", "features", "train", "divergence",
          "refresh_select", "solve", "transfer", "eval", "log",
          "checkpoint")

#: prefix of the profiler span of every phase
SPAN_PREFIX = "sim."

#: JAX's monitoring event for one backend compile
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Span:
    """An open phase: its profiler annotation and start stamp."""

    __slots__ = ("phase", "annotation", "t0_ns")

    def __init__(self, phase: str, annotation, t0_ns: int):
        self.phase = phase
        self.annotation = annotation
        self.t0_ns = t0_ns


class TraceRecorder:
    """Per-phase spans and events; a no-op unless ``cfg.trace``."""

    def __init__(self, cfg):
        self.enabled = bool(getattr(cfg, "trace", False))
        self.mesh = int(getattr(cfg, "mesh", 0) or 0)
        self.events: List[dict] = []
        self.tick = 0
        #: programs compiled since the current tick began
        self.n_compiled = 0
        self._acc = {}                   # phase -> seconds this tick
        self._fh: Optional[IO[str]] = None
        self._listener = None
        if not self.enabled:
            return
        import jax
        self._listener = self._on_duration
        jax.monitoring.register_event_duration_secs_listener(
            self._listener)
        path = getattr(cfg, "trace_path", None)
        if path:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            self._fh = open(path, "w")

    def _on_duration(self, event: str, secs: float, **_):
        if event == BACKEND_COMPILE:
            self.n_compiled += 1

    # ------------------------------------------------------------ timing
    def start(self, phase: str) -> Optional[Span]:
        """Open ``phase``: its profiler span, then its start stamp.  None
        when disabled (the disabled fast path is this attribute read)."""
        if not self.enabled:
            return None
        import jax
        annotation = jax.profiler.TraceAnnotation(SPAN_PREFIX + phase)
        annotation.__enter__()
        return Span(phase, annotation, time.perf_counter_ns())

    def stop(self, span: Optional[Span], *, block=None, **ctx):
        """Close ``span`` (``start``'s return; None returns at once).
        ``block`` (any pytree) is passed to ``jax.block_until_ready``
        first, so the interval covers the phase's device work; the
        profiler span closes after the end stamp."""
        if span is None:
            return
        try:
            if block is not None:
                import jax
                jax.block_until_ready(block)
            t1 = time.perf_counter_ns()
        finally:
            span.annotation.__exit__(None, None, None)
        seconds = (t1 - span.t0_ns) / 1e9
        self._acc[span.phase] = self._acc.get(span.phase, 0.0) + seconds
        event = {"phase": span.phase, "tick": int(self.tick),
                 "mesh": self.mesh, "t0_ns": span.t0_ns,
                 "seconds": seconds, **ctx}
        self.events.append(event)
        if self._fh is not None:
            self._fh.write(json.dumps(event, default=float) + "\n")
            self._fh.flush()

    # ------------------------------------------------- per-tick surface
    def begin_tick(self, t: int):
        self.tick = int(t)
        self.n_compiled = 0

    def tick_wall_fields(self) -> dict:
        """Pop this tick's per-phase totals as RoundRecord field values
        ({} when disabled, so the fields keep their 0.0 defaults)."""
        if not self.enabled:
            return {}
        out = {field: self._acc.pop(phase, 0.0)
               for phase, field in WALL_FIELDS.items()}
        self._acc.clear()
        return out

    def close(self):
        if self._listener is not None:
            import jax
            jax.monitoring.unregister_event_duration_listener(
                self._listener)
            self._listener = None
        if self._fh is not None:
            self._fh.close()
            self._fh = None
