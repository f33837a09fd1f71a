"""Trace subsystem: the simulator's per-phase spans and events
(``events.TraceRecorder``; see docs/architecture.md#trace).

Both executors, both pool backends and the engine bracket every phase of
a tick with the recorder.  Enabled, each phase is a ``sim.<phase>``
profiler span on the device trace's clock and one event; per-tick totals
land in the JSONL rows.  Disabled (the default), every call is a no-op.
"""
from repro.sim.trace.events import PHASES, WALL_FIELDS, TraceRecorder

__all__ = ["PHASES", "TraceRecorder", "WALL_FIELDS"]
