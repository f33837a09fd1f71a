"""From the profiler's trace to device busy time, idle share, kernel time,
and idle gaps named by what the host was doing.

The reduction works on plain tuples, so a test can hand it a synthetic
trace; ``load`` turns an ``.xplane.pb`` into them:

  device ops   (start_ns, duration_ns, name) per chip, from the "XLA Ops"
               line of each ``/device:TPU:<n>`` plane; the name is the
               HLO instruction's (``alpha_combine_flat.1``, ``while.13``)
  host spans   (start_ns, duration_ns, name) of the annotations the
               harness writes around each round and each call into a
               layer, and of the program's phase spans (``sim.<phase>``,
               one per TraceRecorder event); both are
               ``jax.profiler.TraceAnnotation``s
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Sequence, Tuple

Event = Tuple[float, float, str]

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: the annotation the harness puts around the whole window
WINDOW_SPAN = "bench.window"
ROUND_SPAN = "bench.round"
#: host spans kept: the harness's own and the program's phases
SPAN_PREFIXES = ("bench.", "sim.")


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for s, d, name in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b - a, name))
    return out


def busy_ns(events: Sequence[Event]) -> float:
    return sum(e - s for s, e in union([(s, s + d) for s, d, _ in events]))


def idle_gaps(events: Sequence[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """(start, end) of each stretch of [lo, hi] with no op running."""
    gaps, cur = [], lo
    for s, e in union([(s, s + d) for s, d, _ in events]):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def op_totals(events: Sequence[Event]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for _, d, name in events:
        out[name] = out.get(name, 0.0) + d
    return out


def label_gap(gap: Tuple[float, float], spans: Sequence[Event]) -> str:
    """The innermost host span, the harness's or the program's, that
    covers the gap's midpoint, with the round it lies in; "host" where
    no span covers it."""
    mid = (gap[0] + gap[1]) / 2
    covering = [(d, name) for s, d, name in spans if s <= mid <= s + d]
    if not covering:
        return "host"
    rounds = [n for _, n in covering if n.startswith(ROUND_SPAN)]
    inner = min((c for c in covering
                 if not c[1].startswith((ROUND_SPAN, WINDOW_SPAN))),
                default=None)
    where = rounds[0].replace(ROUND_SPAN + " ", "round ") if rounds \
        else "between rounds"
    return f"{inner[1]} ({where})" if inner else f"host ({where})"


@dataclasses.dataclass
class Summary:
    window_ns: float
    busy_ns: float                       # averaged over chips
    ops: List[Event]                     # chip 0, clipped to the window
    gaps: List[Tuple[float, str]]        # (ns, label), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def time_of(self, match) -> Tuple[float, int]:
        """(ns, count) of the ops whose name ``match`` accepts."""
        hits = [d for _, d, name in self.ops if match(name)]
        return float(sum(hits)), len(hits)


def summarize(device_ops: Sequence[Sequence[Event]],
              host_spans: Sequence[Event]) -> Summary:
    """Reduce one traced window; its bounds are the window span's."""
    win = [(s, d) for s, d, n in host_spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError("trace has no window span")
    lo, hi = win[0][0], win[0][0] + win[0][1]
    per_chip = [clip(ops, lo, hi) for ops in device_ops]
    busy = sum(busy_ns(ops) for ops in per_chip) / max(len(per_chip), 1)
    ops0 = per_chip[0] if per_chip else []
    gaps = sorted(((e - s, label_gap((s, e), host_spans))
                   for s, e in idle_gaps(ops0, lo, hi)), reverse=True)
    return Summary(window_ns=hi - lo, busy_ns=busy, ops=ops0, gaps=gaps)


def breakdown(summary: Summary, top: int = 10) -> dict:
    totals = sorted(op_totals(summary.ops).items(), key=lambda kv: -kv[1])
    return {"device_ops": [[n, t / 1e9] for n, t in totals[:top]],
            "idle_gaps": [[n, g / 1e9] for g, n in summary.gaps[:top]]}


# ------------------------------------------------------------- loading
def op_name(text: str) -> str:
    """The instruction's name from an op event's HLO text
    (``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``)."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def load(trace_dir: str) -> Tuple[List[List[Event]], List[Event]]:
    """Device ops per chip, and the harness's and the program's host
    spans, from the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    chips: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = chips.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((ev.start_ns, ev.duration_ns,
                                op_name(ev.name)) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append((ev.start_ns, ev.duration_ns,
                                      ev.name))
    return [chips[k] for k in sorted(chips)], spans

