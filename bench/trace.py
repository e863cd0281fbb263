"""Reduction of a profiler trace to device busy time, idle gaps and collectives.

The JAX profiler writes an ``.xplane.pb``.  On a TPU it holds one plane per
chip, ``/device:TPU:<i>``, whose ``XLA Ops`` line has one event per executed
HLO operation (a ``while`` or ``conditional`` spans the operations of its
body, so events nest), and a ``/host:CPU`` plane whose threads carry the
``jax.profiler.TraceAnnotation`` spans of the benchmark.  Both are on one
clock, in nanoseconds from the start of the trace.

* busy time: the union (never the sum) of a chip's operation intervals;
* idle gaps: the holes in that union, each named by the innermost benchmark
  annotation open on the host at the gap's midpoint;
* exposed collective time: the part of the union of collective operations
  that no other leaf operation covers;
* top operations: each operation's self time (its duration less that of
  the operations nested in it), summed by operation name and output shape.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: Prefix of the benchmark's own host annotations.
ANNOTATION_PREFIX = "bench."
#: HLO operation kinds that move data between chips.
COLLECTIVE_KINDS = ("all-gather", "all-reduce", "collective-permute", "reduce-scatter", "all-to-all")

_DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
_OP_NAME = re.compile(r"%?([^\s=]+)")


@dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    end: float  # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    """Device operations per chip and the benchmark's host annotations."""

    ops: Dict[int, List[Event]]
    annotations: List[Event]


def op_name(event_name: str) -> str:
    """``%fusion.20 = f32[...] fusion(...)`` -> ``fusion.20``."""
    m = _OP_NAME.match(event_name.strip())
    return m.group(1) if m else event_name


def op_label(event_name: str) -> str:
    """``%fusion.20 = f32[64,8]{1,0:T(8,128)} fusion(...)`` -> ``fusion.20 f32[64,8]``."""
    name = op_name(event_name)
    m = re.match(r"%?\S+\s*=\s*(\(?[a-z0-9]+\[[^\]]*\])", event_name.strip())
    return f"{name} {m.group(1).lstrip('(')}" if m else name


def op_kind(event_name: str) -> str:
    """``fusion.20`` -> ``fusion``; ``all-gather-start.3`` -> ``all-gather-start``."""
    return re.sub(r"\.\d+$", "", op_name(event_name))


def is_collective(event_name: str) -> bool:
    kind = op_kind(event_name)
    return any(c in kind for c in COLLECTIVE_KINDS)


def load_xplane(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    annotations: List[Event] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.fullmatch(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(int(m.group(1)), []).extend(
                        Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events
                    )
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                annotations.extend(
                    Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                    if ev.name.startswith(ANNOTATION_PREFIX)
                )
    for evs in ops.values():
        evs.sort(key=lambda e: (e.start, -e.end))
    annotations.sort(key=lambda e: (e.start, -e.end))
    return Trace(ops=ops, annotations=annotations)


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def covered(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(hi - lo for lo, hi in merge(intervals))


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events if e.end > lo and e.start < hi]


def window(trace: Trace, name: str) -> Optional[Tuple[float, float]]:
    """From the start of the first annotation ``name`` to the end of the last."""
    spans = [a for a in trace.annotations if a.name == name]
    if not spans:
        return None
    return min(a.start for a in spans), max(a.end for a in spans)


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    return covered(clip(events, lo, hi))


def containers(events: Sequence[Event]) -> List[bool]:
    """Whether each event (sorted by start, longest first) has another nested in it."""
    flags = [False] * len(events)
    stack: List[int] = []
    for i, e in enumerate(events):
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= events[stack[-1]].end:
            flags[stack[-1]] = True
        stack.append(i)
    return flags


def collective_exposed_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Time in ``[lo, hi)`` in which a collective runs and no other leaf operation does."""
    leaf = [e for e, c in zip(events, containers(events)) if not c or is_collective(e.name)]
    coll = clip([e for e in leaf if is_collective(e.name)], lo, hi)
    other = clip([e for e in leaf if not is_collective(e.name)], lo, hi)
    both = covered(coll) + covered(other) - covered(coll + other)
    return covered(coll) - both


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Self time (ns) of each operation (name and output shape): duration
    less that of the events nested directly in it."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[int] = []
    child_time = [0.0] * len(events)
    for i, e in enumerate(events):
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            child_time[stack[-1]] += min(e.end, events[stack[-1]].end) - e.start
        stack.append(i)
    for e, kids in zip(events, child_time):
        out[op_label(e.name)] += max(e.dur - kids, 0.0)
    return dict(out)


def idle_gaps(trace: Trace, device: int, lo: float, hi: float) -> List[Tuple[str, float]]:
    """Holes in a chip's busy union inside ``[lo, hi)``, longest first, each
    named by the innermost benchmark annotation open at its midpoint."""
    busy = merge(clip(trace.ops.get(device, []), lo, hi))
    gaps, cursor = [], lo
    for b_lo, b_hi in busy:
        if b_lo > cursor:
            gaps.append((cursor, b_lo))
        cursor = max(cursor, b_hi)
    if hi > cursor:
        gaps.append((cursor, hi))
    named = []
    for g_lo, g_hi in gaps:
        mid = 0.5 * (g_lo + g_hi)
        open_ = [a for a in trace.annotations if a.start <= mid < a.end]
        label = min(open_, key=lambda a: a.dur).name if open_ else "outside the benchmark's annotations"
        named.append((label, (g_hi - g_lo) * 1e-9))
    named.sort(key=lambda x: -x[1])
    return named


@dataclass(frozen=True)
class Reading:
    """What a traced window says about the chips it used."""

    window_s: float
    busy_s: Dict[int, float]
    collective_exposed_s: Dict[int, float]
    top_ops: List[Tuple[str, float]]
    gaps: List[Tuple[str, float]]


def read(trace: Trace, devices: Sequence[int], window_annotation: str, top: int = 10) -> Optional[Reading]:
    """Reduce ``trace`` over the span of ``window_annotation`` on ``devices``."""
    span = window(trace, window_annotation)
    if span is None:
        return None
    lo, hi = span
    busy = {d: busy_ns(trace.ops.get(d, []), lo, hi) * 1e-9 for d in devices}
    exposed = {d: collective_exposed_ns(trace.ops.get(d, []), lo, hi) * 1e-9 for d in devices}
    first = devices[0]
    in_window = [Event(e.name, max(e.start, lo), min(e.end, hi)) for e in trace.ops.get(first, [])
                 if e.end > lo and e.start < hi]
    ops = sorted(self_times(in_window).items(), key=lambda x: -x[1])[:top]
    return Reading(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy,
        collective_exposed_s=exposed,
        top_ops=[(name, ns * 1e-9) for name, ns in ops],
        gaps=idle_gaps(trace, first, lo, hi)[:top],
    )
