"""The reduction from a profiler trace to numbers.

Two halves.  :func:`load_xplane` reads an ``.xplane.pb`` with nothing
but jax (``jax.profiler.ProfileData``) into plain tuples.  Everything
after that is interval arithmetic on those tuples, so it is checked on
hand-made events as well as on the small recorded trace beside this
file (``testdata/small.xplane.pb``).

Times are seconds on the trace's own clock.  The benchmark's host spans
reach the trace as ``jax.profiler.TraceAnnotation`` events whose names
start with ``bench.``; the traced window is the span ``bench.window``.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

Interval = Tuple[float, float]

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
#: device lines that hold one event per executed operation
OP_LINES = ("XLA Ops",)
#: HLO operations that move data between chips
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|^send|^recv")


class Event(NamedTuple):
    name: str
    start: float
    end: float


class Trace(NamedTuple):
    """What the benchmark keeps of one trace."""
    device_ops: Dict[str, List[Event]]   # device plane name -> op events
    host_spans: List[Event]              # the benchmark's own spans


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    host_spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "SparseCore" not in plane.name:
            ops = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in OP_LINES:
                    for e in line.events:
                        s = e.start_ns * 1e-9
                        ops.append(Event(e.name, s,
                                         s + e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        host_spans.append(Event(
                            e.name, s, s + e.duration_ns * 1e-9))
    return Trace({k: v for k, v in device_ops.items() if v}, host_spans)


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """Points of the disjoint sorted ``a`` not in the disjoint sorted
    ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], clip(busy, lo, hi))


def _iv(events: Iterable[Event]) -> List[Interval]:
    return [(e.start, e.end) for e in events]


# --------------------------------------------------------------------------
# the reduction
# --------------------------------------------------------------------------

class Reduced(NamedTuple):
    window: Interval
    window_s: float
    busy_s: float                      # mean over devices
    busy_s_by_device: Dict[str, float]
    op_seconds: Dict[str, float]       # by op name, mean over devices
    collective_s: float                # mean over devices
    collective_exposed_s: float        # mean over devices
    idle_by_span: Dict[str, float]     # on the busiest-idle device
    spans: List[Event]                 # host spans inside the window
    busy_by_device: Dict[str, List[Interval]]


_HLO_HEAD = re.compile(r"^%?([\w\-.]+?)(?:\.\d+)? = (\(?\w+\[[\d,]*\])")
_HLO_OPCODE = re.compile(r" ([\w\-]+)\(")


def base_name(name: str) -> str:
    """Operations of one kind add up.  A device op's name is its whole
    HLO line (``%fusion.12 = bf16[8,128]{1,0} fusion(...)``): keep the
    stem, the opcode and the (first) result shape.  ``fusion.123`` ->
    ``fusion``."""
    head = _HLO_HEAD.match(name)
    if head:
        op = _HLO_OPCODE.search(name, head.end())
        return (f"{head.group(1)} {op.group(1) if op else '?'} "
                f"{head.group(2).lstrip('(')}")[:96]
    return (re.sub(r"[.\d]+$", "", name) or name)[:96]


def window_of(trace: Trace) -> Interval:
    w = [s for s in trace.host_spans if s.name == WINDOW_SPAN]
    if w:
        return (w[0].start, w[-1].end)
    ops = [e for evs in trace.device_ops.values() for e in evs]
    if not ops:
        raise ValueError("trace has neither a window span nor device ops")
    return (min(e.start for e in ops), max(e.end for e in ops))


def reduce_trace(trace: Trace) -> Reduced:
    lo, hi = window_of(trace)
    n = max(len(trace.device_ops), 1)
    busy_by, busy_s_by = {}, {}
    op_seconds: Dict[str, float] = {}
    coll_s = exposed_s = 0.0
    for dev, evs in trace.device_ops.items():
        evs = [e for e in evs if e.end > lo and e.start < hi]
        busy = clip(union(_iv(evs)), lo, hi)
        busy_by[dev], busy_s_by[dev] = busy, measure(busy)
        for e in evs:
            d = min(e.end, hi) - max(e.start, lo)
            op_seconds[e.name] = op_seconds.get(e.name, 0.0) + d / n
        coll = clip(union(_iv(e for e in evs
                              if COLLECTIVE.search(e.name))), lo, hi)
        comp = clip(union(_iv(e for e in evs
                              if not COLLECTIVE.search(e.name))), lo, hi)
        coll_s += measure(coll) / n
        exposed_s += measure(subtract(coll, comp)) / n
    spans = [s for s in trace.host_spans
             if s.name != WINDOW_SPAN and s.end > lo and s.start < hi]
    idle_by_span: Dict[str, float] = {}
    if busy_by:
        worst = min(busy_s_by, key=busy_s_by.get)
        idle = gaps(busy_by[worst], lo, hi)
        for name in sorted({s.name for s in spans}):
            cover = union(_iv(s for s in spans if s.name == name))
            t = measure(idle) - measure(subtract(idle, cover))
            idle_by_span[name[len(SPAN_PREFIX):]] = t
        # spans of different names may nest; what no span covers
        covered = union(_iv(spans))
        idle_by_span["(no span)"] = measure(subtract(idle, covered))
    return Reduced((lo, hi), hi - lo,
                   sum(busy_s_by.values()) / n, busy_s_by, op_seconds,
                   coll_s, exposed_s, idle_by_span, spans, busy_by)


def busy_inside(red: Reduced, span_name: str) -> List[Tuple[float, float]]:
    """For each host span of that name inside the window: (its length,
    the device-busy time inside it, on the first device)."""
    if not red.busy_by_device:
        return []
    busy = red.busy_by_device[sorted(red.busy_by_device)[0]]
    out = []
    full = SPAN_PREFIX + span_name
    for s in red.spans:
        if s.name == full and s.start >= red.window[0] \
                and s.end <= red.window[1]:
            out.append((s.end - s.start,
                        measure(clip(busy, s.start, s.end))))
    return out


def seconds_matching(red: Reduced, pattern: str) -> float:
    """Device seconds (mean over devices) of the operations whose name
    matches the regular expression."""
    rx = re.compile(pattern)
    return sum(t for name, t in red.op_seconds.items() if rx.search(name))


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The contract's optional ``breakdown``: the device operations that
    took most time (same kinds added up) and the idle time by what the
    host was doing."""
    kinds: Dict[str, float] = {}
    for name, t in red.op_seconds.items():
        k = base_name(name)
        kinds[k] = kinds.get(k, 0.0) + t
    ops = sorted(kinds.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle if v > 0]}
