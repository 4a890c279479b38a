"""Idle device time by the PROGRAM's phase, on the trace's clock.

The device trace and the benchmark's ``bench.engine.step`` spans are on
the profiler's clock; the program's step records (six disjoint phases a
``step()``: admit, build, launch, sync, sample, account) are on
``time.perf_counter_ns``.  Both describe the same calls, so the k-th
traced span and the k-th traced record give one offset between the
clocks; its median lays the phases over the device's busy intervals
without loading anything the trace's loader dropped.

``sync`` is where the host waits for the device: a device idle THEN
waits for the runtime or its queue, not for Python.  Idle under any
other phase, or between two calls, waits for the host.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional

from .harness import say
from .program_spans import PHASES, SPAN_PREFIX, in_window, window
from .scoped_ops import kept
from .trace import SPAN_PREFIX as BENCH, clip, gaps, measure, subtract, union

#: the clocks are taken to agree only if the pairs do, this closely
MAX_OFFSET_SPREAD_S = 50e-6


def clock_offset(spans, records) -> Optional[dict]:
    """trace clock - perf_counter, from paired (span, record): the mean
    of the start's and the end's difference of each pair (the span
    encloses the record by the call's own entry and exit), then the
    median over pairs and the distance between the quartiles."""
    if not spans or len(spans) != len(records):
        return None
    d = [((sp.start - r["start_ns"] * 1e-9) + (sp.end - r["end_ns"] * 1e-9))
         / 2.0 for sp, r in zip(spans, records)]
    med = statistics.median(d)
    q = statistics.quantiles(d, n=4) if len(d) > 1 else [med, med, med]
    return {"offset_s": med, "spread_s": q[2] - q[0],
            "worst_s": max(abs(x - med) for x in d), "pairs": len(d)}


def idle_by_phase(busy, lo: float, hi: float, records, offset_s: float
                  ) -> Dict[str, float]:
    """Seconds of [lo, hi] in which the device ran nothing, by the phase
    the host was in (records' clocks moved by ``offset_s``);
    ``"(between calls)"`` is what no phase covers."""
    idle = gaps(busy, lo, hi)
    out: Dict[str, float] = {}
    every = []
    for phase in PHASES:
        iv = [(a * 1e-9 + offset_s, b * 1e-9 + offset_s)
              for r in records for name, a, b in r["phases"]
              if name == SPAN_PREFIX + phase]
        every += iv
        out[phase] = measure(idle) - measure(subtract(idle, union(iv)))
    out["(between calls)"] = measure(subtract(idle, union(every)))
    return out


def _books(h) -> Optional[dict]:
    books = None
    red = h.reduced
    w = window(h) if red is not None and red.busy_by_device else None
    if w is not None:
        records = [r for s, r in in_window(w) if s["traced"]]
        spans = sorted((s for s in red.spans
                        if s.name == BENCH + "engine.step"),
                       key=lambda s: s.start)
        off = clock_offset(spans, records)
        if off is None:
            say(f"phase idle: {len(spans)} traced bench.engine.step spans "
                f"but {len(records)} traced step records; not reported")
        elif off["spread_s"] > MAX_OFFSET_SPREAD_S:
            say(f"phase idle: the clocks' offset spreads "
                f"{off['spread_s'] * 1e6:.1f} us over {off['pairs']} pairs "
                f"(limit {MAX_OFFSET_SPREAD_S * 1e6:.0f}); not reported")
        else:
            busy = red.busy_by_device[sorted(red.busy_by_device)[0]]
            lo, hi = red.window
            by = idle_by_phase(busy, lo, hi, records, off["offset_s"])
            n = len(records)
            total = sum(by.values())
            books = {"sync": 1e3 * by["sync"] / n,
                     "host": 1e3 * (total - by["sync"]) / n}
            say(f"phase idle over {n} traced steps: clocks' offset "
                f"{off['offset_s']:.6f}s, spread {off['spread_s'] * 1e6:.1f}"
                f" us (worst pair {off['worst_s'] * 1e6:.1f}); device idle "
                f"{total * 1e3:.3f} ms of the {hi - lo:.3f}s traced "
                f"({100.0 * total / (hi - lo):.3f} %); ms a step by the "
                f"host's phase: "
                + ", ".join(f"{k} {1e3 * v / n:.4f}" for k, v in by.items()))
            longest = max(records, key=lambda r: r["end_ns"] - r["start_ns"])
            parts = []
            for name, a, b in longest["phases"]:
                a, b = (a * 1e-9 + off["offset_s"], b * 1e-9 + off["offset_s"])
                share = measure(clip(busy, a, b)) / (b - a) if b > a else 0.0
                parts.append(f"{name[len(SPAN_PREFIX):]} "
                             f"{(b - a) * 1e3:.3f} ms busy "
                             f"{100.0 * share:.1f} %")
            say(f"  the longest traced step (seq {longest.get('seq')}, "
                f"{(longest['end_ns'] - longest['start_ns']) / 1e6:.3f} ms): "
                + ", ".join(parts))
    return books


def idle_ms(h, which: str) -> Optional[float]:
    books = kept(h, "phase_idle", _books)
    return None if books is None else books[which]
