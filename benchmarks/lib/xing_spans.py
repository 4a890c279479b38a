"""What the Xing cell's per-layer readers share: which steps were
traced, the step records' counts of the wide residual
(``tracing.STEP_COUNTS_MHC``), and the device seconds of the operations
the program runs under the mixing's OWN names (``OpScope.own`` of
``lib/scoped_ops``'s table: ``mhc_pre``, ``mhc_post``, ``mhc_merge``) —
the program's scopes, not result shapes or kernel names, so the readers
read the same whatever implements the mixing.

A program without a wide residual (a parent of the PR that brought it,
another family, or a run without a trace) gives nothing, and the metric
is left out of the line.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from . import scoped_ops
from .program_spans import in_window, window

MIXING = ("mhc_pre", "mhc_post", "mhc_merge")


def wide(h) -> bool:
    return h.counters.get("cfg", {}).get("hc_mult", 1) > 1


def traced_steps(h) -> List[dict]:
    """The traced steps' observations, where the system's residual is
    wider than one stream and the trace was reduced; else none."""
    if h.reduced is None or not wide(h):
        return []
    return [s for s in h.counters.get("steps", []) if s["traced"]]


def traced_pairs(h) -> List[tuple]:
    """(observation, step record) of the traced steps that carry the
    mixing's counts."""
    w = window(h) if wide(h) and h.reduced is not None else None
    if w is None:
        return []
    return [(s, r) for s, r in in_window(w)
            if s["traced"] and "mhc_rows" in r]


def _own_seconds(h) -> Optional[Dict[str, float]]:
    j = scoped_ops.joined(h) if traced_steps(h) else None
    if j is None:
        return None
    out: Dict[str, float] = {}
    for r in j.rows:
        own = getattr(r.rec, "own", "") if r.rec is not None else ""
        if own and r.rec.kind != "control":
            out[own] = out.get(own, 0.0) + r.seconds
    return out


def own_seconds(h) -> Optional[Dict[str, float]]:
    """Traced device seconds by the innermost name the program wrote."""
    return scoped_ops.kept(h, "own_seconds_mhc", _own_seconds)


def mixing_seconds(h) -> float:
    own = own_seconds(h)
    return sum(own.get(n, 0.0) for n in MIXING) if own else 0.0


def ms_a_step(h, names) -> Optional[float]:
    """Device ms a step of everything under ``names``, scaled as
    ``lib/scoped_ops`` scales its parts: the names' share of the traced
    events' seconds times the device-busy time inside a step span."""
    from .trace import busy_inside
    own = own_seconds(h)
    j = scoped_ops.joined(h) if own else None
    pairs = busy_inside(h.reduced, "engine.step") if j else []
    mine = sum(own.get(n, 0.0) for n in names) if own else 0.0
    if not pairs or mine <= 0 or j.total_s <= 0:
        return None
    step_ms = 1e3 * sum(b for _, b in pairs) / len(pairs)
    return step_ms * mine / j.total_s
