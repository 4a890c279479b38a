"""What the Nemotron cell's per-layer readers share: which steps were
traced, the step records' counts of the state-space blocks
(``tracing.STEP_COUNTS_SSM``), and the device seconds of the operations
the program runs under the mixer's OWN names (``OpScope.own`` of
``lib/scoped_ops``'s table: ``ssm_in_proj``, ``ssm_conv``, ``ssm_scan``,
``ssm_out``).

A program without state-space blocks (a parent of the PR that brought
them, another family, or a run without a trace) gives nothing, and the
metric is left out of the line.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from . import scoped_ops
from .program_spans import in_window, window

MIXER = ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_out")


def hybrid(h) -> bool:
    return "M" in str(h.counters.get("cfg", {}).get(
        "hybrid_override_pattern", ""))


def traced_steps(h) -> List[dict]:
    """The traced steps' observations, where the system has state-space
    blocks and the trace was reduced; else none."""
    if h.reduced is None or not hybrid(h):
        return []
    return [s for s in h.counters.get("steps", []) if s["traced"]]


def traced_records(h) -> List[dict]:
    """The step records of the traced steps that carry the state-space
    counts."""
    w = window(h) if hybrid(h) and h.reduced is not None else None
    if w is None:
        return []
    return [r for s, r in in_window(w)
            if s["traced"] and "ssm_slots_live" in r]


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
    return scoped_ops.kept(h, "own_seconds", _own_seconds)


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
