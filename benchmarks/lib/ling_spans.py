"""What the Ling cell's per-layer readers share: which steps were
traced, the step records' counts of the state blocks
(``tracing.STEP_COUNTS_SSM``: a KDA block's state lives in the same
pool under the same counts), and the device seconds of the operations
the program runs under the mixer's OWN names (``OpScope.own`` of
``lib/scoped_ops``'s table: ``kda_in_proj``, ``kda_conv``,
``kda_state_update``, ``kda_chunk_scan``, ``kda_out``) — the program's
scopes, not result shapes (PR 37's rule).

A program without KDA blocks (a parent of the PR that brought them,
another family, or a run without a trace) gives nothing, and the metric
is left out of the line.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from . import costs_ling as costs, scoped_ops
from .program_spans import in_window, window

MIXER = ("kda_in_proj", "kda_conv", "kda_state_update", "kda_chunk_scan",
         "kda_out")


def kda(h) -> bool:
    cfg = h.counters.get("cfg", {})
    return "kda_lower_bound" in cfg and costs.kinds(cfg)["K"] > 0


def traced_steps(h) -> List[dict]:
    """The traced steps' observations, where the system has KDA blocks
    and the trace was reduced; else none."""
    if h.reduced is None or not kda(h):
        return []
    return [s for s in h.counters.get("steps", []) if s["traced"]]


def traced_pairs(h) -> List[tuple]:
    """(observation, step record) of the traced steps that carry the
    state counts."""
    w = window(h) if kda(h) and h.reduced is not None else None
    if w is None:
        return []
    return [(s, r) for s, r in in_window(w)
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
    return scoped_ops.kept(h, "own_seconds_kda", _own_seconds)


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
