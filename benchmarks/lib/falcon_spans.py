"""What the Falcon-H1 cell's per-layer readers share: which steps were
traced, the step records' counts of the state pool
(``tracing.STEP_COUNTS_SSM``), and the device seconds of the operations
the program runs under the two mixers' OWN names (``OpScope.own`` of
``lib/scoped_ops``'s table) — the program's scopes, not result shapes
(PR 37's rule).  Within ``ssm_scan`` the state KERNELS (the decode
rows' update and the chunk's state put: the instructions that are
custom calls) are told from the chunk's scan, which is plain XLA, by the
instruction's opcode.

A program without a Falcon-H1 block (a parent of the PR that brought
it, another family, or a run without a trace) gives nothing, and the
metric is left out of the line.
"""

from __future__ import annotations

from typing import Callable, List, Optional

from . import scoped_ops
from .program_spans import in_window, window

SSM = ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_out")
ATTENTION = ("qkv_proj", "cache_write", "attention", "attn_out")


def falcon(h) -> bool:
    cfg = h.counters.get("cfg", {})
    return "mamba_d_ssm" in cfg and "ssm_multipliers" in cfg


def traced_steps(h) -> List[dict]:
    """The traced steps' observations, where the system is a Falcon-H1
    and the trace was reduced; else none."""
    if h.reduced is None or not falcon(h):
        return []
    return [s for s in h.counters.get("steps", []) if s["traced"]]


def traced_pairs(h) -> List[tuple]:
    """(observation, step record) of the traced steps that carry the
    state counts."""
    w = window(h) if falcon(h) and h.reduced is not None else None
    if w is None:
        return []
    return [(s, r) for s, r in in_window(w)
            if s["traced"] and "ssm_slots_live" in r]


def kernel(rec) -> bool:
    return rec.opcode == "custom-call"


def seconds(h, names, only: Optional[Callable] = None) -> float:
    """Traced device seconds of the instructions whose innermost name
    as the program wrote it is one of ``names`` (and ``only(OpScope)``
    holds); 0 where there is nothing to read."""
    j = scoped_ops.joined(h) if traced_steps(h) else None
    if j is None:
        return 0.0
    return sum(r.seconds for r in j.rows
               if r.rec is not None and r.rec.kind != "control"
               and getattr(r.rec, "own", "") in names
               and (only is None or only(r.rec)))


def ms_a_step(h, names, only: Optional[Callable] = None) -> Optional[float]:
    """Device ms a step of those instructions, scaled as
    ``lib/scoped_ops`` scales its parts: their share of the traced
    events' seconds times the device-busy time inside a step span."""
    from .trace import busy_inside
    mine = seconds(h, names, only)
    j = scoped_ops.joined(h) if mine > 0 else None
    pairs = busy_inside(h.reduced, "engine.step") if j else []
    if not pairs or j.total_s <= 0:
        return None
    step_ms = 1e3 * sum(b for _, b in pairs) / len(pairs)
    return step_ms * mine / j.total_s
