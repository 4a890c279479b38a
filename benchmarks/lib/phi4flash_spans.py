"""What the Phi-4-mini-flash cell's per-layer readers share: which steps
were traced (and whether the trace kept all of them), the step records'
counts of the state pool, of the two kinds of pages and of the shared
pool's readers (``tracing.STEP_COUNTS_SSM`` / ``STEP_COUNTS_BY_KIND`` /
``STEP_COUNTS_SHARED``), and the device seconds of the operations the
program runs under the mixers' OWN names (``OpScope.own`` of
``lib/scoped_ops``'s table) — the program's scopes, not result shapes
(PR 37's rule).  A launch over the pool that several blocks read is
``shared_attention``, one over a window layer's own pages ``attention``;
within ``ssm1_scan`` the KERNELS (the decode rows' update, the chunk's
selective scan, the state's put) are the custom calls.

A program without such blocks (a parent of the PR that brought them,
another family, or a run without a trace) gives nothing, and the metric
is left out of the line; so does a trace that lost some of the traced
steps' events (the note on PR 54: a reader that divides by what is left
reads over 100 %).
"""

from __future__ import annotations

from typing import Callable, List, Optional

from . import scoped_ops
from .harness import say
from .program_spans import in_window, window

SSM1 = ("ssm1_in_proj", "ssm1_conv", "ssm1_scan", "ssm1_out", "gmu")
SHARED = ("shared_attention",)


def sambay(h) -> bool:
    return h.counters.get("cfg", {}).get("model_type") == "phi4flash"


def traced_steps(h) -> List[dict]:
    """The traced steps' observations, where the system is a
    Phi-4-flash, the trace was reduced and it holds a span for every
    traced step; else none."""
    if h.reduced is None or not sambay(h):
        return []
    steps = [s for s in h.counters.get("steps", []) if s["traced"]]
    from .trace import busy_inside
    spans = busy_inside(h.reduced, "engine.step")
    if steps and len(spans) != len(steps):
        say(f"phi4flash readers: {len(steps)} traced steps but "
            f"{len(spans)} spans in the trace; nothing reported")
        return []
    return steps


def traced_pairs(h) -> List[tuple]:
    """(observation, step record) of the traced steps that carry the
    shared pool's and the state pool's counts."""
    if not traced_steps(h):
        return []
    w = window(h)
    if w is None:
        return []
    return [(s, r) for s, r in in_window(w)
            if s["traced"] and "shared_pool_readers" in r
            and "ssm_slots_live" in r]


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
