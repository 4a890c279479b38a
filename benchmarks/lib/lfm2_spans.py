"""What the LFM2 cell's per-layer readers share: which steps were traced
(and whether the trace kept all of them), the step records' counts of a
family whose state blocks hold a tail only and whose pages carry
snapshots (``tracing.STEP_COUNTS_TAIL``) and of an engine's prefix cache
(``tracing.STEP_COUNTS_PREFIX``, any family's), and the device seconds of the
operations the program runs under ITS OWN names (``OpScope.own`` of
``lib/scoped_ops``'s table, which holds BOTH step programs): ``lfm_in_proj``,
``lfm_conv``, ``lfm_out``, ``tail_snapshot``, ``routed_ffn``,
``attention``.

A program without such counts or names (a parent of the PR that brought
them, another family, or a run without a trace) gives nothing, and the
metric is left out of the line; so does a trace that lost some of the
traced steps' events.
"""

from __future__ import annotations

from typing import Callable, List, Optional

from . import scoped_ops
from .harness import say
from .program_spans import in_window, window
from .trace import busy_inside

MIXER = ("lfm_in_proj", "lfm_conv", "lfm_out")
NEED = ("tail_bytes", "tail_snapshots_written")


def records(h, *keys: str) -> List[dict]:
    """The window's step records that carry `keys`: no count of this
    family's is asked for, so a dense engine's records serve as well."""
    w = window(h)
    if w is None:
        return []
    return [r for _, r in in_window(w) if all(k in r for k in keys)]


def traced_pairs(h, *keys: str) -> List[tuple]:
    """(observation, step record) of the traced steps whose records
    carry the tail counts (and `keys`), where the trace was reduced and
    holds a span for every traced step; else none."""
    if h.reduced is None:
        return []
    w = window(h)
    if w is None:
        return []
    pairs = [(s, r) for s, r in in_window(w)
             if s["traced"] and all(k in r for k in NEED + keys)]
    steps = [s for s in h.counters.get("steps", []) if s["traced"]]
    spans = busy_inside(h.reduced, "engine.step")
    if pairs and len(spans) != len(steps):
        say(f"lfm2 readers: {len(steps)} traced steps but {len(spans)} "
            f"spans in the trace; nothing reported")
        return []
    return pairs


def kernel(rec) -> bool:
    return rec.opcode == "custom-call"


def seconds(h, names, only: Optional[Callable] = None) -> float:
    """Traced device seconds of the instructions whose innermost name
    as the program wrote it is one of ``names`` (and ``only(OpScope)``
    holds); 0 where there is nothing to read."""
    j = scoped_ops.joined(h) if traced_pairs(h) else None
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
    mine = seconds(h, names, only)
    j = scoped_ops.joined(h) if mine > 0 else None
    pairs = busy_inside(h.reduced, "engine.step") if j else []
    if not pairs or j.total_s <= 0:
        return None
    step_ms = 1e3 * sum(b for _, b in pairs) / len(pairs)
    return step_ms * mine / j.total_s
