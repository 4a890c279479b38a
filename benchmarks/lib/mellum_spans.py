"""What the Mellum training cell's per-layer readers share: the routing
counts the trainer's step hands back beside its loss
(``paddle_tpu.trainer.pretrain.MOE_METRICS``, kept a step by the system)
and the device seconds of the operations the step program runs under ITS
OWN names (``OpScope.own`` of ``lib/scoped_ops``'s table):
``window_attention`` / ``attention`` (a flash launch under a band / a
full one), ``routed_ffn`` (the grouped GEMMs), ``moe_route`` /
``moe_dispatch`` / ``moe_combine`` (the choice, the sort and gather, the
unsort and weighted sum).

A program without such counts or names (the parent of the PR that
brought them, a dense family, a run without a trace) gives nothing, and
the metric is left out of the line.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

from . import scoped_ops
from .trace import busy_inside

PERMUTE = ("moe_route", "moe_dispatch", "moe_combine")


def routing(h) -> List[Dict[str, float]]:
    """The routing counts of the window's steps, oldest first; [] where
    the system keeps none."""
    rows = getattr(h.counters.get("system"), "routing", None) or []
    n = int(h.counters.get("steps_in_window") or 0)
    return list(rows[-n:]) if n else []


def mean_of(h, key: str) -> Optional[float]:
    rows = [r[key] for r in routing(h) if key in r]
    return sum(rows) / len(rows) if rows else None


def traced_steps(h) -> int:
    return len(busy_inside(h.reduced, "train_step")) \
        if h.reduced is not None else 0


def kernel(rec) -> bool:
    return rec.opcode == "custom-call"


def not_remat(rec) -> bool:
    return rec.direction != "remat"


def _rows(h):
    """The traced events the program's table names (no loop's: its body
    counts); none where the step hands out no routing counts or nothing
    was traced."""
    j = scoped_ops.joined(h) if routing(h) and traced_steps(h) else None
    return [] if j is None else [
        r for r in j.rows if r.rec is not None and r.rec.kind != "control"]


def seconds(h, names, only: Optional[Callable] = None) -> float:
    """Traced device seconds (mean over the chips) of the instructions
    whose innermost name as the program wrote it is one of ``names``
    (and ``only(OpScope)`` holds); 0 where there is nothing to read."""
    return sum(r.seconds for r in _rows(h)
               if getattr(r.rec, "own", "") in names
               and (only is None or only(r.rec)))


def ragged_dots_elsewhere(h, own: str) -> float:
    """Traced device seconds of XLA's grouped GEMM (the custom call
    ``ragged-dot-none``) whose innermost name is NOT ``own``."""
    return sum(r.seconds for r in _rows(h)
               if kernel(r.rec) and getattr(r.rec, "own", "") != own
               and "ragged-dot" in r.name)
