"""What the EvaByte cell's per-layer readers share: which steps were
traced, how chunk-summary attention's kernels show in the device trace,
and the step records' counts of the two page lists
(``tracing.STEP_COUNTS_EVA``).

The program runs its attention kernel under ``jax.named_scope
("eva_attention")`` and the pooling (one pooling kernel and two row
appends a layer) under ``eva_pool``; a Pallas call takes its scope's
name in the trace (``%eva_attention.9 = ... custom-call(``), which is
how PR 33 found ``mla_attention``.

A program without chunk-summary layers (or a run without a trace) gives
nothing, and the metric is left out of the line.
"""

from __future__ import annotations

from typing import List, Tuple

from .trace import seconds_matching

ATTENTION = r"^%?eva_attention[.\d]* = .*custom-call\("
POOL = r"^%?eva_pool[.\d]* = .*custom-call\("


def traced_steps(h) -> List[dict]:
    """The traced steps' observations, where the system's layers are
    chunk-summary attention and the trace was reduced; else none."""
    cfg = h.counters.get("cfg", {})
    if h.reduced is None or cfg.get("attention_class") != "eva":
        return []
    return [s for s in h.counters.get("steps", []) if s["traced"]]


def traced_kernel(h, pattern: str) -> Tuple[List[dict], float]:
    steps = traced_steps(h)
    return steps, (seconds_matching(h.reduced, pattern) if steps else 0.0)


def traced_counts(h, key: str) -> List[float]:
    """One count of the step records of the traced steps."""
    from .program_spans import in_window, window
    w = window(h)
    if w is None:
        return []
    return [r[key] for s, r in in_window(w) if s["traced"] and key in r]
