"""What the Ouro cell's per-layer readers share: which steps were
traced, and the step records' counts of the looped decoder
(``tracing.STEP_COUNTS_LOOP``).

A program without a looped decoder (a parent of the PR that brought it,
another family, or a run without a trace) gives nothing, and the metric
is left out of the line.
"""

from __future__ import annotations

from typing import List


def looped(h) -> bool:
    return int(h.counters.get("cfg", {}).get("total_ut_steps", 0)) > 0


def traced_steps(h) -> List[dict]:
    """The traced steps' observations, where the system is a looped
    decoder and the trace was reduced; else none."""
    if h.reduced is None or not looped(h):
        return []
    return [s for s in h.counters.get("steps", []) if s["traced"]]
