"""Rows of the fullest held expert over the mean rows a held expert,
mean over the window's steps (``moe_expert_rows_max``, the largest over
the step's sparse layers, / ``moe_expert_rows_mean``): the imbalance
the grouped GEMM's tiles see."""

from benchmarks.lib import laguna_spans as ls
from benchmarks.lib.harness import say
from benchmarks.lib.program_spans import mean


def read(h):
    rows = ls.counts(h, "moe_expert_rows_max", "moe_expert_rows_mean")
    if rows is None:
        return None
    ratios = [mx / mn for mx, mn in rows if mn]
    if not ratios:
        return None
    say(f"expert rows over {len(ratios)} steps: max {mean([r[0] for r in rows]):.1f}, "
        f"mean {mean([r[1] for r in rows]):.2f} a held expert")
    return mean(ratios)
