"""Rows of the fullest held expert (the largest over the step's layers)
over the mean rows a held expert, mean over the window's training
steps: the imbalance the grouped GEMM's tiles see."""

from benchmarks.lib import mellum_spans as ms


def read(h):
    ratios = [r["moe_expert_rows_max"] / r["moe_expert_rows_mean"]
              for r in ms.routing(h) if r.get("moe_expert_rows_mean")]
    return sum(ratios) / len(ratios) if ratios else None
