"""Device time a step of latent attention's low-rank and absorb matmuls
— W_qa, W_qb, W_kva, q_nope W_kvb^K (absorb), the latent output through
W_kvb^V — and what rides in their fusions (the two norms, rope, the row
and query padding), every layer, mean over the traced steps.  Found by
result shapes only these operations have (``lib/axk1_spans.py``); W_o's
result is a plain [T, hidden] and is left out."""

from benchmarks.lib import axk1_spans as ax, laguna_spans as ls
from benchmarks.lib.harness import say


def read(h):
    steps = ax.traced_steps(h)
    if not steps:
        return None
    total = ls.seconds_of(h.reduced, ax.projection_pattern(h))
    if total <= 0:
        return None
    say(f"latent projections: {total:.4f}s of device time over "
        f"{len(steps)} traced steps")
    return 1e3 * total / len(steps)
