"""Device time of the chunk pooling a step (``eva_pool``: the pooling
kernel over the chunks a launch closes and the two row appends that
write their pooled K and V rows; every layer), mean over the traced
steps."""

from benchmarks.lib import evabyte_spans as ev
from benchmarks.lib.harness import say


def read(h):
    steps, pool_s = ev.traced_kernel(h, ev.POOL)
    if not steps or pool_s <= 0:
        return None
    say(f"chunk pooling: kernels {pool_s:.4f}s over {len(steps)} traced "
        f"steps")
    return 1e3 * pool_s / len(steps)
