"""Device time of the chunk-summary attention kernel a step (the ragged
paged kernel over a table of pooled pages then window pages; every
layer's call), mean over the traced steps."""

from benchmarks.lib import evabyte_spans as ev
from benchmarks.lib.harness import say


def read(h):
    steps, kernel_s = ev.traced_kernel(h, ev.ATTENTION)
    if not steps or kernel_s <= 0:
        return None
    say(f"chunk-summary attention: kernel {kernel_s:.4f}s over "
        f"{len(steps)} traced steps")
    return 1e3 * kernel_s / len(steps)
