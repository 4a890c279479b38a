"""Device time of the latent-attention kernel a step (the ragged paged
kernel over pages that hold K and V in one row; every layer's call),
mean over the traced steps."""

from benchmarks.lib import axk1_spans as ax
from benchmarks.lib.harness import say


def read(h):
    steps, kernel_s = ax.traced_kernel(h)
    if not steps or kernel_s <= 0:
        return None
    say(f"latent attention: kernel {kernel_s:.4f}s over {len(steps)} "
        f"traced steps")
    return 1e3 * kernel_s / len(steps)
