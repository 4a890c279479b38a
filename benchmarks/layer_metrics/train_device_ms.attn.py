"""Device time a training step of the operations the program runs under
its ``attn`` names (``lib/scoped_ops.TRAIN_PARTS``): forward, recomputed
forward and backward together, mean over the chips and the traced
steps."""

from benchmarks.lib import scoped_ops


def read(h):
    return scoped_ops.train_ms(h, "attn")
