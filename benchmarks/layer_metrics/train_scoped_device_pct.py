"""Share of the traced device time of a training step whose event the
program's table (``attribution.op_scopes``) puts under a name of its
vocabulary."""

from benchmarks.lib import scoped_ops


def read(h):
    return scoped_ops.train_pct(h, "scoped_pct")
