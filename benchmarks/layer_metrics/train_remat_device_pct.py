"""Share of the traced device time spent in the RECOMPUTED forward
(``rematted_computation`` in the operation's own name): what ``remat``
full costs a step."""

from benchmarks.lib import scoped_ops


def read(h):
    return scoped_ops.train_pct(h, "remat_pct")
