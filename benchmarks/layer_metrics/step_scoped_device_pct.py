"""Share of the traced device-busy time whose event the program's table
(``attribution.op_scopes``) puts under a name of its vocabulary."""

from benchmarks.lib import scoped_ops


def read(h):
    return scoped_ops.serve_scoped_pct(h)
