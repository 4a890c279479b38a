"""The accepted ``collective_exposed_pct``'s time a step, the part the
program puts under ``update`` (``lib/scoped_ops._exposed_books``: the
update's scope; else backward by the operation's own name; else forward
and recomputed forward).  The three add up to the accepted metric's."""

from benchmarks.lib import scoped_ops


def read(h):
    return scoped_ops.exposed_ms(h, "update")
