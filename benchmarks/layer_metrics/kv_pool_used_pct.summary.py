"""Mean share of the ONE KV page pool held by the sequences' summary
lists (pooled rows, kept to a sequence's end) after a step, over the
window's steps (``pool_pages_used.summary`` / ``pool_pages_total.
summary``)."""

from benchmarks.lib.laguna_spans import pool_used_pct


def read(h):
    return pool_used_pct(h, "summary")
