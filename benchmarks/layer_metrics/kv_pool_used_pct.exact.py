"""Mean share of the ONE KV page pool held by the sequences' window
lists (the exact rows of each current window, released together at its
close) after a step, over the window's steps (``pool_pages_used.exact``
/ ``pool_pages_total.exact``)."""

from benchmarks.lib.laguna_spans import pool_used_pct


def read(h):
    return pool_used_pct(h, "exact")
