"""Mean share of the full layers' KV page pool in use after a step,
over the window's steps (``pool_pages_used.full`` /
``pool_pages_total.full``)."""

from benchmarks.lib.laguna_spans import pool_used_pct


def read(h):
    return pool_used_pct(h, "full")
