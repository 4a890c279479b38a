"""Mean share of the window layers' KV page pool in use after a step,
over the window's steps (``pool_pages_used.window`` /
``pool_pages_total.window``)."""

from benchmarks.lib.laguna_spans import pool_used_pct


def read(h):
    return pool_used_pct(h, "window")
