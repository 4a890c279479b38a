"""100 x pages between each sequence's oldest visible key and its newest
/ K/V page fetches a KV head that the ragged kernel makes in ONE
sliding-window layer (``pages_live.window`` / ``pages_visited.window``),
summed over the window's steps."""

from benchmarks.lib.laguna_spans import live_page_share


def read(h):
    return live_page_share(h, "window")
