"""100 x pages that hold the launches' tokens / K/V page fetches a KV
head that the ragged kernel makes in ONE full-attention layer
(``pages_live.full`` / ``pages_visited.full``), summed over the
window's steps."""

from benchmarks.lib.laguna_spans import live_page_share


def read(h):
    return live_page_share(h, "full")
