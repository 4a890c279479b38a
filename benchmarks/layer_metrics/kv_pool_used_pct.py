"""Mean share of the KV page pool in use after a step, over the
window's steps (``pool_pages_used`` / ``pool_pages_total``)."""

from benchmarks.lib.harness import say
from benchmarks.lib.program_spans import in_window, mean, window


def read(h):
    w = window(h)
    if w is None:
        return None
    shares = [100.0 * r["pool_pages_used"] / r["pool_pages_total"]
              for _, r in in_window(w) if r["pool_pages_total"]]
    if not shares:
        return None
    say(f"KV pool used: mean {mean(shares):.2f} %, max {max(shares):.2f} % "
        f"over {len(shares)} steps")
    return mean(shares)
