"""100 x (token, expert) pairs that met an expert held here / pairs
routed, over the window's steps (``moe_pairs_held`` /
``moe_pairs_routed``): the share of the layer's routed work this chip
does."""

from benchmarks.lib import laguna_spans as ls
from benchmarks.lib.harness import say


def read(h):
    rows = ls.counts(h, "moe_pairs_held", "moe_pairs_routed")
    if rows is None:
        return None
    held, routed = (sum(col) for col in zip(*rows))
    if not routed:
        return None
    say(f"routed pairs over {len(rows)} steps: {held:.0f} held of "
        f"{routed:.0f} routed")
    return 100.0 * held / routed
