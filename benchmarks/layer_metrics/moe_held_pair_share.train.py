"""100 x (token, expert) pairs that met an expert held here / pairs
routed, over the window's training steps (the step's own
``moe_pairs_held`` / ``moe_pairs_routed``): the share of the layers'
routed work this chip does."""

from benchmarks.lib import mellum_spans as ms
from benchmarks.lib.harness import say


def read(h):
    rows = ms.routing(h)
    routed = sum(r.get("moe_pairs_routed", 0) for r in rows)
    if not routed:
        return None
    held = sum(r["moe_pairs_held"] for r in rows)
    say(f"routed pairs over {len(rows)} training steps: {held:.0f} held "
        f"of {routed:.0f} routed")
    return 100.0 * held / routed
