"""100 x pair rows the routed FFN's dispatch visited / (token, expert)
pairs routed, over the window's training steps (the step's own
``moe_pair_rows_moved`` / ``moe_pairs_routed``): the share of the pair
rows a pass in sorted order moves.  100 where every pass walks all
``T k`` rows; ``moe_held_pair_share.train`` rounded up to whole chunks
where it stops at the last row a held expert owns.  Nothing where the
step hands out no such count (the parent of the PR that brought it)."""

from benchmarks.lib import mellum_spans as ms
from benchmarks.lib.harness import say


def read(h):
    rows = [r for r in ms.routing(h) if "moe_pair_rows_moved" in r]
    routed = sum(r.get("moe_pairs_routed", 0) for r in rows)
    if not routed:
        return None
    moved = sum(r["moe_pair_rows_moved"] for r in rows)
    say(f"pair rows over {len(rows)} training steps: {moved:.0f} moved "
        f"of {routed:.0f} routed")
    return 100.0 * moved / routed
