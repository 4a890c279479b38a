"""The grouped GEMMs' share of their roofline: what the traced steps'
held pairs require (``lib/costs_laguna.moe_gmm_cost``: each held expert
that receives a row read once, a pair's row in and out, 6 x hidden x
width FLOPs a held pair) against the grouped GEMMs' device time."""

from benchmarks.lib import costs_laguna as costs, laguna_spans as ls
from benchmarks.lib.harness import say
from benchmarks.lib.program_spans import in_window, window


def read(h):
    w = window(h)
    if h.reduced is None or w is None \
            or "experts_held" not in h.counters.get("cfg", {}):
        return None
    recs = [r for s, r in in_window(w)
            if s["traced"] and "moe_experts_hit" in r]
    if not recs:
        return None
    gmm_s = ls.seconds_of(h.reduced, ls.moe_patterns(h)[1])
    if gmm_s <= 0:
        return None
    cfg = h.counters["cfg"]
    least, bound = 0.0, {}
    for r in recs:
        # the step's counts are sums over the sparse layers, and the
        # cost is linear in both: one call covers the layers
        t, which = costs.roofline_seconds(*costs.moe_gmm_cost(
            cfg, r["moe_pairs_held"], r["moe_experts_hit"]), h.peak)
        least += t
        bound[which] = bound.get(which, 0) + 1
    say(f"grouped GEMMs: {gmm_s:.4f}s over {len(recs)} traced steps, "
        f"least {least:.4f}s, binding bound by step {bound}")
    return 100.0 * least / gmm_s
