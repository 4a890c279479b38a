"""The grouped GEMMs' share of their roofline where every expert is
held: what the traced launches' pairs require
(``lib/costs_sdar.moe_gmm_cost``: every expert that receives a row read
once, a pair's row in and out, 6 x hidden x width FLOPs a pair) against
the device time of the custom calls the program runs under
``routed_ffn`` — found by the program's scope in BOTH step programs,
not by a kernel's name or a baked row count (ROADMAP R0 c)."""

from benchmarks.lib import costs_sdar as costs, sdar_spans as ds
from benchmarks.lib.harness import say


def read(h):
    pairs = ds.traced_pairs(h, "moe_pairs_held", "moe_experts_hit")
    took = ds.seconds(h, ("routed_ffn",), ds.kernel) if pairs else 0.0
    if took <= 0:
        return None
    cfg = h.counters["cfg"]
    least, bound = 0.0, {}
    for _, r in pairs:
        # the step's counts are sums over the layers, and the cost is
        # linear in both: one call covers them
        t, which = costs.roofline_seconds(*costs.moe_gmm_cost(
            cfg, r["moe_pairs_held"], r["moe_experts_hit"]), h.peak)
        least += t
        bound[which] = bound.get(which, 0) + 1
    say(f"grouped GEMMs (all experts held): {took:.4f}s over {len(pairs)} "
        f"traced steps, least {least:.4f}s, binding bound by step {bound}")
    return 100.0 * least / took
