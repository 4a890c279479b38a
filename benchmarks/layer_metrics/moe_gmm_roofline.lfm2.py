"""The grouped GEMMs' share of their roofline where every expert is
held (64, top-4): what the traced launches' pairs require
(``lib/costs_lfm2.moe_gmm_cost``: every expert that receives a row read
once, a pair's row in and out, 6 x hidden x width FLOPs a pair) against
the device time of the custom calls the program runs under
``routed_ffn`` — found by the program's scope in BOTH step programs."""

from benchmarks.lib import costs_lfm2 as costs, lfm2_spans as fs
from benchmarks.lib.harness import say


def read(h):
    pairs = fs.traced_pairs(h, "moe_pairs_held", "moe_experts_hit")
    took = fs.seconds(h, ("routed_ffn",), fs.kernel) if pairs else 0.0
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
    say(f"grouped GEMMs (64 experts held, top-4): {took:.4f}s over "
        f"{len(pairs)} traced steps, least {least:.4f}s, binding bound by "
        f"step {bound}")
    return 100.0 * least / took
