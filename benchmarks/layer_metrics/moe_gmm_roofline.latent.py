"""The grouped GEMMs' share of their roofline where the experts work in
a latent and are two matrices each: what the traced steps' held pairs
require (``lib/costs_nemotron.moe_gmm_cost``: 4 x latent x width FLOPs
a held pair, each held expert that receives a row read once, a pair's
latent row in and out) against the ``ragged-dot`` device time."""

from benchmarks.lib import costs_nemotron as costs, laguna_spans as ls
from benchmarks.lib import nemotron_spans as ns
from benchmarks.lib.harness import say
from benchmarks.lib.program_spans import in_window, window


def read(h):
    w = window(h) if ns.traced_steps(h) else None
    if w is None or "moe_latent_size" not in h.counters.get("cfg", {}):
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
        t, which = costs.roofline_seconds(*costs.moe_gmm_cost(
            cfg, r["moe_pairs_held"], r["moe_experts_hit"]), h.peak)
        least += t
        bound[which] = bound.get(which, 0) + 1
    say(f"grouped GEMMs in the latent: {gmm_s:.4f}s over {len(recs)} "
        f"traced steps, least {least:.4f}s, binding bound by step {bound}")
    return 100.0 * least / gmm_s
