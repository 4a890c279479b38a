"""The ragged paged-attention kernel's share of its roofline at a stored
row of 64 lanes, 4 query heads a KV head, two attention layers: what the
traced steps' real lengths require (``lib/costs_lfm2.
ragged_attention_cost``: each live cache token's K and V once a
sequence, QK^T and PV over the causal part) against the device time of
the custom calls the program runs under ``attention``."""

from benchmarks.lib import costs_lfm2 as costs, lfm2_spans as fs
from benchmarks.lib.harness import say


def read(h):
    pairs = fs.traced_pairs(h)
    took = fs.seconds(h, ("attention",), fs.kernel) if pairs else 0.0
    if took <= 0:
        return None
    cfg = h.counters["cfg"]
    layers = costs.kinds(cfg)["attn"]
    least, bound = 0.0, {}
    for s, _ in pairs:
        t, which = costs.roofline_seconds(
            *costs.ragged_attention_cost(cfg, s["seqs"]), h.peak)
        least += t * layers
        bound[which] = bound.get(which, 0) + 1
    say(f"ragged attention, rows of 64 ({layers} layers): kernel "
        f"{took:.4f}s over {len(pairs)} steps, least {least:.4f}s, binding "
        f"bound by step {bound}")
    return 100.0 * least / took
