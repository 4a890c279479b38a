"""The ragged kernel's share of its HBM roofline over the SHARED pool in
the pair layout (40 query heads over 10 KV pairs of 128, group 4, no
rotary): the pages the traced launches visit in the full pool
(``pages_visited.full``, one launch) x the page's bytes, ONCE A READER
(``shared_pool_readers``: every launch fetches them), and the FLOPs of
QK^T over 64 and PV over 128 for 40 heads
(``lib/costs_phi4flash.attention_cost``), against the device time of the
custom calls the program runs under ``shared_attention``."""

from benchmarks.lib import costs_phi4flash as costs, phi4flash_spans as ps
from benchmarks.lib.harness import say


def read(h):
    pairs = ps.traced_pairs(h)
    took = ps.seconds(h, ps.SHARED, ps.kernel) if pairs else 0.0
    if took <= 0:
        return None
    cfg = h.counters["cfg"]
    page = costs.page_bytes(cfg, h.counters["page_size"])
    least, bound = 0.0, {}
    for s, r in pairs:
        flops, _ = costs.attention_cost(cfg, s["seqs"])
        t, which = costs.roofline_seconds(
            flops, float(r["pages_visited.full"] * page), h.peak)
        least += t * r["shared_pool_readers"]
        bound[which] = bound.get(which, 0) + 1
    say(f"ragged attention over the shared pool (group 4, D 128): kernel "
        f"{took:.4f}s over {len(pairs)} steps, least {least:.4f}s, binding "
        f"bound by step {bound}")
    return 100.0 * least / took
