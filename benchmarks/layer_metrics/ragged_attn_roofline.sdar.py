"""The ragged paged-attention kernel's share of its roofline under the
BLOCK rule: what the traced launches require of one layer
(``lib/costs_sdar.ragged_attention_cost``: every cache token of the
launch's sequences read once — a block's B rows share the read — q in
and the output out, at least B x tokens pairs), every layer, against the
device time of the custom calls the program runs under ``attention``."""

from benchmarks.lib import costs_sdar as costs, sdar_spans as ds
from benchmarks.lib.harness import say


def read(h):
    pairs = ds.traced_pairs(h, "decode_rows", "prefill_rows")
    took = ds.seconds(h, ("attention",), ds.kernel) if pairs else 0.0
    if took <= 0:
        return None
    cfg = h.counters["cfg"]
    least, bound = 0.0, {}
    for _, r in pairs:
        t, which = costs.roofline_seconds(*costs.ragged_attention_cost(
            cfg, r["diffusion_kv_tokens"],
            r["decode_rows"] + r["prefill_rows"]), h.peak)
        least += t * cfg["num_hidden_layers"]
        bound[which] = bound.get(which, 0) + 1
    say(f"ragged attention (block rule): kernel {took:.4f}s over "
        f"{len(pairs)} traced steps, least {least:.4f}s, binding bound by "
        f"step {bound}")
    return 100.0 * least / took
