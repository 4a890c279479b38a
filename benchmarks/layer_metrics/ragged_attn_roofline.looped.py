"""The ragged paged-attention kernel's share of its roofline where the
layer list runs several times a token: the bytes and FLOPs the traced
steps' real lengths require of ONE application (``lib/costs.
ragged_attention_cost``, pages of the engine's size) times the (pass,
layer) applications of a step, against the device time of the
instructions the program runs under its ``attention`` name
(``lib/scoped_ops``: by the program's own table, not by operand
names)."""

from benchmarks.lib import costs_ouro as costs, ouro_spans, scoped_ops
from benchmarks.lib.harness import say


def read(h):
    steps = ouro_spans.traced_steps(h)
    j = scoped_ops.joined(h) if steps else None
    kernel_s = j.by_scope.get("attention", 0.0) if j else 0.0
    if kernel_s <= 0:
        return None
    cfg, page = h.counters["cfg"], h.counters["page_size"]
    least, bound = 0.0, {}
    for s in steps:
        t, which = costs.roofline_seconds(
            *costs.ragged_attention_cost(cfg, s["seqs"], page), h.peak)
        least += t * costs.slots(cfg)
        bound[which] = bound.get(which, 0) + 1
    say(f"ragged attention (looped decoder, {costs.slots(cfg)} "
        f"applications a step): under `attention` {kernel_s:.4f}s over "
        f"{len(steps)} steps, least {least:.4f}s, binding bound by step "
        f"{bound}")
    return 100.0 * least / kernel_s
