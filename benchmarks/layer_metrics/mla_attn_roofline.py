"""The latent-attention kernel's share of its roofline: what the traced
steps' real lengths REQUIRE (``lib/costs_axk1.mla_attention_cost``: each
visible cache token's 576 values once a layer, the queries in and the
output out, the lesser of the absorbed and the unabsorbed form's FLOPs a
sequence) against the kernel's device time in the trace.  The stored
row's padding and the absorbed form's extra FLOPs on a prefill chunk
are the kernel's cost, not its need."""

from benchmarks.lib import axk1_spans as ax, costs_axk1 as costs
from benchmarks.lib.harness import say


def read(h):
    steps, kernel_s = ax.traced_kernel(h)
    if not steps or kernel_s <= 0:
        return None
    cfg = h.counters["cfg"]
    least, bound = 0.0, {}
    for s in steps:
        t, which = costs.roofline_seconds(
            *costs.mla_attention_cost(cfg, s["seqs"]), h.peak)
        least += t * cfg["num_hidden_layers"]
        bound[which] = bound.get(which, 0) + 1
    say(f"latent attention: kernel {kernel_s:.4f}s over {len(steps)} "
        f"steps, least {least:.4f}s, binding bound by step {bound}")
    return 100.0 * least / kernel_s
