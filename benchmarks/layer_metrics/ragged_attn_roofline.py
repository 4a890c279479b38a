"""The ragged paged-attention kernel's share of its roofline: the bytes
and FLOPs the traced steps' real lengths require (``lib/costs.py``),
against the kernel's device time in the trace."""

from benchmarks.lib import costs
from benchmarks.lib.harness import say
from benchmarks.lib.trace import seconds_matching

#: how the kernel shows in the device trace today: a custom call of the
#: jitted ``step`` whose operands are the ragged row tables (the program
#: gives its kernels no stable names yet: PERF.md, Open questions)
KERNEL = r"custom-call\(.*%kv_lengths"


def read(h):
    steps = [s for s in h.counters.get("steps", []) if s["traced"]]
    if h.reduced is None or not steps:
        return None
    kernel_s = seconds_matching(h.reduced, KERNEL)
    if kernel_s <= 0:
        return None
    cfg, page = h.counters["cfg"], h.counters["page_size"]
    least, bound = 0.0, {}
    for s in steps:
        flops, byts = costs.ragged_attention_cost(cfg, s["seqs"], page)
        t, which = costs.roofline_seconds(flops, byts, h.peak)
        least += t * cfg["num_hidden_layers"]
        bound[which] = bound.get(which, 0) + 1
    say(f"ragged attention: kernel {kernel_s:.4f}s over {len(steps)} "
        f"steps, least {least:.4f}s, binding bound by step {bound}")
    return 100.0 * least / kernel_s
