"""The ragged paged-attention kernel's share of its roofline where
layers differ: required bytes and FLOPs of each layer at its own head
count, and with its window's bound where it has one
(``lib/costs_laguna.py``), against the kernel's device time in the
trace (all layers' calls)."""

from benchmarks.lib import costs_laguna as costs
from benchmarks.lib.harness import say
from benchmarks.lib.trace import seconds_matching

#: as ``ragged_attn_roofline``: a custom call of the jitted ``step``
#: whose operands are the ragged row tables
KERNEL = r"custom-call\(.*%kv_lengths"


def read(h):
    steps = [s for s in h.counters.get("steps", []) if s["traced"]]
    if h.reduced is None or not steps \
            or "layer_types" not in h.counters.get("cfg", {}):
        return None
    kernel_s = seconds_matching(h.reduced, KERNEL)
    if kernel_s <= 0:
        return None
    cfg = h.counters["cfg"]
    least, bound = 0.0, {}
    for s in steps:
        for flops, byts in costs.step_attention_cost(cfg, s["seqs"]):
            t, which = costs.roofline_seconds(flops, byts, h.peak)
            least += t
            bound[which] = bound.get(which, 0) + 1
    say(f"ragged attention (mixed layers): kernel {kernel_s:.4f}s over "
        f"{len(steps)} steps, least {least:.4f}s, binding bound by layer "
        f"and step {bound}")
    return 100.0 * least / kernel_s
