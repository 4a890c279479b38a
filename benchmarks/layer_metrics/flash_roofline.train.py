"""The flash-attention kernels' (forward and backward) share of their
roofline in the traced training steps: FLOPs and bytes of causal
attention at the step's shapes (``lib/costs.py``) against the kernels'
device time.  With remat "full" the forward kernel runs twice a step;
the second run is recomputation and is not credited."""

from benchmarks.lib import costs
from benchmarks.lib.harness import say
from benchmarks.lib.trace import busy_inside, seconds_matching

#: how the kernels show in the device trace today: the custom calls of
#: the per-shard ``shard_map`` region (ops/on_mesh.py); the train step
#: has no other custom call (the program gives its kernels no stable
#: names yet: PERF.md, Open questions)
KERNEL = r"shard_map[.\d]* = .* custom-call\("


def read(h):
    red = h.reduced
    if red is None:
        return None
    kernel_s = seconds_matching(red, KERNEL)
    steps = len(busy_inside(red, "train_step"))
    if kernel_s <= 0 or not steps:
        return None
    cfg, t = h.counters["cfg"], h.counters["trainer"]
    par = t["parallel"]
    data = par.get("sharding", 1) * par.get("dp", 1)
    # one chip's share: its sequences, its heads
    flops, byts = costs.flash_causal_cost(
        cfg, t["global_batch"] // data, t["seq_len"],
        cfg["num_attention_heads"] // par.get("mp", 1))
    least, which = costs.roofline_seconds(flops, byts, h.peak)
    least *= cfg["num_hidden_layers"] * steps
    say(f"flash attention: kernels {kernel_s:.4f}s over {steps} steps, "
        f"least {least:.4f}s, binding bound: {which}")
    return 100.0 * least / kernel_s
