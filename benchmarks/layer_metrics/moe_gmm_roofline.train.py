"""The grouped GEMMs' share of their roofline in the trainer's step,
forward AND backward: what the steps' HELD pairs require
(``lib/costs_mellum.moe_gmm_cost``: three passes of 2 x 3 x hidden x
width FLOPs a held pair, the held stacks' and the rows' bytes; pairs
held from the steps' own counts) against the device time of the custom
calls the step program runs directly under ``routed_ffn`` (the forward's
three grouped GEMMs, their recomputation under remat — not credited —
and the backward's against the transposed stacks) AND of the
``ragged-dot`` custom calls under any other name: the compiler gives the
stacks' gradients (``bf16[16,2304,896]``, ``[16,896,2304]``) no name of
their own, and they answer to their reader's, ``update`` (the first
traced run, PR 66: 46 of a step's 112 ms of grouped GEMMs)."""

from benchmarks.lib import costs_mellum as costs, mellum_spans as ms
from benchmarks.lib.harness import say


def read(h):
    took = ms.seconds(h, ("routed_ffn",), ms.kernel) \
        + ms.ragged_dots_elsewhere(h, "routed_ffn")
    steps = ms.traced_steps(h)
    held = ms.mean_of(h, "moe_pairs_held")
    if took <= 0 or not steps or not held:
        return None
    least, which = costs.roofline_seconds(
        *costs.moe_gmm_cost(h.counters["cfg"], held), h.peak)
    least *= steps
    say(f"grouped GEMMs (trainer, forward and backward): {took:.4f}s over "
        f"{steps} traced steps at {held:.0f} held pairs a step, least "
        f"{least:.4f}s, binding bound: {which}")
    return 100.0 * least / took
