"""The chunk-summary attention kernel's share of its roofline: what the
traced steps' real lengths REQUIRE (``lib/costs_evabyte.
eva_attention_cost``: each visible pooled and exact row's 16,384 B once
a layer a sequence a launch, the queries in and the output out; the
FLOPs of the rows each query sees) against the kernel's device time in
the trace."""

from benchmarks.lib import costs_evabyte as costs, evabyte_spans as ev
from benchmarks.lib.harness import say


def read(h):
    steps, kernel_s = ev.traced_kernel(h, ev.ATTENTION)
    if not steps or kernel_s <= 0:
        return None
    cfg = h.counters["cfg"]
    least, bound = 0.0, {}
    for s in steps:
        t, which = costs.roofline_seconds(
            *costs.eva_attention_cost(cfg, s["seqs"]), h.peak)
        least += t * cfg["num_hidden_layers"]
        bound[which] = bound.get(which, 0) + 1
    say(f"chunk-summary attention: kernel {kernel_s:.4f}s over "
        f"{len(steps)} steps, least {least:.4f}s, binding bound by step "
        f"{bound}")
    return 100.0 * least / kernel_s
