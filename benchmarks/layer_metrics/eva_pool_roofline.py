"""The chunk pooling's share of its roofline: what the chunks that the
traced launches closed REQUIRE (``lib/costs_evabyte.eva_pool_cost``: a
chunk's 16 rows read and one written, a layer; ``summaries_written`` of
the traced steps' records) against the device time of the ``eva_pool``
kernels.  The appends move whole pages for one row and every launch
runs all its pooling slots, closed chunk or not: that is the kernels'
cost, not their need."""

from benchmarks.lib import costs_evabyte as costs, evabyte_spans as ev
from benchmarks.lib.harness import say


def read(h):
    steps, pool_s = ev.traced_kernel(h, ev.POOL)
    chunks = ev.traced_counts(h, "summaries_written")
    if not steps or pool_s <= 0 or not chunks:
        return None
    cfg = h.counters["cfg"]
    t, which = costs.roofline_seconds(
        *costs.eva_pool_cost(cfg, int(sum(chunks))), h.peak)
    least = t * cfg["num_hidden_layers"]
    say(f"chunk pooling: kernels {pool_s:.4f}s, {int(sum(chunks))} chunks "
        f"closed over {len(chunks)} traced steps, least {least:.6f}s "
        f"({which})")
    return 100.0 * least / pool_s
