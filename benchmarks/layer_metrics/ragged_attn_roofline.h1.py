"""The ragged paged-attention kernel's share of its roofline at 5 query
heads a KV head, nine layers: what the traced steps' real lengths
require (``lib/costs_falcon.attention_cost``: each live cache token's K
and V once a sequence, QK^T and PV over the causal part at 20 heads of
128) against the device time of the custom calls the program runs under
``attention``."""

from benchmarks.lib import costs_falcon as costs, falcon_spans as fs
from benchmarks.lib.harness import say


def read(h):
    steps = fs.traced_steps(h)
    took = fs.seconds(h, ("attention",), fs.kernel) if steps else 0.0
    if took <= 0:
        return None
    cfg = h.counters["cfg"]
    layers = cfg["num_hidden_layers"]
    least, bound = 0.0, {}
    for s in steps:
        t, which = costs.roofline_seconds(
            *costs.attention_cost(cfg, s["seqs"]), h.peak)
        least += t * layers
        bound[which] = bound.get(which, 0) + 1
    say(f"ragged attention, group 5 ({layers} layers): kernel {took:.4f}s "
        f"over {len(steps)} steps, least {least:.4f}s, binding bound by "
        f"step {bound}")
    return 100.0 * least / took
