"""The state KERNELS' share of their HBM roofline in the state-minor
layout: what the traced launches require
(``lib/costs_falcon.ssm_update_cost``: each live decode slot's state
once in and once out a layer, its row's operands with a group's B / C
rows NOT expanded; and, where the launch carries a chunk, its state's
put: the new state read, the slot written) against the device time of
the custom calls the program runs under ``ssm_scan`` — the update and
the put, not the chunk's scan, which is plain XLA
(``ssm_chunk_scan_device_ms.h1``)."""

from benchmarks.lib import costs_falcon as costs, falcon_spans as fs
from benchmarks.lib.harness import say


def read(h):
    pairs = fs.traced_pairs(h)
    took = fs.seconds(h, ("ssm_scan",), fs.kernel) if pairs else 0.0
    if took <= 0:
        return None
    cfg = h.counters["cfg"]
    layers = cfg["num_hidden_layers"]
    state = costs.state_only_bytes(cfg)
    least, bound = 0.0, {}
    for _, r in pairs:
        chunk = bool(r["ssm_scan_rows"])
        flops, byts = costs.ssm_update_cost(cfg, r["ssm_slots_live"] - chunk)
        t, which = costs.roofline_seconds(flops, byts + 2 * state * chunk,
                                          h.peak)
        least += t * layers
        bound[which] = bound.get(which, 0) + 1
    say(f"state kernels, state-minor ({layers} layers): custom calls under "
        f"`ssm_scan` {took:.4f}s over {len(pairs)} traced steps, least "
        f"{least:.4f}s, binding bound by step {bound}")
    return 100.0 * least / took
