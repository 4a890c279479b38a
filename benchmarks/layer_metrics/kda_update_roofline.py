"""The decode rows' delta-rule update's share of its roofline: what the
traced launches' live slots require (``lib/costs_ling.kda_update_cost``:
each live decode slot's state once in and once out a block, its row's
operands; 7 FLOPs an element of the state) against the device time of
what the program runs under ``kda_state_update``."""

from benchmarks.lib import costs_ling as costs, ling_spans as lg
from benchmarks.lib.harness import say


def read(h):
    pairs = lg.traced_pairs(h)
    own = lg.own_seconds(h) if pairs else None
    took = own.get("kda_state_update", 0.0) if own else 0.0
    if took <= 0:
        return None
    cfg = h.counters["cfg"]
    blocks = costs.kinds(cfg)["K"]
    least, bound = 0.0, {}
    for _, r in pairs:
        t, which = costs.roofline_seconds(*costs.kda_update_cost(
            cfg, r["ssm_slots_live"] - bool(r["ssm_scan_rows"])), h.peak)
        least += t * blocks
        bound[which] = bound.get(which, 0) + 1
    say(f"KDA update ({blocks} blocks): under `kda_state_update` "
        f"{took:.4f}s over {len(pairs)} traced steps, least {least:.4f}s, "
        f"binding bound by step {bound}")
    return 100.0 * least / took
