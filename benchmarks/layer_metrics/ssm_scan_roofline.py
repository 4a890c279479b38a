"""The recurrence's share of its roofline: what the traced launches'
rows require (``lib/costs_nemotron.ssm_scan_cost``: each live decode
slot's state once in and once out a block, the chunk's, the rows'
operands; the recurrence's FLOPs, the chunk's at 128-row scan chunks)
against the device time of what the program runs under ``ssm_scan``."""

from benchmarks.lib import costs_nemotron as costs, nemotron_spans as ns
from benchmarks.lib.harness import say


def read(h):
    recs = ns.traced_records(h)
    own = ns.own_seconds(h) if recs else None
    scan_s = own.get("ssm_scan", 0.0) if own else 0.0
    if scan_s <= 0:
        return None
    cfg = h.counters["cfg"]
    blocks = costs.kinds(cfg)["M"]
    least, bound = 0.0, {}
    for r in recs:
        chunk = r["ssm_scan_rows"]
        t, which = costs.roofline_seconds(*costs.ssm_scan_cost(
            cfg, r["ssm_slots_live"] - bool(chunk), chunk,
            bool(r["ssm_state_resets"])), h.peak)
        least += t * blocks
        bound[which] = bound.get(which, 0) + 1
    say(f"state-space recurrence ({blocks} blocks): under `ssm_scan` "
        f"{scan_s:.4f}s over {len(recs)} traced steps, least "
        f"{least:.4f}s, binding bound by step {bound}")
    return 100.0 * least / scan_s
