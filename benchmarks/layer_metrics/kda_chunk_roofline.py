"""The prefill chunk's delta-rule scan's share of its roofline: what the
traced launches' chunks require (``lib/costs_ling.kda_chunk_cost``: the
chunk's state once in — not where the launch starts the sequence — and
once out a block, its rows' operands; the WY form's FLOPs at sub-chunks
of 64) against the device time of what the program runs under
``kda_chunk_scan`` (the scan and the state's write back).  The scan is
plain XLA (`ops.pallas_kda.kda_chunk_scan`), not a kernel."""

from benchmarks.lib import costs_ling as costs, ling_spans as lg
from benchmarks.lib.harness import say


def read(h):
    pairs = lg.traced_pairs(h)
    own = lg.own_seconds(h) if pairs else None
    took = own.get("kda_chunk_scan", 0.0) if own else 0.0
    if took <= 0:
        return None
    cfg = h.counters["cfg"]
    blocks = costs.kinds(cfg)["K"]
    least, bound, with_chunk = 0.0, {}, 0
    for _, r in pairs:
        if not r["ssm_scan_rows"]:
            continue
        with_chunk += 1
        t, which = costs.roofline_seconds(*costs.kda_chunk_cost(
            cfg, r["ssm_scan_rows"], bool(r["ssm_state_resets"])), h.peak)
        least += t * blocks
        bound[which] = bound.get(which, 0) + 1
    say(f"KDA chunk scan ({blocks} blocks): under `kda_chunk_scan` "
        f"{took:.4f}s over {len(pairs)} traced steps ({with_chunk} with a "
        f"chunk), least {least:.4f}s, binding bound by step {bound}")
    return 100.0 * least / took
