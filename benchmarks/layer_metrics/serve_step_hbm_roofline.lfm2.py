"""(the weights a step reads — the experts that receive a row, everything
else once — + every live cache token once an attention layer + every
named slot's tail in and out a conv block + the snapshots the chunk
writes and reads) / peak HBM bandwidth, over the device-busy time of the
same traced steps (``lib/costs_lfm2.serve_step_bytes``):
``serve_step_hbm_roofline`` where most of a sequence's memory outside
its pages is two rows a block."""

from benchmarks.lib import costs_lfm2 as costs, lfm2_spans as fs
from benchmarks.lib.harness import say
from benchmarks.lib.trace import busy_inside


def read(h):
    pairs = fs.traced_pairs(h, "ssm_slots_live", "moe_experts_hit")
    spans = busy_inside(h.reduced, "engine.step") if pairs else []
    steps = [s for s in h.counters.get("steps", []) if s["traced"]]
    if not pairs or len(pairs) != len(steps):
        return None     # a traced step without a launch's record
    cfg, wb = h.counters["cfg"], h.counters["weight_bytes"]
    byts = sum(costs.serve_step_bytes(
        cfg, wb, sum(ln for n, ln in s["seqs"] if n > 0),
        r["ssm_slots_live"], r["tail_snapshots_written"],
        r["tail_restores"], r["moe_experts_hit"]) for s, r in pairs)
    busy = sum(b for _, b in spans)
    say(f"serving step (tail-only hybrid) over {len(pairs)} traced steps: "
        f"bytes {byts / h.peak.hbm_bytes_per_s:.4f}s, device busy "
        f"{busy:.4f}s")
    return 100.0 * byts / h.peak.hbm_bytes_per_s / busy if busy else None
