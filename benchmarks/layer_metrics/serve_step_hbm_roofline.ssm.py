"""(the weights a step reads — the experts that receive a row, the new
tokens' embedding rows, everything else once — + every named slot's
state in and out a state-space block + the attention blocks' live cache
tokens) / peak HBM bandwidth, over the device-busy time of the same
traced steps (``lib/costs_nemotron.serve_step_bytes``):
``serve_step_hbm_roofline`` where most of a sequence's memory is a
fixed-size state."""

from benchmarks.lib import costs_nemotron as costs, nemotron_spans as ns
from benchmarks.lib.harness import say
from benchmarks.lib.program_spans import in_window, window
from benchmarks.lib.trace import busy_inside


def read(h):
    steps = ns.traced_steps(h)
    w = window(h) if steps else None
    if w is None:
        return None
    pairs = busy_inside(h.reduced, "engine.step")
    if len(pairs) != len(steps):
        say(f"serve_step_hbm_roofline.ssm: {len(steps)} traced steps but "
            f"{len(pairs)} spans in the trace; not reported")
        return None
    cfg, wb = h.counters["cfg"], h.counters["weight_bytes"]
    byts = 0.0
    for s, r in in_window(w):
        if not s["traced"] or "ssm_slots_live" not in r:
            continue
        seqs = [(n, ln) for n, ln in s["seqs"] if n > 0]
        byts += costs.serve_step_bytes(
            cfg, wb, sum(n for n, _ in seqs), r["ssm_slots_live"],
            r["ssm_state_resets"], sum(ln for _, ln in seqs),
            r.get("moe_experts_hit", 0.0))
    busy = sum(b for _, b in pairs)
    if not byts or not busy:
        return None
    say(f"serving step (state-space hybrid) over {len(steps)} traced "
        f"steps: bytes {byts / h.peak.hbm_bytes_per_s:.4f}s, device busy "
        f"{busy:.4f}s")
    return 100.0 * byts / h.peak.hbm_bytes_per_s / busy
