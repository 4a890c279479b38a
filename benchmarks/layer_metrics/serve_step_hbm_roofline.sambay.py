"""(the weights a step reads — everything once, the embedding as the
tied head too, and the new tokens' rows — + every named slot's state in
and out every Mamba-1 layer + the ONE full pool's live tokens ONCE A
READER, eight times a step + the window layers' live tokens once each)
/ peak HBM bandwidth, over the device-busy time of the same traced steps
(``lib/costs_phi4flash.serve_step_bytes``): ``serve_step_hbm_roofline``
where fourteen of thirty-two layers hold no memory and read one
layer's.  A launch with a chunk is bound by its matmuls, not by these
bytes, and reads low."""

from benchmarks.lib import costs_phi4flash as costs, phi4flash_spans as ps
from benchmarks.lib.harness import say
from benchmarks.lib.trace import busy_inside


def read(h):
    steps = ps.traced_steps(h)
    pairs = ps.traced_pairs(h) if steps else []
    if not pairs:
        return None
    cfg, wb = h.counters["cfg"], h.counters["weight_bytes"]
    win = cfg["sliding_window"]
    byts = 0.0
    for s, r in pairs:
        seqs = [(n, ln) for n, ln in s["seqs"] if n > 0]
        byts += costs.serve_step_bytes(
            cfg, wb, sum(n for n, _ in seqs), r["ssm_slots_live"],
            r["ssm_state_resets"], sum(ln for _, ln in seqs),
            sum(min(ln, win + n - 1) for n, ln in seqs))
    busy = sum(b for _, b in busy_inside(h.reduced, "engine.step"))
    if not byts or not busy:
        return None
    say(f"serving step (Phi-4-flash) over {len(steps)} traced steps: bytes "
        f"{byts / h.peak.hbm_bytes_per_s:.4f}s, device busy {busy:.4f}s")
    return 100.0 * byts / h.peak.hbm_bytes_per_s / busy
