"""(the weights a step reads — the new tokens' embedding rows,
everything else once — + every named slot's state in and out EVERY
layer + every layer's live cache tokens) / peak HBM bandwidth, over the
device-busy time of the same traced steps
(``lib/costs_falcon.serve_step_bytes``): ``serve_step_hbm_roofline``
where every layer holds a fixed-size state AND pages.  A launch with a
chunk is bound by its matmuls, not by these bytes, and reads low."""

from benchmarks.lib import costs_falcon as costs, falcon_spans as fs
from benchmarks.lib.harness import say
from benchmarks.lib.trace import busy_inside


def read(h):
    steps = fs.traced_steps(h)
    pairs = fs.traced_pairs(h) if steps else []
    if not pairs:
        return None
    spans = busy_inside(h.reduced, "engine.step")
    if len(spans) != len(steps):
        say(f"serve_step_hbm_roofline.h1: {len(steps)} traced steps but "
            f"{len(spans)} spans in the trace; not reported")
        return None
    cfg, wb = h.counters["cfg"], h.counters["weight_bytes"]
    byts = 0.0
    for s, r in pairs:
        seqs = [(n, ln) for n, ln in s["seqs"] if n > 0]
        byts += costs.serve_step_bytes(
            cfg, wb, sum(n for n, _ in seqs), r["ssm_slots_live"],
            r["ssm_state_resets"], sum(ln for _, ln in seqs))
    busy = sum(b for _, b in spans)
    if not byts or not busy:
        return None
    say(f"serving step (Falcon-H1) over {len(steps)} traced steps: bytes "
        f"{byts / h.peak.hbm_bytes_per_s:.4f}s, device busy {busy:.4f}s")
    return 100.0 * byts / h.peak.hbm_bytes_per_s / busy
