"""(every weight held once + every cache token of the launch's
sequences once a layer) / peak HBM bandwidth, over the device-busy time
of the same traced steps: ``serve_step_hbm_roofline`` where a launch's
sequences are read from the step records (`diffusion_kv_tokens`: a
denoise pass moves no request's committed length, so the window's
``seqs`` does not list it)."""

from benchmarks.lib import costs_sdar as costs, sdar_spans as ds
from benchmarks.lib.harness import say
from benchmarks.lib.trace import busy_inside


def read(h):
    pairs = ds.traced_pairs(h)
    spans = busy_inside(h.reduced, "engine.step") if pairs else []
    steps = [s for s in h.counters.get("steps", []) if s["traced"]]
    if not pairs or len(pairs) != len(steps):
        return None     # a traced step without a launch's record
    cfg, wb = h.counters["cfg"], h.counters["weight_bytes"]
    byts = sum(costs.serve_step_bytes(wb, cfg, r["diffusion_kv_tokens"])
               for _, r in pairs)
    busy = sum(b for _, b in spans)
    say(f"serving step (diffusion over blocks) over {len(pairs)} traced "
        f"steps: bytes {byts / h.peak.hbm_bytes_per_s:.4f}s, device busy "
        f"{busy:.4f}s")
    return 100.0 * byts / h.peak.hbm_bytes_per_s / busy if busy else None
