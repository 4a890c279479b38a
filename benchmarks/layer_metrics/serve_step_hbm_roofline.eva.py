"""(every weight held once + the pooled and exact rows each live
sequence reads, a layer) / peak HBM bandwidth, over the device-busy
time of the same traced steps: ``serve_step_hbm_roofline`` where the
cache is chunk-summary attention's two lists."""

from benchmarks.lib import costs_evabyte as costs, evabyte_spans as ev
from benchmarks.lib.harness import say
from benchmarks.lib.trace import busy_inside


def read(h):
    steps = ev.traced_steps(h)
    if not steps:
        return None
    pairs = busy_inside(h.reduced, "engine.step")
    if len(pairs) != len(steps):
        say(f"serve_step_hbm_roofline.eva: {len(steps)} traced steps but "
            f"{len(pairs)} spans in the trace; not reported")
        return None
    cfg, wb = h.counters["cfg"], h.counters["weight_bytes"]
    byts = sum(costs.serve_step_bytes(wb, cfg, s["seqs"]) for s in steps)
    busy = sum(b for _, b in pairs)
    say(f"serving step (chunk-summary cache) over {len(steps)} traced "
        f"steps: bytes {byts / h.peak.hbm_bytes_per_s:.4f}s, device busy "
        f"{busy:.4f}s")
    return 100.0 * byts / h.peak.hbm_bytes_per_s / busy if busy else None
