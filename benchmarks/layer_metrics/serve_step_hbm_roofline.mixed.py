"""(every weight held once + every cache token each layer's kind has to
read once) / peak HBM bandwidth, over the device-busy time of the same
traced steps: ``serve_step_hbm_roofline`` where a window layer reads
only what its window spans."""

from benchmarks.lib import costs_laguna as costs
from benchmarks.lib.harness import say
from benchmarks.lib.trace import busy_inside


def read(h):
    steps = [s for s in h.counters.get("steps", []) if s["traced"]]
    if h.reduced is None or not steps \
            or "layer_types" not in h.counters.get("cfg", {}):
        return None
    pairs = busy_inside(h.reduced, "engine.step")
    if len(pairs) != len(steps):
        say(f"serve_step_hbm_roofline.mixed: {len(steps)} traced steps but "
            f"{len(pairs)} spans in the trace; not reported")
        return None
    cfg, wb = h.counters["cfg"], h.counters["weight_bytes"]
    byts = sum(costs.serve_step_bytes(wb, cfg, s["seqs"]) for s in steps)
    busy = sum(b for _, b in pairs)
    say(f"serving step (mixed layers) over {len(steps)} traced steps: "
        f"bytes {byts / h.peak.hbm_bytes_per_s:.4f}s, device busy "
        f"{busy:.4f}s")
    return 100.0 * byts / h.peak.hbm_bytes_per_s / busy if busy else None
