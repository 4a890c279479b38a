"""(every weight once + every live cache token once) / peak HBM
bandwidth, over the device-busy time of the same traced steps.  Says how
far the serving step is from the bound that holds in decode; where a
step carries a full prefill chunk the FLOP bound is the higher one (the
run prints both)."""

from benchmarks.lib import costs
from benchmarks.lib.harness import say
from benchmarks.lib.trace import busy_inside


def read(h):
    steps = [s for s in h.counters.get("steps", []) if s["traced"]]
    if h.reduced is None or not steps:
        return None
    pairs = busy_inside(h.reduced, "engine.step")
    if len(pairs) != len(steps):
        say(f"serve_step_hbm_roofline: {len(steps)} traced steps but "
            f"{len(pairs)} spans in the trace; not reported")
        return None
    cfg, wb = h.counters["cfg"], h.counters["weight_bytes"]
    byts = sum(costs.serve_step_bytes(
        wb, cfg, sum(kv for _, kv in s["seqs"])) for s in steps)
    rows = sum(sum(n for n, _ in s["seqs"]) for s in steps)
    flops = 2.0 * costs.n_params(cfg) * rows
    busy = sum(b for _, b in pairs)
    say(f"serving step bounds over {len(steps)} traced steps: bytes "
        f"{byts / h.peak.hbm_bytes_per_s:.4f}s, matmul flops "
        f"{flops / h.peak.bf16_flops:.4f}s, device busy {busy:.4f}s")
    return 100.0 * byts / h.peak.hbm_bytes_per_s / busy if busy else None
