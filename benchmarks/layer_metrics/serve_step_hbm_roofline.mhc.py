"""(the weights a step reads — the experts that receive a row, the new
tokens' embedding rows, everything else once, the mixing's ``phi``
among it — + every live cache token's 576 values a layer) / peak HBM
bandwidth, over the device-busy time of the same traced steps
(``lib/costs_xing.serve_step_bytes``): ``serve_step_hbm_roofline``
where the residual is four streams wide.  The stream itself adds no
main-memory bytes: a launch's 11 MB stay on the chip."""

from benchmarks.lib import costs_xing as costs, xing_spans as xs
from benchmarks.lib.harness import say
from benchmarks.lib.trace import busy_inside


def read(h):
    steps = xs.traced_steps(h)
    pairs = xs.traced_pairs(h) if steps else []
    if not pairs:
        return None
    busy_by_step = busy_inside(h.reduced, "engine.step")
    if len(busy_by_step) != len(steps):
        say(f"serve_step_hbm_roofline.mhc: {len(steps)} traced steps but "
            f"{len(busy_by_step)} spans in the trace; not reported")
        return None
    cfg, wb = h.counters["cfg"], h.counters["weight_bytes"]
    byts = 0.0
    for s, r in pairs:
        seqs = [(n, ln) for n, ln in s["seqs"] if n > 0]
        byts += costs.serve_step_bytes(
            cfg, wb, sum(n for n, _ in seqs), sum(ln for _, ln in seqs),
            r.get("moe_experts_hit", 0.0))
    busy = sum(b for _, b in busy_by_step)
    if not byts or not busy:
        return None
    say(f"serving step (four-stream residual) over {len(steps)} traced "
        f"steps: bytes {byts / h.peak.hbm_bytes_per_s:.4f}s, device busy "
        f"{busy:.4f}s")
    return 100.0 * byts / h.peak.hbm_bytes_per_s / busy
