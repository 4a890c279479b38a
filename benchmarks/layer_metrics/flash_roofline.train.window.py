"""The flash kernels' (forward, dq, dk / dv) share of their roofline on
the trainer's SLIDING-window layers (a band of the block-pair table): FLOPs and bytes
of the VISIBLE (query, key) pairs at the step's shapes
(``lib/costs_mellum.flash_band_cost``: exact pairs, not blocks visited)
against the device time of the custom calls the step program runs under
``window_attention``.  Under remat "full" a layer whose checkpoint does not
keep the kernel's residuals runs the forward twice; the second run is
recomputation and is not credited."""

from benchmarks.lib import costs_mellum as costs, mellum_spans as ms
from benchmarks.lib.harness import say


def read(h):
    took = ms.seconds(h, ("window_attention",), ms.kernel)
    steps = ms.traced_steps(h)
    if took <= 0 or not steps:
        return None
    cfg, t = h.counters["cfg"], h.counters["trainer"]
    layers = [k for k in costs.kinds(cfg) if k == costs.SLIDING]
    flops, byts = costs.flash_band_cost(
        cfg, t["global_batch"], t["seq_len"],
        costs.window_of(cfg, layers[0]) if layers else None)
    least, which = costs.roofline_seconds(flops, byts, h.peak)
    least *= len(layers) * steps
    say(f"flash kernels under window_attention: {took:.4f}s over {steps} steps "
        f"({len(layers)} layers), least {least:.4f}s, binding bound: "
        f"{which}")
    return 100.0 * least / took
