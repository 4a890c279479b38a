"""Device ms a step of the residual's mixing — everything the program
runs under ``mhc_pre``, ``mhc_post`` and ``mhc_merge``, all layers —
mean over the traced steps (``lib/xing_spans``).  Its line also prices
what the traced launches' mixing moved and computed
(``lib/costs_xing.mhc_sublayer_cost`` at the step records' ``mhc_rows``
x ``mhc_sublayers``) a second of that time, beside the main memory's
peak: text, not a share of a roofline — the stream of one launch stays
in on-chip memory, for whose bandwidth ``lib/peaks.py`` has no
published number."""

from benchmarks.lib import costs_xing as costs, xing_spans as xs
from benchmarks.lib.harness import say


def read(h):
    ms = xs.ms_a_step(h, xs.MIXING)
    if ms is None:
        return None
    parts = {n: xs.ms_a_step(h, (n,)) or 0.0 for n in xs.MIXING}
    say("hyper-connections, device ms a step: "
        + ", ".join(f"{k} {v:.3f}" for k, v in parts.items()))
    pairs, took = xs.traced_pairs(h), xs.mixing_seconds(h)
    if pairs and took > 0:
        cfg = h.counters["cfg"]
        flops = stream = rest = 0.0
        for _, r in pairs:
            f, s, o = costs.mhc_sublayer_cost(cfg, int(r["mhc_rows"]))
            flops += f * r["mhc_sublayers"]
            stream += s * r["mhc_sublayers"]
            rest += o * r["mhc_sublayers"]
        say(f"hyper-connections over {len(pairs)} traced steps, "
            f"{took:.4f}s under the three scopes: the stream's traffic "
            f"{stream / took / 1e9:.0f} GB a second of that time (the main "
            f"memory's peak is {h.peak.hbm_bytes_per_s / 1e9:.0f}: the "
            f"stream is not there), the other operands "
            f"{rest / took / 1e9:.0f} GB/s, {flops / took / 1e12:.2f} "
            f"TFLOP/s")
    return ms
