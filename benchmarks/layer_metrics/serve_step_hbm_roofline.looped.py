"""(the layers' weights once a PASS + the head once + the new tokens'
embedding rows + every live token's rows in all (pass, layer) slots
once) / peak HBM bandwidth, over the device-busy time of the same
traced steps (``lib/costs_ouro.serve_step_bytes``):
``serve_step_hbm_roofline`` where the layer list runs several times a
token.  The line says the FLOP bound beside it, of the rows the
sequences own and of the rows the step computes (its whole flat
buffer, whatever is live)."""

from benchmarks.lib import costs_ouro as costs, ouro_spans
from benchmarks.lib.harness import say
from benchmarks.lib.trace import busy_inside


def read(h):
    steps = ouro_spans.traced_steps(h)
    if not steps:
        return None
    pairs = busy_inside(h.reduced, "engine.step")
    if len(pairs) != len(steps):
        say(f"serve_step_hbm_roofline.looped: {len(steps)} traced steps "
            f"but {len(pairs)} spans in the trace; not reported")
        return None
    cfg, page = h.counters["cfg"], h.counters["page_size"]
    eng = h.counters["system"].engine
    T, S = eng.max_slots + eng.prefill_chunk, eng.max_slots + 1
    byts = sum(costs.serve_step_bytes(cfg, s["seqs"]) for s in steps)
    owned = sum(costs.serve_step_flops(
        cfg, sum(n for n, _ in s["seqs"]), len(s["seqs"]), s["seqs"], page)
        for s in steps)
    computed = sum(costs.serve_step_flops(cfg, T, S, s["seqs"], page)
                   for s in steps)
    busy = sum(b for _, b in pairs)
    say(f"serving step (looped decoder) over {len(steps)} traced steps: "
        f"bytes {byts / h.peak.hbm_bytes_per_s:.4f}s, flops of the owned "
        f"rows {owned / h.peak.bf16_flops:.4f}s, of the {T} rows a step "
        f"computes {computed / h.peak.bf16_flops:.4f}s, device busy "
        f"{busy:.4f}s")
    return 100.0 * byts / h.peak.hbm_bytes_per_s / busy if busy else None
