"""The Mamba-1 recurrence's share of its HBM roofline, nine layers:
what the traced launches require under ``ssm1_scan`` — the dt / B / C
projections' weights once a layer, each live decode slot's state once in
and once out with its row's operands
(``lib/costs_phi4flash.ssm1_update_cost``) and, where the launch carries
a chunk, the slot's state in and out, the put's copy and a row's dt, x,
B, C in and y out (``ssm1_scan_cost``) — against the device time of
EVERYTHING the program runs under ``ssm1_scan``: the three kernels (the
update, the chunk's selective scan, the state's put), the projections
and softplus that make their operands, and the copies around them.  The
whole scope and not the custom calls alone, because the compiler stages
a layer's whole pool [33, 1, 16, 5120] through fast memory around the
update (``S(1)`` in the traced layouts): the kernel then reads no HBM
itself, its bytes move in asynchronous copies under its neighbours, and
the custom calls alone read 156 % of the bytes' bound on decode steps (my
chip run, PR 56).  The scan is elementwise and SEQUENTIAL in the rows (7
vector FLOPs an element of the state a row, and an exponential): no
matrix peak bounds it, so the binding bound of the two the harness knows
is the bytes'; the line says the kernels' own seconds and the vector
FLOPs a second they ran at."""

from benchmarks.lib import costs_phi4flash as costs, phi4flash_spans as ps
from benchmarks.lib.harness import say


def read(h):
    pairs = ps.traced_pairs(h)
    took = ps.seconds(h, ("ssm1_scan",)) if pairs else 0.0
    if took <= 0:
        return None
    cfg = h.counters["cfg"]
    layers = costs.layer_kinds(cfg).count("S")
    proj = costs.ssm1_operand_weight_bytes(cfg)
    least, bound, flops_all, chunks = 0.0, {}, 0.0, 0
    for _, r in pairs:
        rows = r["ssm_scan_rows"]
        chunks += bool(rows)
        uf, ub = costs.ssm1_update_cost(cfg, r["ssm_slots_live"] - bool(rows))
        sf, sb = costs.ssm1_scan_cost(cfg, rows, bool(r["ssm_state_resets"]))
        t, which = costs.roofline_seconds(uf + sf, ub + sb + proj, h.peak)
        least += t * layers
        flops_all += (uf + sf) * layers
        bound[which] = bound.get(which, 0) + 1
    kernels = ps.seconds(h, ("ssm1_scan",), ps.kernel)
    say(f"Mamba-1 recurrence ({layers} layers): everything under "
        f"`ssm1_scan` {took:.4f}s over {len(pairs)} traced steps ({chunks} "
        f"with a chunk), of which the custom calls {kernels:.4f}s "
        f"({flops_all / max(kernels, 1e-12) / 1e9:.1f} GFLOP/s of vector "
        f"work); least {least:.4f}s, binding bound by step {bound}")
    return 100.0 * least / took
