"""Device ms a launch THAT CARRIES A CHUNK of the chunk's scan, all
nine layers: what the program runs under ``ssm_scan`` that is not a
kernel (`ssm_chunk_scan` is plain XLA: batched matmuls over group and
head, in scan chunks of 128 rows; the rows' operands for the kernels
ride along), over the traced launches whose step record has
``ssm_scan_rows`` > 0.  ROADMAP S14 d prices a kernel for it; its line
says the FLOPs those chunks require and the share of the MXU's peak
they ran at."""

from benchmarks.lib import costs_falcon as costs, falcon_spans as fs
from benchmarks.lib.harness import say


def read(h):
    pairs = fs.traced_pairs(h)
    with_chunk = [r for _, r in pairs if r["ssm_scan_rows"]]
    per_step = fs.ms_a_step(h, ("ssm_scan",), lambda rec: not fs.kernel(rec))
    if per_step is None or not with_chunk:
        return None
    cfg = h.counters["cfg"]
    ms = per_step * len(pairs) / len(with_chunk)
    flops = sum(costs.ssm_chunk_scan_cost(
        cfg, r["ssm_scan_rows"], bool(r["ssm_state_resets"]))[0]
        for r in with_chunk) * cfg["num_hidden_layers"] / len(with_chunk)
    say(f"chunk scan (plain XLA, {cfg['num_hidden_layers']} layers): "
        f"{ms:.3f} ms a launch with a chunk ({len(with_chunk)} of "
        f"{len(pairs)} traced launches), {flops / 1e9:.2f} GFLOP required: "
        f"{100.0 * flops / (ms * 1e-3) / h.peak.bf16_flops:.2f} % of the "
        f"bf16 peak")
    return ms
