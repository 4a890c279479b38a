"""What the A.X-K1 cell's per-layer readers share: which steps were
traced, and how latent attention's operations show in the device trace.

The program names ``mla_q`` / ``mla_kv`` / ``mla_attention`` /
``mla_out`` scopes, but ``lib/trace.py::load_xplane`` keeps an event's
name only (PERF.md, Open questions), so the kernel is found as the
other cells' is, by its row-table operand, and the projections by
RESULT shapes only they have.

A program without latent attention (or a run without a trace) gives
nothing, and the metric is left out of the line.
"""

from __future__ import annotations

import re
from typing import List, Tuple

from .trace import seconds_matching

#: as ``ragged_attn_roofline``: a custom call of the jitted ``step``
#: whose operands are the ragged row tables
KERNEL = r"custom-call\(.*%kv_lengths"


def traced_steps(h) -> List[dict]:
    """The traced steps' observations, where the system holds latent
    rows and the trace was reduced; else none."""
    if h.reduced is None or "kv_lora_rank" not in h.counters.get("cfg", {}):
        return []
    return [s for s in h.counters.get("steps", []) if s["traced"]]


def traced_kernel(h) -> Tuple[List[dict], float]:
    steps = traced_steps(h)
    return steps, (seconds_matching(h.reduced, KERNEL) if steps else 0.0)


def projection_pattern(h):
    """Operations whose result is one of: c_q [T, q_lora_rank]; q [T,
    heads x (nope + rope)] or by head; kv_a [T, latent + rope]; q_eff
    [T, heads, latent], the query row [T, heads, latent + rope] and
    its padded form [T, heads, row as stored]; the padded row [T, row as stored]; the latent output through
    W_kvb^V [T, heads, v]."""
    c = h.counters["cfg"]
    eng = h.counters["system"].engine
    T = eng.max_slots * (1 + eng.spec_k) + eng.prefill_chunk
    nh, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    stored = eng._kv_geom[1]
    tails = {f"{c['q_lora_rank']}", f"{nh * (dn + dr)}", f"{nh},{dn + dr}",
             f"{nh},{dn}", f"{nh},{dr}", f"{r + dr}", f"{stored}",
             f"1,{stored}", f"{nh},{r}", f"{nh},{r + dr}", f"{nh},{stored}",
             f"{nh},{dv}"}
    shapes = "|".join(sorted(tails))
    return re.compile(rf" = \(?\w+\[(?:1,)?{T},(?:{shapes})\]")
