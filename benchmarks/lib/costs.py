"""Operations and bytes that the work REQUIRES, computed from shapes.

These are lower bounds: what the algorithm has to compute and move, not
what an implementation happens to.  A roofline share built on them
cannot honestly pass 100 %: if one does, the count here is too high or
the time leaves out part of the work, and it is reported as a fault.

Copied in kind from ``paddle_tpu/observability/costmodel.py``
(``_c_ragged``, ``_c_flash_sdpa``, ``decode_step_budget``,
``kv_bytes_per_token_layer``), with two changes: counts follow the
step's real lengths instead of a full page table, and causal attention
counts only the pairs it needs.  Checked by hand at the serving
configuration in ``tests/test_costs.py``.
"""

from __future__ import annotations

from typing import Iterable, Mapping, Tuple


def dense_params(c: Mapping) -> Tuple[int, int]:
    """(parameters of one decoder layer, parameters outside the layers)
    of a dense GQA decoder with a gated FFN and an untied head."""
    h, d = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    layer = (h * d * (nq + 2 * nkv) + nq * d * h
             + 3 * h * c["intermediate_size"] + 2 * h)
    outer = c["vocab_size"] * h * (1 if c.get("tie_word_embeddings")
                                   else 2) + h
    return layer, outer


def n_params(c: Mapping) -> int:
    layer, outer = dense_params(c)
    return c["num_hidden_layers"] * layer + outer


def kv_bytes_per_token(c: Mapping, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one context token holds, over all layers."""
    return (2 * c["num_key_value_heads"] * c["head_dim"] * dtype_bytes
            * c["num_hidden_layers"])


def attended_pairs(n_query: int, kv_len: int) -> int:
    """(query, key) pairs of causal attention where the ``n_query`` new
    tokens are the last of ``kv_len``."""
    return n_query * kv_len - n_query * (n_query - 1) // 2


def ragged_attention_cost(c: Mapping, seqs: Iterable[Tuple[int, int]],
                          page_size: int, dtype_bytes: int = 2
                          ) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's ragged paged attention over
    ``seqs`` = (new tokens, kv length after them) per live sequence.
    Every live K/V page is read once, q read and the output written
    once; two matmuls over the causal pairs."""
    nq, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    flops = byts = 0.0
    for n, kv_len in seqs:
        if n <= 0:
            continue
        pages = -(-kv_len // page_size)
        byts += 2 * nkv * pages * page_size * d * dtype_bytes
        byts += 2 * n * nq * d * dtype_bytes
        flops += 4.0 * nq * d * attended_pairs(n, kv_len)
    return flops, byts


def serve_step_bytes(weight_bytes: int, c: Mapping, live_kv_tokens: float,
                     dtype_bytes: int = 2) -> float:
    """HBM bytes one serving step has to move: every weight once and
    every live cache token once (``decode_step_budget`` without page
    rounding)."""
    return weight_bytes + live_kv_tokens * kv_bytes_per_token(c, dtype_bytes)


def flash_causal_cost(c: Mapping, batch: int, seq: int, heads: int,
                      dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's causal self-attention forward AND
    backward over ``batch`` sequences of ``seq`` tokens and ``heads``
    query heads (kv heads already repeated, as the trainer feeds the
    kernel).  Forward: QK^T and PV over the causal half.  Backward
    recomputes the scores and forms dq, dk, dv: 2.5x the forward's
    matmul work.  Bytes: q, k, v, o read or written once each way."""
    d = c["head_dim"]
    pairs = seq * (seq + 1) // 2
    fwd = 4.0 * batch * heads * d * pairs
    flops = fwd * 3.5
    one = batch * heads * seq * d * dtype_bytes
    byts = 4 * one + 8 * one          # fwd: q k v o; bwd: q k v o do dq dk dv
    return flops, byts


def train_flops_per_token(c: Mapping, seq: int) -> float:
    """6 N plus the attention term 12 L heads head_dim seq (dense
    convention; recomputed operations are not counted)."""
    return (6.0 * n_params(c)
            + 12.0 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * seq)


def roofline_seconds(flops: float, byts: float, peak) -> Tuple[float, str]:
    """The least time the chip could take, and which bound holds."""
    tf, tb = flops / peak.bf16_flops, byts / peak.hbm_bytes_per_s
    return (tf, "flops") if tf >= tb else (tb, "bytes")
