"""Operations and bytes that an SDAR serving step REQUIRES, from shapes.

Lower bounds, as in ``costs.py``: what the algorithm has to compute and
move, not what an implementation happens to.  A roofline share built on
them cannot honestly pass 100 %.  ``c`` is the system's ``cfg``: the
published keys as run (every expert and the whole vocabulary held) and
the three generation keys.  Checked by hand in
``benchmarks/tests/test_sdar.py``.
"""

from __future__ import annotations

from typing import Mapping, Tuple


def attention_params(c: Mapping) -> int:
    """Wq and Wo, Wk and Wv, the q / k norms' gain vectors."""
    h, d = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * h * nq * d + 2 * h * nkv * d + 2 * d


def expert_params(c: Mapping) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_params(c: Mapping) -> int:
    """One layer: attention, two norms, the router, every expert."""
    h = c["hidden_size"]
    return (attention_params(c) + 2 * h + h * c["num_experts"]
            + c["num_experts"] * expert_params(c))


def n_params(c: Mapping) -> int:
    """Parameters held: the layers run, the embedding and the untied
    head over the whole vocabulary, the last norm."""
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def kv_bytes_per_token_layer(c: Mapping, dtype_bytes: int = 2) -> int:
    return 2 * c["num_key_value_heads"] * c["head_dim"] * dtype_bytes


def ragged_attention_cost(c: Mapping, kv_tokens: float, rows: float,
                          dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's ragged paged attention under the
    block rule over a launch whose sequences hold ``kv_tokens`` cache
    tokens in all (open blocks included) and own ``rows`` flat rows:
    every cache token's K and V read ONCE a sequence (a block's B rows
    share the read), q read and the output written once a row; every
    sequence brings at least B rows and a row of a block sees its
    sequence whole, so at least B x kv_tokens (query, key) pairs."""
    nq, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    byts = (2 * nkv * kv_tokens + 2 * rows * nq) * d * dtype_bytes
    flops = 4.0 * nq * d * c["block_length"] * kv_tokens
    return flops, byts


def moe_gmm_cost(c: Mapping, pairs: float, experts_hit: float,
                 dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one routed layer's three grouped GEMMs: 6 x
    hidden x width FLOPs a (row, expert) pair; every expert that
    receives a row read once; a pair's row in and its row out."""
    h, w = c["hidden_size"], c["moe_intermediate_size"]
    flops = 6.0 * h * w * pairs
    byts = (experts_hit * expert_params(c) + 2 * pairs * h) * dtype_bytes
    return flops, byts


def serve_step_bytes(weight_bytes: int, c: Mapping, kv_tokens: float,
                     dtype_bytes: int = 2) -> float:
    """HBM bytes one launch has to move: every weight held once and, a
    layer, every cache token its sequences hold once."""
    return weight_bytes + c["num_hidden_layers"] * kv_tokens \
        * kv_bytes_per_token_layer(c, dtype_bytes)


def roofline_seconds(flops: float, byts: float, peak) -> Tuple[float, str]:
    tf, tb = flops / peak.bf16_flops, byts / peak.hbm_bytes_per_s
    return (tf, "flops") if tf >= tb else (tb, "bytes")
