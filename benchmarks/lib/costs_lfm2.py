"""Operations and bytes that a serving step of the LFM2-MoE hybrid
REQUIRES, from shapes.

Lower bounds, as in ``costs.py``: what the algorithm has to compute and
move, the same whatever implements it.  ``c`` is the system's ``cfg``:
the published keys as run (``layers_held`` the published indices of the
layers held, every expert and the whole vocabulary held).  Checked by
hand in ``benchmarks/tests/test_lfm2.py``.
"""

from __future__ import annotations

from typing import Iterable, Mapping, Tuple

from .costs import attended_pairs, roofline_seconds  # noqa: F401


def head_dim(c: Mapping) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def layer_kinds(c: Mapping):
    """[(the mixer's type, whether the FFN is dense)] of the layers
    held."""
    held = c.get("layers_held") or range(c["num_hidden_layers"])
    return [(c["layer_types"][i], i < c["num_dense_layers"]) for i in held]


def kinds(c: Mapping) -> dict:
    """Blocks held by kind: conv and attention mixers, dense and routed
    FFNs."""
    k = layer_kinds(c)
    return {"conv": sum(m == "conv" for m, _ in k),
            "attn": sum(m != "conv" for m, _ in k),
            "dense": sum(d for _, d in k),
            "moe": sum(not d for _, d in k)}


def conv_params(c: Mapping) -> int:
    """W_in to three streams, W_out, the depthwise taps."""
    h = c["hidden_size"]
    return h * 3 * h + h * h + h * c["conv_L_cache"]


def attention_params(c: Mapping) -> int:
    """Wq and Wo, Wk and Wv, the q / k norms' gain vectors."""
    h, d = c["hidden_size"], head_dim(c)
    return (2 * h * c["num_attention_heads"] * d
            + 2 * h * c["num_key_value_heads"] * d + 2 * d)


def dense_ffn_params(c: Mapping) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: Mapping) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def moe_params(c: Mapping, experts: int) -> int:
    """The router over ALL experts and its bias, and ``experts``
    experts."""
    e = c["num_experts"]
    return c["hidden_size"] * e + e + experts * expert_params(c)


def n_params(c: Mapping, active: bool = False) -> int:
    """Parameters held: the layers run (two norms each), the embedding
    (which is the head) and the last norm; with ``active`` what a token
    meets: ``num_experts_per_tok`` experts a routed layer."""
    k, h = kinds(c), c["hidden_size"]
    experts = c["num_experts_per_tok"] if active else c["num_experts"]
    return (k["conv"] * conv_params(c) + k["attn"] * attention_params(c)
            + (k["conv"] + k["attn"]) * 2 * h
            + k["dense"] * dense_ffn_params(c)
            + k["moe"] * moe_params(c, experts)
            + c["vocab_size"] * h + h)


def tail_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """What a sequence holds in ONE conv block, and what a page's
    snapshot holds there: the last K - 1 rows of B * z."""
    return (c["conv_L_cache"] - 1) * c["hidden_size"] * dtype_bytes


def kv_row_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """K and V of one token in ONE attention layer."""
    return 2 * c["num_key_value_heads"] * head_dim(c) * dtype_bytes


def ragged_attention_cost(c: Mapping, seqs: Iterable[Tuple[int, int]],
                          dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE attention layer's ragged paged attention
    over ``seqs`` = (new tokens, kv length after them) a live sequence:
    every live cache token's K and V read once, q read and the output
    written once; two matmuls over the causal pairs."""
    nq, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  head_dim(c))
    flops = byts = 0.0
    for n, kv_len in seqs:
        if n <= 0:
            continue
        byts += (2 * nkv * kv_len + 2 * n * nq) * d * dtype_bytes
        flops += 4.0 * nq * d * attended_pairs(n, kv_len)
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


def serve_step_bytes(c: Mapping, weight_bytes: int, kv_tokens: float,
                     slots: int, snapshots: int, restores: int,
                     experts_hit: float, dtype_bytes: int = 2) -> float:
    """HBM bytes one launch has to move: the weights held once — of the
    experts only those that receive a row; the embedding whole, it is
    the head — every live cache token once an attention layer, every
    named slot's tail in and out a conv block, and the snapshots the
    chunk writes and reads there."""
    k = kinds(c)
    unhit = k["moe"] * c["num_experts"] - experts_hit
    return float(weight_bytes - dtype_bytes * unhit * expert_params(c)
                 + k["attn"] * kv_row_bytes(c, dtype_bytes) * kv_tokens
                 + k["conv"] * tail_bytes(c, dtype_bytes)
                 * (2 * slots + snapshots + restores))
