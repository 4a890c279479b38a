"""Operations and bytes that a Laguna serving step REQUIRES, from shapes.

Lower bounds, as in ``costs.py``: what the algorithm has to compute and
move, not what an implementation happens to.  A roofline share built on
them cannot honestly pass 100 %.  ``c`` is the system's ``cfg``: the
published keys as run, ``num_experts`` the router's width,
``experts_held`` = (first, count) the share held here, ``vocab_size``
the rows held.  Checked by hand in ``tests/test_laguna_costs.py``.
"""

from __future__ import annotations

from typing import Iterable, Mapping, Optional, Tuple

SLIDING = "sliding_attention"


def _layers(c: Mapping):
    """(index, query heads, window or None, dense?) of each layer run."""
    for i in range(c["num_hidden_layers"]):
        yield (i, c["num_attention_heads_per_layer"][i],
               c["sliding_window"] if c["layer_types"][i] == SLIDING
               else None, i in c["mlp_only_layers"])


def attention_params(c: Mapping, nq: int) -> int:
    """Wq and Wo, Wk and Wv, the head gate."""
    h, d, nkv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    return 2 * h * nq * d + 2 * h * nkv * d + h * nq


def expert_params(c: Mapping) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_params(c: Mapping, nq: int, dense: bool) -> int:
    """One layer as HELD: attention, two norms, and the dense FFN or
    the router (all outputs), the held experts and the shared one."""
    h = c["hidden_size"]
    n = attention_params(c, nq) + 2 * h
    if dense:
        return n + 3 * h * c["intermediate_size"]
    return (n + h * c["num_experts"]
            + c["experts_held"][1] * expert_params(c)
            + 3 * h * c["shared_expert_intermediate_size"])


def n_params(c: Mapping) -> int:
    """Parameters this chip holds: its layers, the embedding and the
    head over the vocabulary held, the last norm."""
    return (sum(layer_params(c, nq, dense) for _, nq, _, dense in _layers(c))
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def kv_bytes_per_token_layer(c: Mapping, dtype_bytes: int = 2) -> int:
    return 2 * c["num_key_value_heads"] * c["head_dim"] * dtype_bytes


def attended_pairs(n_query: int, kv_len: int,
                   window: Optional[int] = None) -> int:
    """(query, key) pairs where the ``n_query`` new tokens are the last
    of ``kv_len``; a windowed query at position p sees min(p + 1,
    window) keys."""
    if window is None:
        return n_query * kv_len - n_query * (n_query - 1) // 2
    return sum(min(p + 1, window)
               for p in range(kv_len - n_query, kv_len))


def live_tokens(n_query: int, kv_len: int,
                window: Optional[int] = None) -> int:
    """Cache tokens a layer has to read for one sequence: all of them,
    or from the oldest key its oldest new query sees."""
    if window is None:
        return kv_len
    return kv_len - max(kv_len - n_query - window + 1, 0)


def ragged_attention_cost(c: Mapping, seqs: Iterable[Tuple[int, int]],
                          nq: int, window: Optional[int],
                          dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's ragged paged attention over
    ``seqs`` = (new tokens, kv length after them): every visible cache
    token's K and V read once (token-granular: a lower bound of the
    page-granular read), q read and the output written once, two
    matmuls over the visible pairs."""
    nkv, d = c["num_key_value_heads"], c["head_dim"]
    flops = byts = 0.0
    for n, kv_len in seqs:
        if n <= 0:
            continue
        byts += 2 * nkv * live_tokens(n, kv_len, window) * d * dtype_bytes
        byts += 2 * n * nq * d * dtype_bytes
        flops += 4.0 * nq * d * attended_pairs(n, kv_len, window)
    return flops, byts


def step_attention_cost(c: Mapping, seqs) -> Iterable[Tuple[float, float]]:
    """(FLOPs, bytes) of each layer's attention in one step."""
    seqs = list(seqs)
    return [ragged_attention_cost(c, seqs, nq, window)
            for _, nq, window, _ in _layers(c)]


def moe_gmm_cost(c: Mapping, pairs_held: float, experts_hit: float,
                 dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one routed layer's three grouped GEMMs: 6 x
    hidden x width FLOPs a held pair; each held expert that receives a
    row read once; a pair's row in and its row out."""
    h, w = c["hidden_size"], c["moe_intermediate_size"]
    flops = 6.0 * h * w * pairs_held
    byts = (experts_hit * expert_params(c) + 2 * pairs_held * h) \
        * dtype_bytes
    return flops, byts


def serve_step_bytes(weight_bytes: int, c: Mapping, seqs,
                     dtype_bytes: int = 2) -> float:
    """HBM bytes one serving step has to move: every weight held once
    and, a layer, every cache token that layer's kind has to read."""
    seqs = [(n, kv) for n, kv in seqs if n > 0]
    per = kv_bytes_per_token_layer(c, dtype_bytes)
    return weight_bytes + sum(
        per * sum(live_tokens(n, kv, window) for n, kv in seqs)
        for _, _, window, _ in _layers(c))


def roofline_seconds(flops: float, byts: float, peak) -> Tuple[float, str]:
    tf, tb = flops / peak.bf16_flops, byts / peak.hbm_bytes_per_s
    return (tf, "flops") if tf >= tb else (tb, "bytes")
