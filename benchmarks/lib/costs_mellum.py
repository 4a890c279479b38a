"""Operations and bytes that the Mellum TRAINING cell's work requires,
computed from shapes and from the step's own routing counts (lower
bounds, as ``costs.py``'s are): one chip's share of a 4-way
expert-parallel stage — every attention head, ``experts_held[1]`` of
the router's experts, a slice of the vocabulary.

Checked by hand at the cell's configuration in
``benchmarks/tests/test_mellum.py``.
"""

from __future__ import annotations

from typing import Mapping, Optional, Tuple

from .costs import roofline_seconds  # noqa: F401  (re-exported to the readers)

SLIDING = "sliding_attention"


def layer_params(c: Mapping) -> Tuple[int, int]:
    """(parameters of one layer outside its experts, parameters of ONE
    expert): q / k / v / o, the router over ALL its outputs and the two
    norm gains; gate, up and down of width ``moe_intermediate_size``."""
    h, d = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    outside = (h * d * (nq + 2 * nkv) + nq * d * h
               + h * c["published"]["num_experts"] + 2 * h)
    return outside, 3 * h * c["moe_intermediate_size"]


def held_params(c: Mapping) -> int:
    """Every parameter this chip holds: the layers with their held
    experts, the embedding and the untied head over the held rows of
    the vocabulary, the final norm."""
    outside, expert = layer_params(c)
    return (c["num_hidden_layers"] * (outside + c["experts_held"][1] * expert)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def visible_pairs(seq: int, window: Optional[int]) -> int:
    """(query, key) pairs of one sequence a causal mask leaves visible:
    query i sees min(i + 1, window) keys."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def window_of(c: Mapping, kind: str) -> Optional[int]:
    return c["sliding_window"] if kind == SLIDING else None


def kinds(c: Mapping):
    return c["layer_types"][:c["num_hidden_layers"]]


def train_flops_per_token(c: Mapping, seq: int,
                          pairs_held_per_token: float) -> float:
    """Model FLOPs a token of one step, forward and backward (6 a
    multiplied parameter), recomputation not counted: the projections
    and the router of every layer, ``pairs_held_per_token`` experts a
    layer — the (token, expert) pairs that met an expert HELD here, from
    the step's own counts: the other pairs are other chips' work —, the
    head over the held vocabulary (the embedding is a lookup), and
    attention over the VISIBLE pairs (12 heads head_dim FLOPs a pair:
    two matmuls forward, four backward)."""
    outside, expert = layer_params(c)
    matmul = outside - 2 * c["hidden_size"]         # the norms multiply no matrix
    L = c["num_hidden_layers"]
    weights = (L * (matmul + pairs_held_per_token * expert)
               + c["vocab_size"] * c["hidden_size"])
    pairs = sum(visible_pairs(seq, window_of(c, k)) for k in kinds(c)) / seq
    return (6.0 * weights
            + 12.0 * c["num_attention_heads"] * c["head_dim"] * pairs)


def flash_band_cost(c: Mapping, batch: int, seq: int,
                    window: Optional[int], dtype_bytes: int = 2
                    ) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's attention kernels, forward AND
    backward, over ``batch`` sequences of ``seq`` tokens: QK^T and PV
    over the VISIBLE (query, key) pairs — counted exactly, not by the
    blocks an implementation visits —, the backward recomputing the
    scores and forming dq, dk, dv (2.5 x the forward's matmul work, as
    ``costs.flash_causal_cost``).  Bytes: q, o, do, dq over the query
    heads and k, v, dk, dv over the KV heads, once each way (a kernel
    fed K / V repeated to the query heads moves more than it must)."""
    d, nq, nkv = c["head_dim"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    flops = 3.5 * 4.0 * batch * nq * d * visible_pairs(seq, window)
    q_rows = batch * nq * seq * d * dtype_bytes
    kv_rows = batch * nkv * seq * d * dtype_bytes
    byts = (2 * q_rows + 2 * kv_rows) + (4 * q_rows + 4 * kv_rows)
    return flops, byts


def moe_gmm_cost(c: Mapping, pairs_held: float,
                 dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of the grouped GEMMs of ONE step over all its
    routed layers, forward AND backward, for ``pairs_held`` (token,
    expert) pairs that met a held expert (summed over the layers): three
    passes (forward, the rows'
    gradient, the stacks' gradient) of 2 x 3 x hidden x width FLOPs a
    held pair; the remat forward is not credited.  Bytes: the held
    stacks read in the first two passes and their gradients written in
    the third; a held pair's row in and out of each of the three GEMMs a
    pass (hidden in / width out for gate and up, width in / hidden out
    for down).  Rows of absent experts cost nothing they must."""
    h, w = c["hidden_size"], c["moe_intermediate_size"]
    flops = 3 * 2.0 * pairs_held * 3 * h * w
    stacks = c["num_hidden_layers"] * c["experts_held"][1] * 3 * h * w
    row_bytes = pairs_held * (2 * (h + w) + (w + h)) * dtype_bytes
    return flops, 3 * stacks * dtype_bytes + 3 * row_bytes
