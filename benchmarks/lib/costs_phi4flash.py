"""Operations and bytes that a serving step of the Phi-4-mini-flash
decoder REQUIRES, from shapes.

Lower bounds, as in ``costs.py``: what the algorithm has to compute and
move, the same whatever implements it.  ``c`` is the system's ``cfg``
(the published keys and the four Mamba-1 constants the configuration
states as assumed).  Checked by hand in ``tests/test_phi4flash.py``.
"""

from __future__ import annotations

from typing import Mapping, Sequence, Tuple

from .costs import roofline_seconds  # noqa: F401


def d_inner(c: Mapping) -> int:
    return c.get("mamba_expand", 2) * c["hidden_size"]


def d_state(c: Mapping) -> int:
    return c.get("mamba_d_state", 16)


def d_conv(c: Mapping) -> int:
    return c.get("mamba_d_conv", 4)


def dt_rank(c: Mapping) -> int:
    r = c.get("mamba_dt_rank", "auto")
    return -(-c["hidden_size"] // 16) if r == "auto" else int(r)


def head_dim(c: Mapping) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def layer_kinds(c: Mapping) -> str:
    n = c["num_hidden_layers"]
    h = n // 2
    return "".join(
        ("S" if l <= h else "G") if l % 2 == 0 else
        ("W" if l < h else "F" if l == h + 1 else "X") for l in range(n))


def mamba_params(c: Mapping) -> int:
    """W_in, the convolution and its bias, W_x, W_dt and its bias, A_log,
    D, W_out."""
    h, ci, n, r = c["hidden_size"], d_inner(c), d_state(c), dt_rank(c)
    return (h * 2 * ci + ci * d_conv(c) + ci + ci * (r + 2 * n)
            + r * ci + ci + ci * n + ci + ci * h)


def attention_params(c: Mapping, cross: bool = False) -> int:
    """Wq (+ Wk, Wv) with bias, Wo with bias, four lambda vectors, the
    2D-wide norm's gain."""
    h, dd = c["hidden_size"], head_dim(c)
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    out = nq * dd + (0 if cross else 2 * nkv * dd)
    return h * out + out + nq * dd * h + h + 4 * dd + 2 * dd


def gmu_params(c: Mapping) -> int:
    return 2 * c["hidden_size"] * d_inner(c)


def ffn_params(c: Mapping) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_params(c: Mapping, kind: str) -> int:
    """A mixer, the FFN, two LayerNorms with bias."""
    mixer = {"S": mamba_params(c), "G": gmu_params(c),
             "X": attention_params(c, cross=True)}.get(
                 kind, attention_params(c))
    return mixer + ffn_params(c) + 4 * c["hidden_size"]


def n_params(c: Mapping) -> int:
    """Every layer, the embedding (the head is tied to it), the last
    LayerNorm."""
    return (sum(layer_params(c, k) for k in layer_kinds(c))
            + c["vocab_size"] * c["hidden_size"] + 2 * c["hidden_size"])


def state_only_bytes(c: Mapping) -> int:
    """The recurrent state a sequence holds in ONE Mamba-1 layer: d_inner
    x d_state, float32, as the pool stores it (channels along the lanes:
    no lane is padded)."""
    return d_inner(c) * d_state(c) * 4


def state_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """... and the convolution's tail with it: what a slot stores."""
    return state_only_bytes(c) + (d_conv(c) - 1) * d_inner(c) * dtype_bytes


def kv_row_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """K and V of one token in ONE attention layer that owns pages."""
    return 2 * c["num_key_value_heads"] * head_dim(c) * dtype_bytes


def page_bytes(c: Mapping, page_size: int, dtype_bytes: int = 2) -> int:
    return page_size * kv_row_bytes(c, dtype_bytes)


def ssm1_operand_weight_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """W_x [d_inner, dt_rank + 2 d_state] and W_dt [dt_rank, d_inner]
    with its bias: the projections that make ONE layer's dt, B and C."""
    ci, n, r = d_inner(c), d_state(c), dt_rank(c)
    return (ci * (r + 2 * n) + r * ci + ci) * dtype_bytes


def ssm1_update_cost(c: Mapping, slots: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's state update for ``slots`` live
    decode slots: each slot's state once in and once out; its row's dt
    and x [C] and its B and C rows [N] in float32, y [C] out; A [N, C]
    once a launch.  7 FLOPs an element of the state (dt A, the decay's
    multiply, dt x B, the add, the read-out's multiply-add) and its
    exponential."""
    ci, n = d_inner(c), d_state(c)
    row = (3 * ci + 2 * n) * 4
    return 7.0 * ci * n * slots, \
        float(slots * (2 * state_only_bytes(c) + row)
              + (ci * n * 4 if slots else 0))


def ssm1_scan_cost(c: Mapping, rows: int, starts: bool
                   ) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's selective scan over a chunk of
    ``rows`` rows of one sequence: 7 FLOPs an element of the state a row
    (as the update's), sequential in the rows; the slot's state once in
    (not where the launch starts the sequence) and twice out (the scan
    leaves it, the put writes the slot), read once by the put; a row's
    dt and x in and y out [C] and its B and C rows [N] in float32."""
    if not rows:
        return 0.0, 0.0
    ci, n = d_inner(c), d_state(c)
    row = (3 * ci + 2 * n) * 4
    return 7.0 * ci * n * rows, \
        float(state_only_bytes(c) * (4 - bool(starts)) + row * rows
              + ci * n * 4)


def attention_cost(c: Mapping, seqs: Sequence[Tuple[int, int]],
                   window=None, dtype_bytes: int = 2
                   ) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE ragged launch over ``seqs`` = (new tokens,
    context after them) a sequence in the PAIR layout (H query heads
    over KV / 2 pairs 2D wide): QK^T over D and PV over 2D for every
    query head over the causal part (a window's keys at most), each live
    cache token's K and V read once a sequence (a window layer: the
    tokens its window spans), q in and o out."""
    nq, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  head_dim(c))
    flops = byts = 0.0
    for n, ln in seqs:
        if n <= 0:
            continue
        pairs = n * ln - n * (n - 1) / 2.0      # causal (query, key) pairs
        keys = ln
        if window is not None:
            keys = min(ln, window + n - 1)
            pairs = min(pairs, float(n) * window)
        flops += (2.0 * d + 2.0 * 2 * d) * nq * pairs
        byts += (2 * nkv * d * keys + (nq * d + nq * 2 * d) * n) \
            * dtype_bytes
    return flops, byts


def serve_step_bytes(c: Mapping, weight_bytes: int, new_tokens: int,
                     state_slots: int, starts: int, kv_tokens: int,
                     window_tokens: int, dtype_bytes: int = 2) -> float:
    """HBM bytes one serving step has to move: the weights once — of the
    embedding the new tokens' rows AND, the head being tied to it, every
    row again for the logits — every named slot's state in and out every
    Mamba-1 layer (a slot that starts is not read; the tails with them),
    the ONE full pool's live tokens ONCE A READER (the layer that owns
    them and every cross layer fetch them: 1 + the cross layers), and
    the window layers' live tokens once each."""
    kinds = layer_kinds(c)
    readers = 1 + kinds.count("X")
    return float(weight_bytes
                 + dtype_bytes * new_tokens * c["hidden_size"]
                 + kinds.count("S") * state_bytes(c, dtype_bytes)
                 * (2 * state_slots - starts)
                 + kv_row_bytes(c, dtype_bytes)
                 * (readers * kv_tokens + kinds.count("W") * window_tokens))
