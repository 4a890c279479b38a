"""Operations and bytes that a serving step of the Falcon-H1 decoder
REQUIRES, from shapes.

Lower bounds, as in ``costs.py``: what the algorithm has to compute and
move, the same whatever implements it.  ``c`` is the system's ``cfg``
(the published keys as run: ``num_hidden_layers`` the layers held,
``vocab_size`` the rows held).  Checked by hand in
``tests/test_falcon.py``.
"""

from __future__ import annotations

from typing import Mapping, Sequence, Tuple

from .costs import roofline_seconds  # noqa: F401


def conv_dim(c: Mapping) -> int:
    return c["mamba_d_ssm"] + 2 * c["mamba_n_groups"] * c["mamba_d_state"]


def mamba_params(c: Mapping) -> int:
    """W_in, the convolution and its bias, dt_bias + A_log + D, the
    gated norm's gain, W_out."""
    h, d, w, nh = (c["hidden_size"], c["mamba_d_ssm"], conv_dim(c),
                   c["mamba_n_heads"])
    return (h * (d + w + nh) + w * c["mamba_d_conv"] + w + 3 * nh + d
            + d * h)


def attention_params(c: Mapping) -> int:
    h, dd = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    return h * dd * (nq + 2 * nkv) + nq * dd * h


def ffn_params(c: Mapping) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_params(c: Mapping) -> int:
    """Both mixers on the one norm, the FFN on its own: two gains."""
    return (mamba_params(c) + attention_params(c) + ffn_params(c)
            + 2 * c["hidden_size"])


def n_params(c: Mapping) -> int:
    """What this chip holds: its layers, its rows of the embedding and
    of the head, the last norm."""
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def state_only_bytes(c: Mapping) -> int:
    """The recurrent state a sequence holds in ONE layer: heads x head
    width x state size, float32, as the state-minor pool stores it (no
    lane is padded: the state's columns are whole registers)."""
    return c["mamba_d_ssm"] * c["mamba_d_state"] * 4


def state_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """... and the convolution's tail with it: what a slot stores."""
    return state_only_bytes(c) \
        + (c["mamba_d_conv"] - 1) * conv_dim(c) * dtype_bytes


def kv_row_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """K and V of one token in ONE layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * dtype_bytes


def ssm_update_cost(c: Mapping, slots: int, dtype_bytes: int = 2
                    ) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's state update for ``slots`` live
    decode slots in the state-minor layout: each slot's state once in
    and once out; its row's dt x [P, H] and decay [H] in float32, its
    groups' B and C rows [G, N] in float32 (NOT expanded to heads), y
    [P, H] out.  5 FLOPs an element of the state (decay, the outer
    product's multiply-add, the read-out's)."""
    nh, p, n, g = (c["mamba_n_heads"], c["mamba_d_head"],
                   c["mamba_d_state"], c["mamba_n_groups"])
    row = (p * nh + nh) * 4 + 2 * g * n * 4 + p * nh * 4
    return 5.0 * nh * p * n * slots, \
        float(slots * (2 * state_only_bytes(c) + row))


def ssm_chunk_scan_cost(c: Mapping, rows: int, starts: bool,
                        dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's scan over a chunk of ``rows`` rows
    of one sequence, in scan chunks of ``mamba_chunk_size``: C B^T a
    group, the masked product with x' a head, the read-out of and the
    update to the state a head; the slot's state once in (not where the
    launch starts the sequence) and once out, a row's x', B, C in the
    serving type, its dt in float32, its y out."""
    nh, p, n, g = (c["mamba_n_heads"], c["mamba_d_head"],
                   c["mamba_d_state"], c["mamba_n_groups"])
    if not rows:
        return 0.0, 0.0
    L = c["mamba_chunk_size"]
    chunks = -(-rows // L)
    flops = chunks * (2.0 * L * L * n * g + 2.0 * L * L * p * nh
                      + 4.0 * L * n * p * nh)
    row = conv_dim(c) * dtype_bytes + 4 * nh + c["mamba_d_ssm"] * dtype_bytes
    return flops, float(state_only_bytes(c) * (2 - bool(starts))
                        + row * rows)


def attention_cost(c: Mapping, seqs: Sequence[Tuple[int, int]],
                   dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's ragged attention over ``seqs`` =
    (new tokens, context after them) a sequence, at 5 query heads a KV
    head: QK^T and PV over the causal part, each live cache token's K
    and V read once a sequence, q in and o out."""
    nq, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    flops = byts = 0.0
    for n, ln in seqs:
        if n <= 0:
            continue
        pairs = n * ln - n * (n - 1) / 2.0      # causal (query, key) pairs
        flops += 4.0 * nq * d * pairs
        byts += (2 * nkv * d * ln + 2 * nq * d * n) * dtype_bytes
    return flops, byts


def serve_step_bytes(c: Mapping, weight_bytes: int, new_tokens: int,
                     state_slots: int, starts: int, kv_tokens: int,
                     dtype_bytes: int = 2) -> float:
    """HBM bytes one serving step has to move: the weights held once —
    of the embedding only the new tokens' rows — every named slot's
    state in and out EVERY layer (a slot that starts is not read; the
    tails with them), and every layer's live cache tokens."""
    h, nl = c["hidden_size"], c["num_hidden_layers"]
    weights = weight_bytes - dtype_bytes * (c["vocab_size"] - new_tokens) * h
    return float(weights
                 + nl * state_bytes(c, dtype_bytes)
                 * (2 * state_slots - starts)
                 + nl * kv_row_bytes(c, dtype_bytes) * kv_tokens)
