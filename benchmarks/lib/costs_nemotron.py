"""Operations and bytes that a serving step of the Nemotron-H hybrid
REQUIRES, from shapes.

Lower bounds, as in ``costs.py``: what the algorithm has to compute and
move, the same whatever implements it.  ``c`` is the system's ``cfg``
(the published keys as run: ``hybrid_override_pattern`` cut to the
blocks held, ``n_routed_experts`` the ROUTER's width, ``experts_held``
the share).  Checked by hand in ``tests/test_nemotron.py``.
"""

from __future__ import annotations

from typing import Mapping, Tuple

from .costs import roofline_seconds  # noqa: F401

SCAN_CHUNK = 128


def kinds(c: Mapping) -> dict:
    p = c["hybrid_override_pattern"][:c["num_hidden_layers"]]
    return {k: p.count(k) for k in "M*E"}


def d_inner(c: Mapping) -> int:
    return c["mamba_num_heads"] * c["mamba_head_dim"]


def conv_dim(c: Mapping) -> int:
    return d_inner(c) + 2 * c["n_groups"] * c["ssm_state_size"]


def mamba_params(c: Mapping) -> int:
    """W_in, the convolution and its bias, dt_bias + A_log + D, the
    gated norm's gain, W_out, the block's norm."""
    h, d, w, nh = c["hidden_size"], d_inner(c), conv_dim(c), \
        c["mamba_num_heads"]
    return (h * (d + w + nh) + w * c["conv_kernel"] + w + 3 * nh + d
            + d * h + h)


def attention_params(c: Mapping) -> int:
    h, dd = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    return h * dd * (nq + 2 * nkv) + nq * dd * h + h


def expert_params(c: Mapping) -> int:
    """Two matrices in the latent."""
    return 2 * c["moe_latent_size"] * c["moe_intermediate_size"]


def moe_params(c: Mapping, experts: int) -> int:
    """The router over ALL experts and its bias, the two latent
    projections, the shared expert on the full width, the block's norm,
    and ``experts`` experts."""
    h, e = c["hidden_size"], c["n_routed_experts"]
    return (h * e + e + 2 * h * c["moe_latent_size"]
            + 2 * h * c["moe_shared_expert_intermediate_size"] + h
            + experts * expert_params(c))


def experts_held(c: Mapping) -> int:
    held = c.get("experts_held")
    return held[1] if held else c["n_routed_experts"]


def n_params(c: Mapping) -> int:
    """What this chip holds: the blocks of its pattern, its experts, its
    rows of the embedding and of the head, the last norm."""
    k = kinds(c)
    return (k["M"] * mamba_params(c) + k["*"] * attention_params(c)
            + k["E"] * moe_params(c, experts_held(c))
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def n_params_active(c: Mapping) -> int:
    """Parameters a token meets: ``num_experts_per_tok`` experts a routed
    block, everything else once."""
    k = kinds(c)
    return (k["M"] * mamba_params(c) + k["*"] * attention_params(c)
            + k["E"] * moe_params(c, c["num_experts_per_tok"])
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def state_only_bytes(c: Mapping) -> int:
    """The recurrent state a sequence holds in ONE state-space block:
    heads x head width x state size, float32."""
    return d_inner(c) * c["ssm_state_size"] * 4


def state_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """... and the convolution's tail with it: what a slot stores."""
    return state_only_bytes(c) \
        + (c["conv_kernel"] - 1) * conv_dim(c) * dtype_bytes


def kv_row_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """K and V of one token in ONE attention block."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * dtype_bytes


def ssm_scan_cost(c: Mapping, decode_slots: int, chunk_rows: int,
                  chunk_starts: bool, dtype_bytes: int = 2
                  ) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE state-space block's recurrence in one
    launch: each live decode slot's state once in and once out, and the
    chunk's (not read where the launch starts the sequence); a row's x',
    B, C in the serving type, its dt in float32, its y out.  FLOPs: a
    decode row 5 an element of the state (decay, the outer product's
    multiply-add, the read-out's); the chunk in scan chunks of 128 rows
    — C B^T a group, the masked product with x' a head, the read-out of
    and the update to the state a head."""
    nh, p, n, g = (c["mamba_num_heads"], c["mamba_head_dim"],
                   c["ssm_state_size"], c["n_groups"])
    st = state_only_bytes(c)
    row = (d_inner(c) + 2 * g * n) * dtype_bytes + 4 * nh \
        + d_inner(c) * dtype_bytes
    rows = decode_slots + chunk_rows
    byts = 2 * st * decode_slots + row * rows
    flops = 5.0 * nh * p * n * decode_slots
    if chunk_rows:
        byts += st * (2 - bool(chunk_starts))
        chunks = -(-chunk_rows // SCAN_CHUNK)
        L = SCAN_CHUNK
        flops += chunks * (2.0 * L * L * n * g + 2.0 * L * L * p * nh
                           + 4.0 * L * n * p * nh)
    return flops, float(byts)


def moe_gmm_cost(c: Mapping, pairs_held: float, experts_hit: float,
                 dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of the routed blocks' two grouped GEMMs in the
    latent: 4 x latent x width FLOPs a held pair; each held expert that
    receives a row read once; a pair's latent row in and out."""
    z, w = c["moe_latent_size"], c["moe_intermediate_size"]
    flops = 4.0 * z * w * pairs_held
    byts = (experts_hit * expert_params(c) + 2 * pairs_held * z) \
        * dtype_bytes
    return flops, byts


def serve_step_bytes(c: Mapping, weight_bytes: int, new_tokens: int,
                     state_slots: int, starts: int, kv_tokens: int,
                     experts_hit: float, dtype_bytes: int = 2) -> float:
    """HBM bytes one serving step has to move: the weights held once —
    of the embedding only the new tokens' rows, of the experts only those
    that receive a row — every named slot's state in and out a
    state-space block (a slot that starts is not read; the tails with
    them), and the attention blocks' live cache tokens."""
    k = kinds(c)
    h = c["hidden_size"]
    unhit = k["E"] * experts_held(c) - experts_hit
    weights = weight_bytes - dtype_bytes * (
        c["vocab_size"] * h - new_tokens * h + unhit * expert_params(c))
    return float(weights
                 + k["M"] * state_bytes(c, dtype_bytes)
                 * (2 * state_slots - starts)
                 + k["*"] * kv_row_bytes(c, dtype_bytes) * kv_tokens)
