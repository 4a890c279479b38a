"""Operations and bytes that a serving step of the Ling 3.0 hybrid
REQUIRES, from shapes.

Lower bounds, as in ``costs.py``: what the algorithm has to compute and
move, the same whatever implements it.  ``c`` is the system's ``cfg``
(the published keys as run: ``layers_held`` the published indices of the
layers held, ``num_experts`` the ROUTER's width, ``experts_held`` the
share).  Checked by hand in ``tests/test_ling.py``.
"""

from __future__ import annotations

from typing import Mapping, Tuple

from .costs import roofline_seconds  # noqa: F401

SUB_CHUNK = 64


def kinds(c: Mapping) -> dict:
    """Blocks by kind: a layer is a mixer (K or L) and an FFN (D or E)."""
    held = c.get("layers_held") or range(c["num_hidden_layers"])
    n_l = sum((i + 1) % c["layer_group_size"] == 0 for i in held)
    n_d = sum(i < c["first_k_dense_replace"] for i in held)
    return {"K": len(held) - n_l, "L": n_l, "D": n_d, "E": len(held) - n_d}


def kda_width(c: Mapping) -> int:
    return c["num_attention_heads"] * c["head_dim"]


def kda_params(c: Mapping) -> int:
    """W_q, W_k, W_v and their convolutions, W_f and dt_bias, A_log,
    W_beta, W_g, the heads' gain, W_o."""
    h, w, nh = c["hidden_size"], kda_width(c), c["num_attention_heads"]
    return (3 * h * w + 3 * w * c["short_conv_kernel_size"] + h * w + w
            + nh + 2 * h * nh + c["head_dim"] + w * h)


def latent_params(c: Mapping) -> int:
    """W_q, W_kva and the latent's gain, W_kvb, W_g, W_o."""
    h, nh, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    return (h * nh * (dn + dr) + h * (r + dr) + r + r * nh * (dn + dv)
            + h * nh + nh * dv * h)


def dense_params(c: Mapping) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: Mapping) -> int:
    """Three matrices on the full width."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def moe_params(c: Mapping, experts: int) -> int:
    """The router over ALL experts and its bias, the shared expert, and
    ``experts`` experts."""
    h, e = c["hidden_size"], c["num_experts"]
    return (h * e + e + 3 * h * c["moe_shared_expert_intermediate_size"]
            + experts * expert_params(c))


def experts_held(c: Mapping) -> int:
    held = c.get("experts_held")
    return held[1] if held else c["num_experts"]


def _params(c: Mapping, experts: int) -> int:
    k = kinds(c)
    h = c["hidden_size"]
    return (k["K"] * kda_params(c) + k["L"] * latent_params(c)
            + k["D"] * dense_params(c) + k["E"] * moe_params(c, experts)
            + 2 * (k["K"] + k["L"]) * h         # a norm a block
            + 2 * c["vocab_size"] * h + h)


def n_params(c: Mapping) -> int:
    """What this chip holds: its layers' blocks, its experts, its rows
    of the embedding and of the head, the norms."""
    return _params(c, experts_held(c))


def n_params_active(c: Mapping) -> int:
    """Parameters a token meets: ``num_experts_per_tok`` experts a routed
    block, everything else once."""
    return _params(c, c["num_experts_per_tok"])


def state_only_bytes(c: Mapping) -> int:
    """The recurrent state a sequence holds in ONE KDA block: heads x
    head width x head width, float32."""
    return kda_width(c) * c["head_dim"] * 4


def state_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """... and the three convolutions' tails with it: what a slot
    stores."""
    return state_only_bytes(c) + (c["short_conv_kernel_size"] - 1) * 3 \
        * kda_width(c) * dtype_bytes


def kv_row_values(c: Mapping) -> int:
    """The values of one token's row in the latent block as the
    algorithm needs them: the latent and the rope key."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def kv_row_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """... and as STORED: padded to whole 128-lane registers."""
    return -(-kv_row_values(c) // 128) * 128 * dtype_bytes


def kda_update_cost(c: Mapping, live: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE KDA block's decode-row update in one
    launch: each live slot's state once in and once out; its row's q, k,
    log decay, v and beta in float32 in, its o out.  7 FLOPs an element
    of the state: the decay, S'^T k and S'^T q (a multiply-add each),
    the rank-one write's multiply-add."""
    nh, d = c["num_attention_heads"], c["head_dim"]
    row = nh * (4 * d + 1) * 4 + nh * d * 4
    return 7.0 * nh * d * d * live, float(
        (2 * state_only_bytes(c) + row) * live)


def kda_chunk_cost(c: Mapping, rows: int, starts: bool
                   ) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE KDA block's scan of a chunk of ``rows`` rows
    (whole sub-chunks of 64) of one sequence: its state once in (not
    where the launch starts the sequence) and once out, a row's
    operands in and its o out.  FLOPs a sub-chunk a head in the WY form:
    the two decayed Gram matrices (3 x 64^2 x d each), the unit-lower
    inverse (2 x 64^3 / 3), the products with the state and with the
    pseudo-values (6 x 64 x d^2 + 4 x 64^2 x d)."""
    if not rows:
        return 0.0, 0.0
    nh, d, s = c["num_attention_heads"], c["head_dim"], SUB_CHUNK
    n = -(-rows // s)
    row = nh * (4 * d + 1) * 4 + nh * d * 4
    flops = n * nh * (6.0 * s * s * d + 2.0 * s ** 3 / 3
                      + 6.0 * s * d * d + 4.0 * s * s * d)
    return flops, float(state_only_bytes(c) * (2 - bool(starts))
                        + row * n * s)


def serve_step_bytes(c: Mapping, weight_bytes: int, new_tokens: int,
                     state_slots: int, starts: int, kv_tokens: int,
                     experts_hit: float, dtype_bytes: int = 2) -> float:
    """HBM bytes one serving step has to move: the weights held once —
    of the embedding only the new tokens' rows, of the experts only those
    that receive a row — every named slot's state in and out a KDA block
    (a slot that starts is not read; the tails with them), and the
    latent block's live cache tokens (their 576 values)."""
    k = kinds(c)
    h = c["hidden_size"]
    unhit = k["E"] * experts_held(c) - experts_hit
    weights = weight_bytes - dtype_bytes * (
        c["vocab_size"] * h - new_tokens * h + unhit * expert_params(c))
    return float(weights
                 + k["K"] * state_bytes(c, dtype_bytes)
                 * (2 * state_slots - starts)
                 + k["L"] * kv_row_values(c) * dtype_bytes * kv_tokens)
