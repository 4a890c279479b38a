"""Operations and bytes that an A.X-K1 serving step REQUIRES, from shapes.

Lower bounds, as in ``costs.py`` and ``costs_laguna.py``: what the
algorithm has to compute and move, not what an implementation happens
to (a cache row stored padded to 640 columns still NEEDS its 576: the
padding is waste, charged to the kernel's time and not to its need).
``c`` is the system's ``cfg``: the published keys as run,
``n_routed_experts`` the router's width, ``experts_held`` = (first,
count) the share held here, ``vocab_size`` the rows held.  Checked by
hand in ``tests/test_axk1.py``.
"""

from __future__ import annotations

from typing import Iterable, Mapping, Tuple

from .costs_laguna import attended_pairs, roofline_seconds  # noqa: F401


def attention_params(c: Mapping) -> int:
    """W_qa, the q norm, W_qb, W_kva, the latent norm, W_kvb, W_o."""
    h, nh = c["hidden_size"], c["num_attention_heads"]
    rq, r = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    return (h * rq + rq + rq * nh * (dn + dr) + h * (r + dr) + r
            + r * nh * (dn + dv) + nh * dv * h)


def expert_params(c: Mapping) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_params(c: Mapping, dense: bool) -> int:
    """One layer as HELD: attention, two norms, and the dense FFN or the
    router (all outputs), the held experts and the shared ones."""
    h = c["hidden_size"]
    n = attention_params(c) + 2 * h
    if dense:
        return n + 3 * h * c["intermediate_size"]
    return (n + h * c["n_routed_experts"]
            + (c["experts_held"][1] + c["n_shared_experts"])
            * expert_params(c))


def n_params(c: Mapping) -> int:
    """Parameters this chip holds: its layers, the embedding and the
    head over the vocabulary held, the last norm."""
    return (sum(layer_params(c, i < c["first_k_dense_replace"])
                for i in range(c["num_hidden_layers"]))
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def latent_row_values(c: Mapping) -> int:
    """Values a token a layer NEEDS in the cache: latent + rope key."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def mla_attention_cost(c: Mapping, seqs: Iterable[Tuple[int, int]],
                       dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's latent attention over ``seqs`` =
    (new tokens, kv length after them).  Bytes: every visible cache
    token's row (latent + rope key) read once, the queries in at the
    row's width and the output out at the latent's, a head.  FLOPs, a
    sequence, the LESSER of the two forms of the same numbers: absorbed
    (each attended pair costs 2 x (latent + rope) + 2 x latent a head)
    and unabsorbed (2 x (nope + rope) + 2 x v a head a pair, after every
    visible token's keys and values are built from its latent: 2 x
    latent x heads x (nope + v))."""
    nh, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    row = latent_row_values(c)
    flops = byts = 0.0
    for n, kv_len in seqs:
        if n <= 0:
            continue
        pairs = attended_pairs(n, kv_len)
        absorbed = 2.0 * nh * (row + r) * pairs
        unabsorbed = 2.0 * nh * (dn + dr + dv) * pairs \
            + 2.0 * kv_len * r * nh * (dn + dv)
        flops += min(absorbed, unabsorbed)
        byts += (kv_len * row + n * nh * (row + r)) * dtype_bytes
    return flops, byts


def serve_step_bytes(weight_bytes: int, c: Mapping, seqs,
                     dtype_bytes: int = 2) -> float:
    """HBM bytes one serving step has to move: every weight held once
    and, a layer, every live cache token's row."""
    live = sum(kv for n, kv in seqs if n > 0)
    return weight_bytes + (c["num_hidden_layers"] * live
                           * latent_row_values(c) * dtype_bytes)
