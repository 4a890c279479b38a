"""Operations and bytes that a serving step of the Ouro looped decoder
REQUIRES, from shapes.

Lower bounds, as in ``costs.py``: what the algorithm has to compute and
move.  ``c`` is the system's ``cfg`` (the published keys as run).  The
layer list runs ``total_ut_steps`` times a token: the layers' weights
cross once a PASS, and a token keeps a cache row for every (pass,
layer) slot, each read by that slot's attention alone.  A sequence is
(new tokens, length after them), as the harness sees it.  Checked by
hand in ``tests/test_ouro.py``.
"""

from __future__ import annotations

from typing import Iterable, Mapping, Tuple

from .costs import ragged_attention_cost, roofline_seconds  # noqa: F401


def layer_params(c: Mapping) -> int:
    """q, k, v, o; gate, up, down; FOUR gains (sandwich norms)."""
    h, d = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    return (h * d * (nq + 2 * nkv) + nq * d * h
            + 3 * h * c["intermediate_size"] + 4 * h)


def n_params(c: Mapping) -> int:
    """The layers (held ONCE), the embedding, the untied head, the last
    norm, the exit gate's vector and bias."""
    h, v = c["hidden_size"], c["vocab_size"]
    return (c["num_hidden_layers"] * layer_params(c) + 2 * v * h + h
            + h + 1)


def slots(c: Mapping) -> int:
    """Cache slots a token: one for every pass of every layer."""
    return c["total_ut_steps"] * c["num_hidden_layers"]


def row_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """One cache row of one slot: K and V of every KV head."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * dtype_bytes


def token_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """All of a token's cache rows."""
    return slots(c) * row_bytes(c, dtype_bytes)


def serve_step_bytes(c: Mapping, seqs: Iterable[Tuple[int, int]],
                     dtype_bytes: int = 2) -> float:
    """HBM bytes one serving step has to move: the layers' weights once
    a pass, the last norm and the gate with them, the head once, one
    embedding row a new token, and every live token's rows once (each
    slot's by its own attention)."""
    h = c["hidden_size"]
    seqs = [(n, ln) for n, ln in seqs if n > 0]
    loop = c["num_hidden_layers"] * layer_params(c) + 2 * h + 1
    return float(dtype_bytes * (
        c["total_ut_steps"] * loop + h * c["vocab_size"]
        + h * sum(n for n, _ in seqs))
        + token_bytes(c, dtype_bytes) * sum(ln for _, ln in seqs))


def serve_step_flops(c: Mapping, rows: int, logit_rows: int,
                     seqs: Iterable[Tuple[int, int]], page_size: int
                     ) -> float:
    """FLOPs of one serving step over ``rows`` rows of the flat buffer:
    two a matmul parameter a row a pass, the gate, the head over the
    rows whose logits are taken, and attention's pairs in every slot."""
    h = c["hidden_size"]
    matmul = layer_params(c) - 4 * h
    attn, _ = ragged_attention_cost(c, seqs, page_size)
    return (2.0 * rows * c["total_ut_steps"]
            * (c["num_hidden_layers"] * matmul + h)
            + 2.0 * logit_rows * h * c["vocab_size"] + slots(c) * attn)
