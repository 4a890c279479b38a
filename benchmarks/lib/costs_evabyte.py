"""Operations and bytes that an EvaByte serving step REQUIRES, from
shapes.

Lower bounds, as in ``costs.py``: what the algorithm has to compute and
move.  ``c`` is the system's ``cfg`` (the published keys as run).  A
sequence is (new tokens, length after them), as the harness sees it;
the engine never lets a launch's tokens straddle a window, so the new
tokens' window is the last one's.  Checked by hand in
``tests/test_evabyte.py``.
"""

from __future__ import annotations

from typing import Iterable, Mapping, Tuple

from .costs_laguna import attended_pairs, roofline_seconds  # noqa: F401


def head_dim(c: Mapping) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def layer_params(c: Mapping) -> int:
    """q, k, v, o; gate, up, down; two norm offsets; phi and mu."""
    h, i = c["hidden_size"], c["intermediate_size"]
    return 4 * h * h + 3 * h * i + 2 * h \
        + 2 * c["num_attention_heads"] * head_dim(c)


def n_params(c: Mapping) -> int:
    """The layers, the embedding, the byte heads, the last norm."""
    h, v = c["hidden_size"], c["vocab_size"]
    return (c["num_hidden_layers"] * layer_params(c) + v * h
            + h * c["num_pred_heads"] * v + h)


def row_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """One cache row of one layer, exact or pooled: K and V of every
    head."""
    return 2 * c["num_key_value_heads"] * head_dim(c) * dtype_bytes


def rows_read(c: Mapping, n: int, length: int) -> Tuple[int, int]:
    """(pooled, exact) rows a layer reads for a sequence whose ``n`` new
    tokens end at ``length``: the pooled rows of every closed window,
    and the current window's rows."""
    if n <= 0:
        return 0, 0
    closed = (length - 1) // c["window_size"]
    return (closed * (c["window_size"] // c["chunk_size"]),
            length - closed * c["window_size"])


def eva_attention_cost(c: Mapping, seqs: Iterable[Tuple[int, int]],
                       dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's attention over ``seqs``.  Bytes:
    each visible pooled and exact row once a sequence a launch, the
    queries in and the output out.  FLOPs: 2 x head_dim for the score
    and 2 x head_dim for the value of every (query, row) pair a query
    sees — every pooled row, and the exact rows up to itself — a head."""
    H, D = c["num_attention_heads"], head_dim(c)
    flops = byts = 0.0
    for n, length in seqs:
        pooled, exact = rows_read(c, n, length)
        if not exact:
            continue
        pairs = n * pooled + attended_pairs(n, exact)
        flops += 4.0 * H * D * pairs
        byts += (pooled + exact) * row_bytes(c, dtype_bytes) \
            + 2 * n * H * D * dtype_bytes
    return flops, byts


def eva_pool_cost(c: Mapping, chunks: int,
                  dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's pooling of ``chunks`` closed chunks:
    a chunk's rows read once and one row written; a head, the scores
    (2 x head_dim a row) and two weighted sums (2 x head_dim a row
    each)."""
    H, D, ck = c["num_attention_heads"], head_dim(c), c["chunk_size"]
    return (6.0 * H * D * ck * chunks,
            float((ck + 1) * row_bytes(c, dtype_bytes) * chunks))


def serve_step_bytes(weight_bytes: int, c: Mapping, seqs,
                     dtype_bytes: int = 2) -> float:
    """HBM bytes one serving step has to move: every weight held once
    and, a layer, every row each live sequence reads."""
    rows = sum(sum(rows_read(c, n, length)) for n, length in seqs)
    return weight_bytes + (c["num_hidden_layers"] * rows
                           * row_bytes(c, dtype_bytes))
