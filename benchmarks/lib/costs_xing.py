"""Operations and bytes that a Xing 4.0 serving step REQUIRES, from
shapes.

Lower bounds, as in ``costs_axk1.py`` (whose latent-attention and cache
costs apply unchanged and are re-exported): what the algorithm has to
compute and move, not what an implementation happens to.  ``c`` is the
system's ``cfg``: the published keys as run, ``n_routed_experts`` the
router's width, ``experts_held`` = (first, count) the share held here,
``vocab_size`` the rows held.  Checked by hand in
``tests/test_xing.py``.
"""

from __future__ import annotations

from typing import Mapping, Tuple

from .costs_axk1 import (attention_params, expert_params,  # noqa: F401
                         latent_row_values, mla_attention_cost,
                         roofline_seconds)


def mixing_outputs(c: Mapping) -> int:
    """Coefficients a sublayer's mixing computes a token: n for the
    sublayer's input, n for its output, n x n for the residual."""
    n = c["hc_mult"]
    return n * n + 2 * n


def mixing_params(c: Mapping) -> int:
    """One sublayer's mixing: phi [n C, n^2 + 2 n], b, (a_pre, a_post,
    a_res)."""
    k = mixing_outputs(c)
    return c["hc_mult"] * c["hidden_size"] * k + k + 3


def layer_params(c: Mapping, dense: bool) -> int:
    """One layer as HELD: attention, two norms, TWO sublayers' mixing,
    and the dense FFN or the router (all outputs, with its correction
    bias), the held experts and the shared ones."""
    h = c["hidden_size"]
    n = attention_params(c) + 2 * h + 2 * mixing_params(c)
    if dense:
        return n + 3 * h * c["intermediate_size"]
    return (n + (h + 1) * c["n_routed_experts"]
            + (c["experts_held"][1] + c["n_shared_experts"])
            * expert_params(c))


def n_params(c: Mapping) -> int:
    """Parameters this chip holds: its layers, the embedding and the
    head over the vocabulary held, the last norm."""
    return (sum(layer_params(c, i < c["first_k_dense_replace"])
                for i in range(c["num_hidden_layers"]))
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def stream_row_bytes(c: Mapping, dtype_bytes: int = 2) -> int:
    """One token's residual: n streams of the hidden width."""
    return c["hc_mult"] * c["hidden_size"] * dtype_bytes


def mhc_sublayer_cost(c: Mapping, rows: int, dtype_bytes: int = 2
                      ) -> Tuple[float, float, float]:
    """(FLOPs, the stream's bytes, the other bytes) that ONE sublayer's
    mixing over ``rows`` rows REQUIRES in one launch — `mhc_pre` and
    `mhc_post` together, as the program's own
    `observability.costmodel` counts the two kernels' operands
    (``tests/test_xing.py`` ties the two).  The stream: once in for the
    coefficients and the sublayer's input, once in and once out for the
    update.  The rest: the sublayer's input out and its output in, and
    the weights ``phi`` once.  FLOPs a row: the product with ``phi`` (2
    x n C x (n^2 + 2 n)), the sum of squares (2 x n C), the sublayer's
    input (2 x n C) and the update (2 x (n^2 + n) x C); the Sinkhorn
    iterations (some 2 k a row) are left out.

    Which MEMORY the stream's bytes cross is the compiler's choice: a
    launch of 384 rows x 28,672 B = 11 MB fits the chip's on-chip
    memory and the compiled step keeps it there from entry to exit
    (`S(1)` on every `bf16[384,14336]` of the compiled text; the two
    kernels read 1.1 TB a second of their time, over the main memory's
    819 GB/s: PERF.md section 6, PR 50).  So no share of the HBM
    roofline is made of them (it read 136 %), and `serve_step_bytes`
    leaves the stream out; a launch too large to stay on the chip pays
    them in main memory."""
    n, h, k = c["hc_mult"], c["hidden_size"], mixing_outputs(c)
    flops = rows * (2.0 * n * h * k + 4.0 * n * h + 2.0 * (n * n + n) * h)
    stream = 3.0 * rows * stream_row_bytes(c, dtype_bytes)
    rest = (2.0 * rows * h + n * h * k) * dtype_bytes
    return flops, stream, rest


def sublayers(c: Mapping) -> int:
    return 2 * c["num_hidden_layers"]


def serve_step_bytes(c: Mapping, weight_bytes: int, new_tokens: int,
                     kv_tokens: int, experts_hit: float,
                     dtype_bytes: int = 2) -> float:
    """HBM bytes one serving step has to move: the weights held once —
    of the embedding only the new tokens' rows, of the experts only
    those that receive a row (``experts_hit``, summed over the routed
    layers); the mixing's ``phi`` are among them — and every live cache
    token's row (latent + rope key) a layer.  The four-stream residual
    adds nothing: a launch's stream stays on the chip
    (`mhc_sublayer_cost`)."""
    h = c["hidden_size"]
    routed = c["num_hidden_layers"] - c["first_k_dense_replace"]
    unhit = routed * c["experts_held"][1] - experts_hit
    weights = weight_bytes - dtype_bytes * (
        c["vocab_size"] * h - new_tokens * h + unhit * expert_params(c))
    return float(weights + c["num_hidden_layers"] * kv_tokens
                 * latent_row_values(c) * dtype_bytes)
