"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606) for serving: a residual of ``n``
streams a token, x [T, n C] (stream j in columns [j C, (j + 1) C)),
mixed around every sublayer by coefficients computed from the token's
whole stream.

For one sublayer with weights ``phi`` [n C, n^2 + 2 n], ``b``, ``a``::

    r     = (mean(x^2) + eps)^-1/2            one scalar a row, over n C
    u     = r (x phi)                         [n^2 + 2 n], float32
    Hpre  = sigmoid(a_pre u_pre + b_pre)      [n]
    Hpost = 2 sigmoid(a_post u_post + b_post) [n]
    Hres  = Sinkhorn(exp(clamp(a_res u_res + b_res)))   [n, n]
    x_in  = sum_j Hpre_j x_j                  [C]: the sublayer's input
    x'_i  = sum_j Hres[i, j] x_j + Hpost_i y  the stream after it

The work is memory-bound and small-shaped: two passes over the stream
would be all it needs, and XLA makes five or six (the norm's reduction,
the product, the weighted sum, 40 normalisations of [T, 4, 4] arrays
whose minor dimensions pad to an (8, 128) tile, the update). Two
kernels, each ONE pass:

- `mhc_pre`: a block of rows of the stream once into VMEM. The product
  with ``phi`` runs on the MXU against the block as it lies (``phi`` is
  kept TURNED, [rows of coefficients, n C], so the result is [24 -> 32,
  rows]: the ROWS on the lanes); the sum of squares is taken from the
  same block. The coefficient algebra and the Sinkhorn iterations run
  on [1, rows] vectors — one register a coefficient, never a [rows, 4,
  4] array — and go back to rows-on-sublanes through ONE turn of a
  128 x 128 register square, from which ``x_in`` is made of the block
  still resident and the coefficients leave as a lane-dense [rows, 128]
  array.
- `mhc_post`: the stream, ``y`` and the coefficients once in, the
  stream once out IN PLACE (``input_output_aliases``).

The parameters arrive packed (`references.mhc_pack`): ``phi_t`` [32,
n C] in the stream's type and ``ab`` [32, 128] float32, a group of
coefficients a sublane tile. Plain ``jnp`` forms of both
(`references.mhc_pre_reference` / `mhc_post_reference`) are the oracle
and the model's own forward. A shape the kernels cannot tile on a TPU
(`mhc_tileable`) has no second path here: `ServingEngine` refuses it by
name at construction (`engine._mhc_step_eligible`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .references import MHC_COEF_LANES, mhc_layout

__all__ = ["mhc_pre", "mhc_post", "mhc_tileable", "mhc_row_block",
           "mhc_enter", "mhc_exit"]

#: rows of the stream a grid step of `mhc_pre` holds: one 128 x 128
#: register square of coefficients is turned a step. At n C = 14,336
#: bfloat16 a block is 3.5 MiB, 7 MiB double-buffered, beside 0.9 MiB
#: of ``phi_t`` (twice) and the outputs' 1 MiB (twice): 11.6 MiB of the
#: 16 MiB a core has (`analysis/vmemmodel.py`); 256 rows would not fit
_PRE_ROWS = 128
#: ... and of `mhc_post`, which holds the block twice (in, and out):
#: 2 x 2 x 1.75 MiB at 64 rows, beside y and the coefficients
_POST_ROWS = 64
#: lanes of the stream a statement of either kernel touches at once
_CHUNK = 512
_HI = jax.lax.Precision.HIGHEST


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def mhc_row_block(T: int, rows: int) -> int:
    """Rows a grid step holds: `rows`, or all T where T is not whole
    blocks (interpreted shapes only: `mhc_tileable`)."""
    return rows if T % rows == 0 else T


def mhc_tileable(T: int, n: int, C: int) -> bool:
    """Whether both kernels' blocks tile on a TPU: whole row blocks and
    streams of whole 128-lane registers."""
    return T % _PRE_ROWS == 0 and C % 128 == 0 and n * n + 2 * n <= \
        MHC_COEF_LANES


def _chunks(C: int):
    """Static (start, width) lane chunks of one stream."""
    step = min(_CHUNK, C)
    return [(c, min(step, C - c)) for c in range(0, C, step)]


# ---------------------------------------------------------------------------
# before a sublayer: coefficients and the sublayer's input, one pass
# ---------------------------------------------------------------------------

def _pre_kernel(x_ref, phi_ref, ab_ref, xin_ref, coef_ref, sq_ref, *,
                n: int, C: int, eps: float, hc_eps: float, iters: int,
                lo: float, hi: float):
    f32 = jnp.float32
    R = x_ref.shape[0]
    S = sq_ref.shape[0]                     # the square that is turned
    post0, res0, _ = mhc_layout(n)
    x = x_ref[...]
    # [coefficients, rows]: phi_t against the block as it lies
    u = jax.lax.dot_general(
        phi_ref[...], x, (((1,), (1,)), ((), ())),
        preferred_element_type=f32,
        precision=_HI if x.dtype == f32 else None)
    ss = jnp.zeros((R, 1), f32)
    for j in range(n):
        for c0, cw in _chunks(C):
            xc = x_ref[:, j * C + c0:j * C + c0 + cw].astype(f32)
            ss = ss + jnp.sum(xc * xc, axis=1, keepdims=True)
    r_col = jax.lax.rsqrt(ss / (n * C) + eps)           # [R, 1]
    # rows on the sublanes -> rows on the lanes: a register square
    sq_ref[...] = jnp.zeros((S, S), f32)
    sq_ref[0:R, :] = jnp.broadcast_to(r_col, (R, S))
    r_row = sq_ref[...].T[0:1, 0:R]                     # [1, R]
    z = ab_ref[:, 0:1] * (u * r_row) + ab_ref[:, 1:2]   # [32, R]

    def row(k):
        return z[k:k + 1, :]

    hpre = [jax.nn.sigmoid(row(j)) for j in range(n)]
    hpost = [2.0 * jax.nn.sigmoid(row(post0 + j)) for j in range(n)]
    m = tuple(jnp.exp(jnp.clip(row(res0 + k), lo, hi))
              for k in range(n * n))

    def sinkhorn(_, m):
        col = [sum(m[i * n + j] for i in range(n)) + hc_eps
               for j in range(n)]
        m = [m[i * n + j] / col[j] for i in range(n) for j in range(n)]
        rw = [sum(m[i * n + j] for j in range(n)) + hc_eps
              for i in range(n)]
        return tuple(m[i * n + j] / rw[i]
                     for i in range(n) for j in range(n))

    m = jax.lax.fori_loop(0, iters, sinkhorn, m)
    # back to rows on the sublanes: coefficient k to lane k
    sq_ref[...] = jnp.zeros((S, S), f32)
    for k, v in enumerate(hpost + list(m) + hpre):
        sq_ref[k:k + 1, 0:R] = v
    coef = sq_ref[...].T[0:R, 0:MHC_COEF_LANES]         # [R, 128]
    coef_ref[...] = coef
    p0 = n + n * n
    for c0, cw in _chunks(C):
        acc = jnp.zeros((R, cw), f32)
        for j in range(n):
            acc = acc + coef[:, p0 + j:p0 + j + 1] \
                * x_ref[:, j * C + c0:j * C + c0 + cw].astype(f32)
        xin_ref[:, c0:c0 + cw] = acc.astype(xin_ref.dtype)


def mhc_pre(x, phi_t, ab, *, n: int, eps: float = 1e-6,
            hc_eps: float = 1e-6, iters: int = 20,
            clamp=(-30.0, 30.0)):
    """x [T, n C], ``phi_t`` [rows, n C] (x's type), ``ab`` [rows, 128]
    float32 (`references.mhc_pack`) -> (x_in [T, C] in x's type, coef
    [T, 128] float32: lanes [0, n) Hpost, [n, n + n^2) Hres row-major,
    then n lanes of Hpre). Grid (T / R,): a step holds R rows of the
    stream, read ONCE."""
    T, nC = x.shape
    C = nC // n
    R = mhc_row_block(T, _PRE_ROWS)
    S = max(R, MHC_COEF_LANES)
    rows = phi_t.shape[0]
    return pl.pallas_call(
        functools.partial(_pre_kernel, n=n, C=C, eps=float(eps),
                          hc_eps=float(hc_eps), iters=int(iters),
                          lo=float(clamp[0]), hi=float(clamp[1])),
        grid=(T // R,),
        in_specs=[pl.BlockSpec((R, nC), lambda i: (i, 0)),
                  pl.BlockSpec((rows, nC), lambda i: (0, 0)),
                  pl.BlockSpec((rows, MHC_COEF_LANES), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((R, C), lambda i: (i, 0)),
                   pl.BlockSpec((R, MHC_COEF_LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, C), x.dtype),
                   jax.ShapeDtypeStruct((T, MHC_COEF_LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((S, S), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
    )(x, phi_t.astype(x.dtype), ab)


# ---------------------------------------------------------------------------
# after a sublayer: the stream once in, once out, in place
# ---------------------------------------------------------------------------

def _post_kernel(x_ref, y_ref, coef_ref, o_ref, *, n: int, C: int):
    f32 = jnp.float32
    coef = coef_ref[...]
    for c0, cw in _chunks(C):
        y = y_ref[:, c0:c0 + cw].astype(f32)
        xs = [x_ref[:, j * C + c0:j * C + c0 + cw].astype(f32)
              for j in range(n)]
        for i in range(n):
            acc = coef[:, i:i + 1] * y
            for j in range(n):
                k = n + i * n + j
                acc = acc + coef[:, k:k + 1] * xs[j]
            o_ref[:, i * C + c0:i * C + c0 + cw] = acc.astype(o_ref.dtype)


def mhc_post(x, y, coef, *, n: int):
    """x [T, n C], the sublayer's output y [T, C], ``coef`` [T, 128]
    float32 from `mhc_pre` -> the stream after the sublayer, written
    over x (aliased: the caller's x is dead). Grid (T / R,): a step
    holds R rows of the stream once in and once out."""
    T, nC = x.shape
    C = nC // n
    R = mhc_row_block(T, _POST_ROWS)
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n, C=C),
        grid=(T // R,),
        in_specs=[pl.BlockSpec((R, nC), lambda i: (i, 0)),
                  pl.BlockSpec((R, C), lambda i: (i, 0)),
                  pl.BlockSpec((R, MHC_COEF_LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((R, nC), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
    )(x, y.astype(x.dtype), coef)


# ---------------------------------------------------------------------------
# entry and exit of the stream (plain XLA: a copy and a sum)
# ---------------------------------------------------------------------------

def mhc_enter(h, n: int):
    """h [T, C] -> the stream [T, n C]: every stream the embedding."""
    return jnp.tile(h, (1, n))


def mhc_exit(x, n: int):
    """The stream [T, n C] -> [T, C]: the SUM of the streams, taken in
    float32, in x's type."""
    C = x.shape[1] // n
    return sum(x[:, j * C:(j + 1) * C].astype(jnp.float32)
               for j in range(n)).astype(x.dtype)


# ---------------------------------------------------------------------------
# certification (paddlelint PK105)
# ---------------------------------------------------------------------------

from .oracles import register_oracle  # noqa: E402

register_oracle(
    "mhc_pre", kernel=mhc_pre,
    reference="paddle_tpu.ops.references:mhc_pre_reference",
    parity_test="tests/test_mhc.py::TestPre")
register_oracle(
    "mhc_post", kernel=mhc_post,
    reference="paddle_tpu.ops.references:mhc_post_reference",
    parity_test="tests/test_mhc.py::TestPost")
