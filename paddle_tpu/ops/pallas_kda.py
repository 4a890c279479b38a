"""Kimi delta attention (KDA: a gated DELTA RULE with a decay for every
key channel) for serving: a FIXED-SIZE recurrent state a (sequence,
layer) in a slot-indexed pool.

For head ``h``, state ``S`` [K, V] float32 (K the key width, V the
value width), log decay ``g_t`` [K] <= 0, write strength ``beta_t``::

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

Unlike Mamba-2's elementwise update (`ops.pallas_ssm`) the write needs
``S'^T k`` — a reduction over the state — BEFORE the rank-one update.

The pool is ``[slots, H, K, V]`` float32, a head's state a [K, V] tile
with V along the lanes: both reductions (``S'^T k``, ``S'^T q``) run
down the sublanes — elementwise adds of whole registers — and their
results, like ``v``, ``o`` and the write ``beta (v - S'^T k)``, are lane
vectors. What multiplies a ROW of the state (the decay, ``k``, ``q``)
is needed as a column [K, 1]; the rows' operands arrive heads-major
with K along the lanes and are turned once a (slot, block of heads) in
the kernel. (Heads-minor, Mamba-2's layout, would fill 32 of 128
lanes.)

- `kda_state_update`: ONE step of the recurrence for the decode rows.
  Each LIVE slot's state is read once and written once, in place
  (``input_output_aliases``); a slot no row names is neither read nor
  written. Memory-bound by construction: 2 x H x K x V x 4 bytes a
  slot. In the kernel the state makes two passes through the registers:
  the first decays it and takes both reductions (``o = S'^T q + (k.q)
  w`` needs no third), the second adds ``k w^T`` and stores.
- `kda_chunk_scan`: a run of rows of ONE sequence from its state, in
  sub-chunks (64): inside a sub-chunk the WY / UT-transform form — a
  unit-lower-triangular solve a head — between sub-chunks the state.
  Plain XLA (differentiable: the eager model runs it under `jax.vjp`),
  every product a batch of float32 `HIGHEST` matmuls over the heads,
  which are turned batch-major once at the entry; the sub-chunks of a
  chunk, known at trace time, are a Python loop, not a `lax.scan`. The
  decay between two rows of a sub-chunk is exp of the DIFFERENCE of
  their cumulative log gates (<= 0), never a quotient of two
  exponentials: at the gate's lower bound (-5 a token) a sub-chunk
  spans e^-320. Pair by pair only inside diagonal blocks of 16 rows;
  between blocks the difference is split at a row between the two, two
  exponents <= 0 on the two operands of a matmul over K
  (`_decayed_grams`). Rows whose ``g`` and ``beta`` are 0 are the
  identity, so a chunk is padded by zeroing both.
- a slot is written in place by `ops.pallas_ssm.ssm_state_put`: the
  pool is four-dimensional like Mamba-2's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_state_update", "kda_chunk_scan", "kda_tileable"]

#: heads a grid step of the update holds: a block of [HB, K, V] float32
#: (512 KiB at K = V = 128)
_HB = 8
_HI = jax.lax.Precision.HIGHEST


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _head_block(H: int) -> int:
    return _HB if H % _HB == 0 else H


def kda_tileable(H: int, K: int, V: int) -> bool:
    """Whether the update kernel's blocks tile on a TPU: whole (8, 128)
    float32 registers of state, and the rows' [HB, K] operands turned
    as whole registers."""
    return H % _HB == 0 and K % 128 == 0 and V % 128 == 0


# ---------------------------------------------------------------------------
# one step of the recurrence, the decode rows
# ---------------------------------------------------------------------------

def _columns(rows, K: int):
    """rows [HB, K] -> [K, HB']: head j's vector as column j. On the
    chip a whole [K, K] register square is turned (the rows padded with
    zeros); interpreted, the transpose itself."""
    HB = rows.shape[0]
    if HB < K:
        rows = jnp.concatenate(
            [rows, jnp.zeros((K - HB, K), rows.dtype)], 0)
    return rows.T


def _update_kernel(slots_ref, n_ref,                    # scalar prefetch
                   q_ref, k_ref, g_ref, v_ref, b_ref, sin_ref,
                   o_ref, sout_ref, *, HB: int):
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]
    # nothing live: every step names the spare slot's last block, which
    # goes through unchanged ONCE, as a step of decay 1 that writes
    # nothing (an output block is written back whether or not a step
    # stored to it)
    seed = (n == 0) & (i == 0) & (j == 0)

    @pl.when((i < n) | seed)
    def _step():
        K = q_ref.shape[-1]
        k = k_ref[0]                                    # [HB, K]
        q = q_ref[0]
        qc = _columns(q, K)
        kc = _columns(k, K)
        ac = _columns(jnp.where(seed, 1.0, jnp.exp(g_ref[0])), K)
        beta = jnp.where(seed, 0.0, b_ref[0])           # [HB, 1]
        kq = jnp.sum(k * q, axis=1, keepdims=True)      # [HB, 1]
        v = v_ref[0]                                    # [HB, V]
        for h in range(HB):
            kh = kc[:, h:h + 1]                         # [K, 1]
            s = ac[:, h:h + 1] * sin_ref[0, h]          # S' [K, V]
            u = jnp.sum(s * kh, axis=0, keepdims=True)  # S'^T k [1, V]
            o = jnp.sum(s * qc[:, h:h + 1], axis=0, keepdims=True)
            w = beta[h:h + 1] * (v[h:h + 1] - u)        # [1, V]
            sout_ref[0, h] = s + kh * w
            o_ref[0, h:h + 1, :] = o + kq[h:h + 1] * w


def kda_state_update(pool, slots, n_live, q, k, v, g, beta):
    """One step of the recurrence for the launch's decode rows, the
    pool updated in place.

    pool [NS, H, K, V] float32; ``slots`` [B] int32: the live slots
    FIRST (any order), then padding that names the spare slot NS - 1;
    ``n_live`` [1] int32. Row ``s`` of the operands belongs to slot
    ``s``, all float32: ``q`` / ``k`` [R, H, K] (as the recurrence reads
    them: normalised, ``q`` scaled), ``g`` [R, H, K] (log decay, <= 0),
    ``v`` [R, H, V], ``beta`` [R, H, 1]; R >= NS.

    Returns (o [NS, H, V] float32 — S_t^T q_t; rows of slots that are
    not live hold nothing meaningful — and the pool). Grid (B, H / HB):
    a step holds [HB, K, V] of one slot's state; the steps past the live
    slots repeat the last live block, which stays resident and is not
    touched, so an idle slot's state is neither read nor written."""
    NS, H, K, V = pool.shape
    B = slots.shape[0]
    HB = _head_block(H)
    J = H // HB

    def at(i, slots, n):
        # (slot, whether idle) of step i: past the live ones, the last
        # live slot's last block again
        last = jnp.maximum(n[0] - 1, 0)
        return jnp.clip(slots[jnp.minimum(i, last)], 0, NS - 1), i >= n[0]

    def state_map(i, j, slots, n):
        s, idle = at(i, slots, n)
        return (s, jnp.where(idle, J - 1, j), 0, 0)

    def row_map(i, j, slots, n):
        s, idle = at(i, slots, n)
        return (s, jnp.where(idle, J - 1, j), 0)

    state_spec = pl.BlockSpec((1, HB, K, V), state_map)
    key_spec = pl.BlockSpec((1, HB, K), row_map)
    val_spec = pl.BlockSpec((1, HB, V), row_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, J),
        in_specs=[key_spec, key_spec, key_spec, val_spec,
                  pl.BlockSpec((1, HB, 1), row_map), state_spec],
        out_specs=[val_spec, state_spec],
    )
    f32 = jnp.float32
    o, new_pool = pl.pallas_call(
        functools.partial(_update_kernel, HB=HB),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((NS, H, V), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # flat-input indices INCLUDE the scalar-prefetch operands
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(slots.astype(jnp.int32), n_live.astype(jnp.int32), q.astype(f32),
      k.astype(f32), g.astype(f32), v.astype(f32), beta.astype(f32), pool)
    return o, new_pool


# ---------------------------------------------------------------------------
# a run of rows of one sequence, in sub-chunks
# ---------------------------------------------------------------------------

def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` [..., C, C], C
    a power of two, by doubling: the inverse of the diagonal blocks of
    size 2b from those of size b, ``[[X, 0], [-Y A21 X, Y]]`` — block
    forward substitution, six levels at C = 64, the first of which (X
    = Y = 1) is a subtraction."""
    C = a.shape[-1]
    r = jnp.arange(C)

    def below(b):       # A21 of every diagonal block of size 2b
        return jnp.where(
            (r[:, None] // b == r[None, :] // b + 1)
            & (r[:, None] // (2 * b) == r[None, :] // (2 * b)), a, 0.0)

    d = jnp.eye(C, dtype=a.dtype) - below(1)
    b = 2
    while b < C:
        d = d - jnp.matmul(jnp.matmul(d, below(b), precision=_HI), d,
                           precision=_HI)
        b *= 2
    return d


#: rows of a DIAGONAL block of a sub-chunk's decayed Gram matrices: inside
#: one the decays are taken pair by pair (elementwise), between blocks
#: they factor into two matmul operands. A constant of the arithmetic,
#: not a knob: a sub-chunk of fewer rows is one diagonal block.
_GRAM_BLOCK = 16


def _decayed_grams(q, k, cs):
    """The two decayed Gram matrices of sub-chunks, heads batch-major: q,
    k and cs (the cumulative log gates, decreasing down the rows) [...,
    C, K], C a power of two -> (A, B) [..., C, C], ``A[t, i] = sum_c
    k_t[c] k_i[c] exp(cs_t[c] - cs_i[c])`` and B with ``q_t`` for i <=
    t, 0 above the diagonal.

    Inside the diagonal blocks of `_GRAM_BLOCK` rows the decays are
    taken pair by pair. Between them by halves: at half-size h (the
    block, then doubling to C / 2) a row t of the LATER half of its
    span of 2h rows sees a row i of the EARLIER half through the log
    gate ``rho`` at the earlier half's end, ``(x_t exp(cs_t - rho)) .
    (k_i exp(rho - cs_i))`` — one matmul over K a level for every span
    at once, rows of the other half zeroed and pairs of different spans
    masked off. Both exponents are <= 0 and each factor bounds the
    decay it is a part of, so a factor that underflows stands for a
    decay that underflows too: no quotient of two exponentials, no
    positive exponent."""
    C, K = k.shape[-2:]
    lead = k.shape[:-2]
    b = min(C, _GRAM_BLOCK)
    qb, kb, cb = (a.reshape(lead + (C // b, b, K)) for a in (q, k, cs))
    t = jnp.arange(b)
    seg = cb[..., :, None, :] - cb[..., None, :, :]     # [.., b, b, K]
    kd = kb[..., None, :, :] * jnp.where(               # k_i as row t sees it
        (t[:, None] >= t[None, :])[..., None],
        jnp.exp(jnp.minimum(seg, 0.0)), 0.0)
    # (elementwise into both reductions: no [.., b, b, K] array is kept)
    eye = jnp.eye(C // b, dtype=k.dtype)[:, None, :, None]
    grams = [(jnp.sum(x[..., :, None, :] * kd, -1)[..., :, :, None, :]
              * eye).reshape(lead + (C, C)) for x in (kb, qb)]
    t = jnp.arange(C)
    kq = jnp.stack([k, q], -3)                          # [.., 2, C, K]
    h = b
    while h < C:
        spans = lead + (C // (2 * h), 2 * h, K)
        rho = jnp.broadcast_to(
            cs.reshape(spans)[..., h - 1:h, :], spans).reshape(cs.shape)
        later = ((t // h) % 2 == 1)[:, None]
        low = jnp.where(later, jnp.exp(jnp.minimum(cs - rho, 0.0)), 0.0)
        high = jnp.where(later, 0.0, jnp.exp(jnp.minimum(rho - cs, 0.0)))
        g = jnp.einsum("...rtc,...ic->...rti", kq * low[..., None, :, :],
                       k * high, precision=_HI)
        same = t[:, None] // (2 * h) == t[None, :] // (2 * h)
        grams = [m + jnp.where(same, g[..., r, :, :], 0.0)
                 for r, m in enumerate(grams)]
        h *= 2
    return grams


def _cumsum_rows(g, reverse: bool = False):
    """The inclusive sum down the rows of g [..., C, K] (up them, if
    `reverse`): by doubling (shifted adds) inside the blocks of
    `_GRAM_BLOCK` rows, the blocks' totals summed in order.
    (`jnp.cumsum` reaches the chip as a matmul with a triangle of ones,
    three times this.)"""
    C, K = g.shape[-2:]
    b = min(C, _GRAM_BLOCK)
    x = g.reshape(g.shape[:-2] + (C // b, b, K))
    lead = [(0, 0)] * (x.ndim - 2)
    d = 1
    while d < b:
        x = x + (jnp.pad(x[..., d:, :], lead + [(0, d), (0, 0)]) if reverse
                 else jnp.pad(x[..., :b - d, :], lead + [(d, 0), (0, 0)]))
        d *= 2
    # what the blocks before (after) a block add up to
    if reverse:
        rest = jnp.pad(jnp.cumsum(x[..., ::-1, 0, :], -2)[..., -2::-1, :],
                       lead[:-1] + [(0, 1), (0, 0)])
    else:
        rest = jnp.pad(jnp.cumsum(x[..., -1, :], -2)[..., :-1, :],
                       lead[:-1] + [(1, 0), (0, 0)])
    return (x + rest[..., None, :]).reshape(g.shape)


def _heads_major(a, chunk: int):
    """[L, H, ...] -> [L / chunk, H, chunk, ...]."""
    return jnp.moveaxis(a.reshape((-1, chunk) + a.shape[1:]), 2, 1)


def kda_chunk_scan(q, k, v, g, beta, state, *, chunk: int = 64):
    """A run of L rows of ONE sequence from ``state``, in sub-chunks of
    ``chunk`` rows, a power of two (L is padded up with identity rows).

    q, k [L, H, K] (normalised, ``q`` scaled), v [L, H, V], g [L, H, K]
    (log decay, <= 0), beta [L, H], state [H, K, V]; float32 inside. A
    row with ``g`` 0 and ``beta`` 0 changes nothing and its own output
    is discarded by the caller. Returns (o [L, H, V] float32 = S_t^T
    q_t, the state after the last row).

    The operands are turned heads batch-MAJOR once ([sub-chunks, H,
    chunk, .]) and ``o`` once at the exit; every product is a batch of
    matmuls over the heads. What does not read the state — the Gram
    matrices, the solve, every decay — is taken for all sub-chunks at
    once; the sub-chunks, known at trace time, are then a Python loop
    of four products each."""
    if chunk & (chunk - 1):
        raise ValueError(f"sub-chunk {chunk} must be a power of two")
    L = q.shape[0]
    f32 = jnp.float32
    pad = -L % chunk
    q, k, v, g, beta = (_heads_major(jnp.pad(
        a.astype(f32), ((0, pad),) + ((0, 0),) * (a.ndim - 1)), chunk)
        for a in (q, k, v, g, beta))
    cs = _cumsum_rows(g)                                # <= 0
    a, b = _decayed_grams(q, k, cs)                     # [n, H, C, C]
    t = jnp.arange(chunk)
    a = jnp.where(t[:, None] > t[None, :], a, 0.0) * beta[..., :, None]
    tinv = _unit_lower_inverse(a) * beta[..., None, :]  # (I + A)^-1 Diag(b)
    gam = jnp.exp(cs)                                   # from the start
    kq = jnp.concatenate([k * gam, q * gam], -2)        # [n, H, 2 C, K]
    kl = k * jnp.exp(_cumsum_rows(g, reverse=True) - g)  # to the end, <= 1
    state, o = state.astype(f32), []
    for j in range(q.shape[0]):
        # what row i's write sees of the state it starts from (and q's
        # rows read of it), then the pseudo-values
        # U = (I + A)^-1 Diag(beta) (V - (Gamma k) S_0)
        from_state = jnp.matmul(kq[j], state, precision=_HI)
        u = jnp.matmul(tinv[j], v[j] - from_state[:, :chunk], precision=_HI)
        o.append(from_state[:, chunk:] + jnp.matmul(b[j], u, precision=_HI))
        state = gam[j, :, -1, :, None] * state + jnp.einsum(
            "hic,hiv->hcv", kl[j], u, precision=_HI)
    o = jnp.moveaxis(jnp.stack(o), 1, 2)                # [n, C, H, V]
    return o.reshape((-1,) + o.shape[2:])[:L], state


# ---------------------------------------------------------------------------
# certification (paddlelint PK105)
# ---------------------------------------------------------------------------

from .oracles import register_oracle  # noqa: E402

register_oracle(
    "kda_state_update", kernel=kda_state_update,
    reference="paddle_tpu.ops.references:kda_state_update_reference",
    parity_test="tests/test_kda_kernel.py::TestStateUpdate")
