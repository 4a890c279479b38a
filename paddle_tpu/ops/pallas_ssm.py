"""Mamba-2 (SSD, arXiv:2405.21060) state-space mixing for serving: a
FIXED-SIZE recurrent state a (sequence, layer) in a slot-indexed pool.

For head ``h`` (group ``g = h // (H / G)``), state ``S_h`` [P, N]::

    S_t = exp(dt_t[h] A[h]) S_{t-1} + dt_t[h] x_t[h] (outer) B_t[g]
    y_t[h] = S_t C_t[g]                     (the caller adds D[h] x_t[h])

The pool is stored HEADS-MINOR, ``[slots, P, N, H]`` float32 (P the
head width, N the state size, H the heads): for a fixed (p, n) the H
heads lie along the 128 lanes, so a head's scalars (its decay, its
``dt``) are lane vectors, a group's ``B`` / ``C`` rows expanded to heads
are [N, H] tiles, and the update is plain elementwise work on whole
registers — no transpose, no broadcast across lanes.

- `ssm_state_update`: ONE step of the recurrence for the decode rows.
  Each LIVE slot's state is read once and written once, in place
  (``input_output_aliases``); a slot no row names is neither read nor
  written. Memory-bound by construction: 2 x P x N x H x 4 bytes a slot.
- `ssm_chunk_scan`: a run of rows of ONE sequence from its state, in
  scan chunks (the config's ``chunk_size``, 128): inside a chunk the
  quadratic (attention-like) form, between chunks the state. Plain XLA:
  batched matmuls over (group, head). Rows whose ``dt`` is 0 are the
  identity, so a chunk is padded by zeroing ``dt``.
- `ssm_state_put`: writes one slot of the pool in place (the chunk's
  new state).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_state_update", "ssm_chunk_scan", "ssm_state_put"]

#: rows of P a grid step of the update holds: a block of
#: [PB, N, H] float32 (1 MiB at N = H = 128)
_PB = 16


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _p_block(P: int) -> int:
    return _PB if P % _PB == 0 else P


# ---------------------------------------------------------------------------
# one step of the recurrence, the decode rows
# ---------------------------------------------------------------------------

def _update_kernel(slots_ref, n_ref,                    # scalar prefetch
                   xdt_ref, dec_ref, b_ref, c_ref, sin_ref,
                   y_ref, sout_ref, *, PB: int):
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]
    # nothing live: every step names the spare slot's last block, which
    # goes through unchanged ONCE, as a step of decay 1 that adds
    # nothing (an output block is written back whether or not a step
    # stored to it)
    seed = (n == 0) & (i == 0) & (j == 0)

    @pl.when((i < n) | seed)
    def _step():
        dec = jnp.where(seed, 1.0, dec_ref[0])          # [1, H]
        bm = jnp.where(seed, 0.0, b_ref[0].astype(jnp.float32))  # [N, H]
        cm = c_ref[0].astype(jnp.float32)
        for p in range(PB):
            new = dec * sin_ref[0, p] + xdt_ref[0, p:p + 1, :] * bm
            sout_ref[0, p] = new
            y_ref[0, p:p + 1, :] = jnp.sum(new * cm, axis=0, keepdims=True)


def ssm_state_update(pool, slots, n_live, xdt, dec, bh, ch):
    """One step of the recurrence for the launch's decode rows, the
    pool updated in place.

    pool [NS, P, N, H] float32; ``slots`` [B] int32: the live slots
    FIRST (any order), then padding that names the spare slot NS - 1;
    ``n_live`` [1] int32. Row ``s`` of the operands belongs to slot
    ``s``: ``xdt`` [R, P, H] float32 (dt x), ``dec`` [R, 1, H] float32
    (exp(dt A)), ``bh`` / ``ch`` [R, N, H] (a group's B / C rows expanded
    to its heads), R >= NS.

    Returns (y [NS, P, H] float32 — S_t C_t; rows of slots that are not
    live hold nothing meaningful — and the pool). Grid (B, P / PB): a
    step holds [PB, N, H] of one slot's state; the steps past the live
    slots repeat the last live block, which stays resident and is not
    touched, so an idle slot's state is neither read nor written."""
    NS, P, N, H = pool.shape
    B = slots.shape[0]
    PB = _p_block(P)
    J = P // PB

    def at(i, slots, n):
        # (slot, block of P) of step i: past the live ones, the last
        # live slot's last block again
        last = jnp.maximum(n[0] - 1, 0)
        return jnp.clip(slots[jnp.minimum(i, last)], 0, NS - 1), i >= n[0]

    def state_map(i, j, slots, n):
        s, idle = at(i, slots, n)
        return (s, jnp.where(idle, J - 1, j), 0, 0)

    def row_map(i, j, slots, n):
        s, idle = at(i, slots, n)
        return (s, jnp.where(idle, J - 1, j), 0)

    def whole_map(i, j, slots, n):
        return (at(i, slots, n)[0], 0, 0)

    state_spec = pl.BlockSpec((1, PB, N, H), state_map)
    row_spec = pl.BlockSpec((1, PB, H), row_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, J),
        in_specs=[row_spec, pl.BlockSpec((1, 1, H), whole_map),
                  pl.BlockSpec((1, N, H), whole_map),
                  pl.BlockSpec((1, N, H), whole_map), state_spec],
        out_specs=[row_spec, state_spec],
    )
    y, new_pool = pl.pallas_call(
        functools.partial(_update_kernel, PB=PB),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((NS, P, H), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # flat-input indices INCLUDE the scalar-prefetch operands
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(slots.astype(jnp.int32), n_live.astype(jnp.int32), xdt, dec, bh, ch,
      pool)
    return y, new_pool


# ---------------------------------------------------------------------------
# one slot written in place
# ---------------------------------------------------------------------------

def _put_kernel(slot_ref, new_ref, pin_ref, po_ref):
    go = slot_ref[1] > 0

    # nothing to put: every step names one block, which goes through
    # unchanged once
    @pl.when(go | (pl.program_id(0) == 0))
    def _put():
        po_ref[0] = jnp.where(go, new_ref[...], pin_ref[0])


def ssm_state_put(pool, slot, state):
    """``pool`` [NS, P, N, H] with slot ``slot[0]`` replaced by ``state``
    [P, N, H], in place: the other slots are not touched.  ``slot`` is
    [2] int32: (the slot, whether to put at all) — with 0 there the
    pool comes back as it was."""
    NS, P, N, H = pool.shape
    PB = _p_block(P)
    J = P // PB

    def block(j, s):
        return jnp.where(s[1] > 0, j, J - 1)

    spec = pl.BlockSpec(
        (1, PB, N, H),
        lambda j, s: (jnp.clip(s[0], 0, NS - 1), block(j, s), 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(J,),
        in_specs=[pl.BlockSpec((PB, N, H),
                               lambda j, s: (block(j, s), 0, 0)), spec],
        out_specs=spec)
    return pl.pallas_call(
        _put_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(slot.astype(jnp.int32), state.astype(pool.dtype), pool)


# ---------------------------------------------------------------------------
# a run of rows of one sequence, in scan chunks
# ---------------------------------------------------------------------------

def _scan_chunk(state, rows, *, G: int):
    """One scan chunk: (state [P, N, H], (xdt [L, H, P], dA [L, H], B,
    C [L, G, N])) -> (new state, y [L, H, P]); float32."""
    xdt, dA, bm, cm = rows
    L, H, P = xdt.shape
    N, K = bm.shape[-1], H // G
    hi = jax.lax.Precision.HIGHEST
    cs = jnp.cumsum(dA, 0)                              # [L, H], <= 0
    # inside the chunk: y_t += sum_{r<=t} exp(cs_t - cs_r) (C_t.B_r) xdt_r
    t = jnp.arange(L)
    seg = jnp.where((t[:, None] >= t[None, :])[..., None],
                    cs[:, None, :] - cs[None, :, :], -jnp.inf)
    cb = jnp.einsum("tgn,rgn->trg", cm, bm)             # [L, L, G]
    m = jnp.exp(seg).reshape(L, L, G, K) * cb[..., None]
    x5 = xdt.reshape(L, G, K, P)
    y = jnp.einsum("trgk,rgkp->tgkp", m, x5)
    # from the state the chunk starts with
    s5 = state.reshape(P, N, G, K)
    y = y + jnp.exp(cs).reshape(L, G, K, 1) * jnp.einsum(
        "tgn,pngk->tgkp", cm, s5, precision=hi)
    # the state the chunk leaves
    w = jnp.exp(cs[-1][None] - cs).reshape(L, G, K, 1)
    new = jnp.exp(cs[-1]).reshape(1, 1, G, K) * s5 + jnp.einsum(
        "rgkp,rgn->pngk", x5 * w, bm, precision=hi)
    return new.reshape(P, N, H), y.reshape(L, H, P)


def ssm_chunk_scan(xdt, dA, bm, cm, state, *, chunk: int = 128):
    """A run of L rows of ONE sequence from ``state``, in scan chunks of
    ``chunk`` rows (L is padded up with identity rows).

    xdt [L, H, P] (dt x), dA [L, H] (dt A, <= 0), bm / cm [L, G, N],
    state [P, N, H]; float32 inside. A row with ``dt`` 0 changes
    nothing (its xdt and dA are 0) and its own y is discarded by the
    caller. Returns (y [L, H, P] float32 = S_t C_t, the state after the
    last row)."""
    L, H, P = xdt.shape
    G = bm.shape[1]
    f32 = jnp.float32
    pad = -L % chunk
    rows = tuple(jnp.pad(a.astype(f32), ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                 .reshape((-1, chunk) + a.shape[1:])
                 for a in (xdt, dA, bm, cm))
    state, y = jax.lax.scan(functools.partial(_scan_chunk, G=G),
                            state.astype(f32), rows)
    return y.reshape(-1, H, P)[:L], state


# ---------------------------------------------------------------------------
# certification (paddlelint PK105)
# ---------------------------------------------------------------------------

from .oracles import register_oracle  # noqa: E402

register_oracle(
    "ssm_state_update", kernel=ssm_state_update,
    reference="paddle_tpu.ops.references:ssm_state_update_reference",
    parity_test="tests/test_pallas_ssm.py::TestStateUpdate")
register_oracle(
    "ssm_state_put", kernel=ssm_state_put,
    reference="paddle_tpu.ops.references:ssm_state_put_reference",
    parity_test="tests/test_pallas_ssm.py::TestStatePut")
