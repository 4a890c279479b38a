"""State-space mixing for serving: a FIXED-SIZE recurrent state a
(sequence, layer) in a slot-indexed float32 pool. TWO recurrences live
here, each with its own kernels:

**Mamba-2** (SSD, arXiv:2405.21060; Nemotron-H, Falcon-H1): ONE decay a
head. For head ``h`` (group ``g = h // (H / G)``), state ``S_h`` [P, N]::

    S_t = exp(dt_t[h] A[h]) S_{t-1} + dt_t[h] x_t[h] (outer) B_t[g]
    y_t[h] = S_t C_t[g]                     (the caller adds D[h] x_t[h])

(P the head width, N the state size, H the heads.)  Served by
`ssm_state_update` (the decode rows), `ssm_chunk_scan` (a chunk's rows:
SSD's quadratic form, which needs the one decay a head) and
`ssm_state_put`.

**Mamba-1** (the selective scan, arXiv:2312.00752; Phi-4-mini-flash): a
decay for every (channel, state column). For channel ``c`` of C, state
``h`` [N, C] (N = 16 columns, ``B`` / ``C`` rows of N shared by all
channels)::

    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c] = sum_n h_t[n, c] C_t[n]         (the caller adds D[c] x_t[c])

Served by `ssm1_state_update` (the decode rows) and `ssm1_chunk_scan` (a
chunk's rows: no quadratic form exists without the one decay, so a
channel block's state stays in VMEM and the kernel walks the rows), and
by the same `ssm_state_put`. Its pool is ``[slots, 1, N, C]``: the C
channels along the lanes, the N columns on the sublanes, nothing padded
(state-minor would store 16 columns in 128 lanes, eightfold).

Mamba-2's pool is float32
in ONE of two layouts, which `state_layout(H, N)` picks by what fills
the 128 lanes of the MINOR dimension — a minor dimension is stored in
whole 128-lane rows, so one of 32 is stored fourfold:

- HEADS-MINOR ``[slots, P, N, H]`` where H is whole registers
  (Nemotron-3-Super: 128 heads of 64 over a state of 128): for a fixed
  (p, n) the H heads lie along the lanes, so a head's scalars (its
  decay, its ``dt``) are lane vectors, a group's ``B`` / ``C`` rows
  expanded to heads are [N, H] tiles, and the update is plain
  elementwise work on whole registers — no transpose, no broadcast
  across lanes.
- STATE-MINOR ``[slots, H, P, N]`` where H is NOT whole registers and N
  is (Falcon-H1-34B: 32 heads of 128 over a state of 256, two registers
  wide; heads-minor would store 128 lanes for its 32 heads, 16 MiB a
  (slot, layer) for 4 MiB of state): a head's state is a [P, N] tile
  with the state's columns along the lanes, a group's ``B`` / ``C`` row
  is a lane vector shared by the group's heads (NOT expanded), and a
  head's ``dt x`` is a column [P, 1] that the kernel spreads along the
  lanes, its decay one value spread over the tile.

Shapes that fill neither (toy widths) stay heads-minor.

- `ssm_state_update`: ONE step of the recurrence for the decode rows.
  Each LIVE slot's state is read once and written once, in place
  (``input_output_aliases``); a slot no row names is neither read nor
  written. Memory-bound by construction: 2 x P x N x H x 4 bytes a slot.
- `ssm_chunk_scan`: a run of rows of ONE sequence from its state, in
  scan chunks (the config's ``chunk_size``, 128): inside a chunk the
  quadratic (attention-like) form, between chunks the state. Plain XLA,
  the heads batch-MAJOR: for head ``h`` of group ``g``, with ``cs`` the
  cumulative sum of ``dt A`` down a scan chunk of L rows (<= 0, so
  every exponential's argument is)::

      CB   = C_g B_g^T                                 [L, L]  once a group
      M    = tril(exp(cs_t - cs_r)) * CB               [L, L]
      Y_h  = M X'_h + exp(cs)[:, None] * (C_g S_h^T)   [L, P]
      S_h' = exp(cs_L) S_h + (X'_h * exp(cs_L - cs))^T B_g     [P, N]

  matmuls batched over (group, head), the two with the state float32
  `HIGHEST`; the scan chunks are a Python loop (two in a serving step),
  NOT a `lax.scan`: inside the step program the loop's stacked y was
  laid out in tiles of two rows and ONE dynamic-update-slice of it took
  half the scan's time (PERF.md section 6, PR 57). Rows whose ``dt`` is
  0 are the identity, so a chunk is padded by zeroing ``dt``.
- `ssm_state_put`: writes one slot of the pool in place (the chunk's
  new state); blocks of the pool's second dimension, either layout.

``layout="state_minor"`` selects the second form of the first two; the
default is the first, and its operands are what they were. The chunk's
scan is written over the state-minor slot [H, P, N]; a heads-minor
caller's ONE slot is turned around it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_state_update", "ssm_chunk_scan", "ssm_state_put",
           "ssm1_state_update", "ssm1_chunk_scan", "state_layout",
           "state_pool_shape", "HEADS_MINOR", "STATE_MINOR"]

HEADS_MINOR, STATE_MINOR = "heads_minor", "state_minor"
_LANES = 128

#: rows of the pool's second dimension a grid step holds, at most, and
#: the bytes of that block, at most (1 MiB: [16, 128, 128] heads-minor
#: at N = H = 128, [8, 128, 256] state-minor at P = 128, N = 256)
_PB = 16
_BLOCK_BYTES = 1 << 20


def state_layout(H: int, N: int) -> str:
    """The pool's layout for H heads over a state of N (module
    docstring): state-minor where N is whole 128-lane registers and H
    is not, else heads-minor."""
    return STATE_MINOR if N % _LANES == 0 and H % _LANES else HEADS_MINOR


def state_pool_shape(slots: int, H: int, P: int, N: int,
                     layout: str = HEADS_MINOR):
    """The pool's shape for `slots` slots in `layout`."""
    return (slots, H, P, N) if layout == STATE_MINOR else (slots, P, N, H)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _p_block(P: int, row_bytes: int = 0) -> int:
    """Rows of a pool's second dimension (of `row_bytes` each) a grid
    step holds: _PB, halved while the block is over _BLOCK_BYTES; all
    of them where that does not divide P."""
    pb = _PB
    while pb > 1 and pb * row_bytes > _BLOCK_BYTES:
        pb //= 2
    return pb if P % pb == 0 else P


# ---------------------------------------------------------------------------
# one step of the recurrence, the decode rows
# ---------------------------------------------------------------------------

def _slot_at(NS: int, i, slots, n):
    """(the slot of grid step i, whether the step is idle): past the
    live ones, the last live slot again, whose last block the index
    maps then name — it stays resident and is not touched."""
    last = jnp.maximum(n[0] - 1, 0)
    return jnp.clip(slots[jnp.minimum(i, last)], 0, NS - 1), i >= n[0]


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


def ssm_state_update(pool, slots, n_live, xdt, dec, bh, ch, *,
                     layout: str = HEADS_MINOR):
    """One step of the recurrence for the launch's decode rows, the
    pool updated in place.

    ``layout="state_minor"``: `_update_state_minor` (pool [NS, H, P, N],
    ``bh`` / ``ch`` a group's rows [R, G, N], not expanded).  Else:

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
    if layout == STATE_MINOR:
        return _update_state_minor(pool, slots, n_live, xdt, dec, bh, ch)
    NS, P, N, H = pool.shape
    B = slots.shape[0]
    PB = _p_block(P)
    J = P // PB

    at = functools.partial(_slot_at, NS)

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


def _update_sm_kernel(slots_ref, n_ref,                 # scalar prefetch
                      xdt_ref, dec_ref, b_ref, c_ref, sin_ref,
                      y_ref, sout_ref, *, HB: int):
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]
    seed = (n == 0) & (i == 0) & (j == 0)   # as `_update_kernel`'s

    @pl.when((i < n) | seed)
    def _step():
        H = xdt_ref.shape[-1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, H), 1)
        xdt = xdt_ref[0]                                # [P, H]
        dec = jnp.where(seed, 1.0, dec_ref[0])          # [1, H]
        bm = jnp.where(seed, 0.0, b_ref[0])             # [1, N]
        cm = c_ref[0]                                   # [1, N]

        @pl.when(j == 0)
        def _clear():
            y_ref[0] = jnp.zeros_like(y_ref[0])

        y = y_ref[0]
        for hb in range(HB):
            # head h's column of dt x and its decay, spread along the
            # lanes: a masked lane sum, no lane -> sublane move
            mine = lane == j * HB + hb
            col = jnp.sum(jnp.where(mine, xdt, 0.0), 1, keepdims=True)
            d = jnp.sum(jnp.where(mine, dec, 0.0), 1, keepdims=True)
            new = d * sin_ref[0, hb] + col * bm         # [P, N]
            sout_ref[0, hb] = new
            y = jnp.where(mine, jnp.sum(new * cm, 1, keepdims=True), y)
        y_ref[0] = y


def _update_state_minor(pool, slots, n_live, xdt, dec, bm, cm):
    """`ssm_state_update` over the state-minor pool [NS, H, P, N]:
    ``xdt`` [R, P, H] and ``dec`` [R, 1, H] as there, ``bm`` / ``cm``
    [R, G, N] a group's rows.  Returns (y [NS, P, H], the pool).  Grid
    (B, H / HB): a step holds HB heads of ONE group of one slot, [HB, P,
    N]; a slot's y block stays resident over its head blocks."""
    NS, H, P, N = pool.shape
    B = slots.shape[0]
    R, G = bm.shape[0], bm.shape[1]
    K = H // G
    HB = _p_block(K, P * N * 4)
    J = H // HB
    f32 = jnp.float32

    at = functools.partial(_slot_at, NS)

    def state_map(i, j, slots, n):
        s, idle = at(i, slots, n)
        return (s, jnp.where(idle, J - 1, j), 0, 0)

    def whole_map(i, j, slots, n):
        return (at(i, slots, n)[0], 0, 0)

    def group_map(i, j, slots, n):
        s, idle = at(i, slots, n)
        return (s * G + jnp.where(idle, J - 1, j) * HB // K, 0, 0)

    state_spec = pl.BlockSpec((1, HB, P, N), state_map)
    row_spec = pl.BlockSpec((1, P, H), whole_map)
    group_spec = pl.BlockSpec((1, 1, N), group_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, J),
        in_specs=[row_spec, pl.BlockSpec((1, 1, H), whole_map),
                  group_spec, group_spec, state_spec],
        out_specs=[row_spec, state_spec],
    )
    y, new_pool = pl.pallas_call(
        functools.partial(_update_sm_kernel, HB=HB),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((NS, P, H), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(slots.astype(jnp.int32), n_live.astype(jnp.int32), xdt, dec,
      bm.astype(f32).reshape(R * G, 1, N),
      cm.astype(f32).reshape(R * G, 1, N), pool)
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
    [P, N, H], in place: the other slots are not touched (a state-minor
    pool [NS, H, P, N] and its [H, P, N] alike: blocks of the second
    dimension).  ``slot`` is [2] int32: (the slot, whether to put at
    all) — with 0 there the pool comes back as it was."""
    NS, P, N, H = pool.shape
    PB = _p_block(P, N * H * pool.dtype.itemsize)
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

def ssm_chunk_scan(xdt, dA, bm, cm, state, *, chunk: int = 128,
                   layout: str = HEADS_MINOR):
    """A run of L rows of ONE sequence from ``state``, in scan chunks of
    ``chunk`` rows (L is padded up with identity rows).

    xdt [L, H, P] (dt x), dA [L, H] (dt A, <= 0), bm / cm [L, G, N],
    state [P, N, H] ([H, P, N] under ``layout="state_minor"``); float32
    inside. A row with ``dt`` 0 changes
    nothing (its xdt and dA are 0) and its own y is discarded by the
    caller. Returns (y [L, H, P] float32 = S_t C_t, the state after the
    last row).

    Plain XLA, the heads batch-MAJOR (module docstring's equations):
    every product is a matmul batched over (group, head) with the batch
    dimensions leading, and the scan chunks are walked by a Python loop
    — L / chunk is static, two in a serving step — so the program holds
    no loop and no stacked output for the compiler to lay out."""
    L, H, P = xdt.shape
    G, N = bm.shape[1:]
    K = H // G
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    pad = -L % chunk
    xdt, dA, bm, cm = (
        jnp.pad(a.astype(f32), ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        .reshape((-1, chunk) + a.shape[1:]) for a in (xdt, dA, bm, cm))
    x = xdt.reshape(-1, chunk, G, K, P).transpose(0, 2, 3, 1, 4)
    b, c = bm.transpose(0, 2, 1, 3), cm.transpose(0, 2, 1, 3)
    cs = jnp.cumsum(dA.reshape(-1, chunk, G, K), 1).transpose(0, 2, 3, 1)
    sm = layout == STATE_MINOR
    s = state.astype(f32)
    s = (s if sm else s.transpose(2, 0, 1)).reshape(G, K, P, N)
    t = jnp.arange(chunk)
    below = t[:, None] >= t[None, :]
    ys = []
    for x_i, b_i, c_i, cs_i in zip(x, b, c, cs):
        # x' [G, K, Lc, P], B and C [G, Lc, N], cs [G, K, Lc]; t >= r
        # keeps every exponential's argument <= 0
        m = jnp.exp(jnp.where(below, cs_i[..., :, None] - cs_i[..., None, :],
                              -jnp.inf)) \
            * jnp.einsum("gtn,grn->gtr", c_i, b_i)[:, None]
        ys.append(jnp.einsum("gktr,gkrp->gktp", m, x_i)
                  + jnp.exp(cs_i)[..., None] * jnp.einsum(
                      "gtn,gkpn->gktp", c_i, s, precision=hi))
        last = cs_i[..., -1:]
        s = jnp.exp(last)[..., None] * s + jnp.einsum(
            "gkrp,grn->gkpn", x_i * jnp.exp(last - cs_i)[..., None], b_i,
            precision=hi)
    y = jnp.stack(ys).transpose(0, 3, 1, 2, 4).reshape(-1, H, P)[:L]
    s = s.reshape(H, P, N)
    return y, (s if sm else s.transpose(1, 2, 0))


# ---------------------------------------------------------------------------
# Mamba-1: a decay for every (channel, state column)
# ---------------------------------------------------------------------------

#: rows of a chunk a grid step of `ssm1_chunk_scan` walks, at most
_RB = 64
#: channels (lanes) of a grid step of `ssm1_chunk_scan`, at most
_CB = 512


def _update1_kernel(slots_ref, n_ref,                   # scalar prefetch
                    dt_ref, x_ref, a_ref, b_ref, c_ref, sin_ref,
                    y_ref, sout_ref):
    i = pl.program_id(0)
    n = n_ref[0]
    seed = (n == 0) & (i == 0)      # as `_update_kernel`'s: dt 0 once

    @pl.when((i < n) | seed)
    def _step():
        dt = jnp.where(seed, 0.0, dt_ref[0])            # [1, C]
        new = jnp.exp(dt * a_ref[...]) * sin_ref[0, 0] \
            + (dt * x_ref[0]) * b_ref[0]                # [N, C]
        sout_ref[0, 0] = new
        y_ref[0] = jnp.sum(new * c_ref[0], axis=0, keepdims=True)


def ssm1_state_update(pool, slots, n_live, dt, x, a, bm, cm):
    """One step of the Mamba-1 recurrence for the launch's decode rows,
    the pool updated in place.

    pool [NS, 1, N, C] float32; ``slots`` [B] int32 and ``n_live`` [1] as
    `ssm_state_update`'s (the live slots first, then the spare NS - 1).
    Row ``s`` of the operands belongs to slot ``s``: ``dt`` [R, C]
    float32 (after the softplus; 0 is the identity), ``x`` [R, C]
    float32, ``bm`` / ``cm`` [R, N]; ``a`` [N, C] float32 (-exp(A_log),
    turned), R >= NS.

    Returns (y [NS, C] float32 — sum_n h_t C_t; rows of slots that are
    not live hold nothing meaningful — and the pool). Grid (B,): a step
    holds ONE slot's whole state [N, C] (327,680 B at 16 x 5120); the
    steps past the live slots repeat the last live block, which stays
    resident and is not touched, so an idle slot's state is neither read
    nor written."""
    NS, _, N, C = pool.shape
    B, R = slots.shape[0], dt.shape[0]
    f32 = jnp.float32

    def at(i, slots, n):
        return _slot_at(NS, i, slots, n)[0]

    row_spec = pl.BlockSpec((1, 1, C), lambda i, s, n: (at(i, s, n), 0, 0))
    col_spec = pl.BlockSpec((1, N, 1), lambda i, s, n: (at(i, s, n), 0, 0))
    state_spec = pl.BlockSpec((1, 1, N, C),
                              lambda i, s, n: (at(i, s, n), 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B,),
        in_specs=[row_spec, row_spec,
                  pl.BlockSpec((N, C), lambda i, s, n: (0, 0)),
                  col_spec, col_spec, state_spec],
        out_specs=[row_spec, state_spec])
    y, new_pool = pl.pallas_call(
        _update1_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((NS, 1, C), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # flat-input indices INCLUDE the scalar-prefetch operands
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(slots.astype(jnp.int32), n_live.astype(jnp.int32),
      dt.astype(f32).reshape(R, 1, C), x.astype(f32).reshape(R, 1, C),
      a.astype(f32), bm.astype(f32).reshape(R, N, 1),
      cm.astype(f32).reshape(R, N, 1), pool)
    return y[:, 0], new_pool


def _scan1_kernel(dt_ref, x_ref, a_ref, b_ref, c_ref, s0_ref,
                  y_ref, s1_ref):
    @pl.when(pl.program_id(1) == 0)
    def _start():
        s1_ref[...] = s0_ref[...]

    a = a_ref[...]                                      # [N, CB]
    h = s1_ref[...]
    for r in range(dt_ref.shape[0]):    # static: no dynamic row index
        dt = dt_ref[r:r + 1, :]                         # [1, CB]
        h = jnp.exp(dt * a) * h + (dt * x_ref[r:r + 1, :]) * b_ref[r]
        y_ref[r:r + 1, :] = jnp.sum(h * c_ref[r], axis=0, keepdims=True)
    s1_ref[...] = h


def ssm1_chunk_scan(dt, x, a, bm, cm, state):
    """A run of L rows of ONE sequence from ``state`` through the
    Mamba-1 recurrence (L is padded up to whole row blocks with identity
    rows).

    dt [L, C] float32 (after the softplus), x [L, C], a [N, C]
    (-exp(A_log), turned), bm / cm [L, N], state [..., N, C] (a slot of
    the pool, [1, N, C]); float32 inside. A row with ``dt`` 0 changes
    nothing and its own y is discarded by the caller. Returns (y [L, C]
    float32 = sum_n h_t C_t, the state after the last row, in
    ``state``'s shape).

    Grid (C / CB, L / RB): independent channel blocks, and for each the
    row blocks in order; a channel block's state [N, CB] is the resident
    output block (registers inside a step, at 16 x 512) while the step
    walks its RB rows — elementwise and sequential by nature: per row an
    exp, four multiplies and an add over [N, CB], and a sum over the N
    sublanes. ``B`` / ``C`` ride as [L, N, 1] columns, spread along the
    lanes in the kernel."""
    L, C = dt.shape
    N = a.shape[0]
    f32 = jnp.float32
    RB = min(_RB, -(-L // 8) * 8)
    pad = -L % RB
    dt, x, bm, cm = (jnp.pad(m.astype(f32), ((0, pad), (0, 0)))
                     for m in (dt, x, bm, cm))
    Lp = L + pad
    CB = next((cb for cb in (_CB, 256, 128) if C % cb == 0), C)
    rows_spec = pl.BlockSpec((RB, CB), lambda j, i: (i, j))
    col_spec = pl.BlockSpec((RB, N, 1), lambda j, i: (i, 0, 0))
    state_spec = pl.BlockSpec((N, CB), lambda j, i: (0, j))
    y, s1 = pl.pallas_call(
        _scan1_kernel, grid=(C // CB, Lp // RB),
        in_specs=[rows_spec, rows_spec, state_spec, col_spec, col_spec,
                  state_spec],
        out_specs=[rows_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((Lp, C), f32),
                   jax.ShapeDtypeStruct((N, C), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(dt, x, a.astype(f32), bm.reshape(Lp, N, 1), cm.reshape(Lp, N, 1),
      state.astype(f32).reshape(N, C))
    return y[:L], s1.reshape(state.shape)


# ---------------------------------------------------------------------------
# certification (paddlelint PK105)
# ---------------------------------------------------------------------------

from .oracles import register_oracle  # noqa: E402

register_oracle(
    "ssm_state_update", kernel=ssm_state_update,
    reference="paddle_tpu.ops.references:ssm_state_update_reference",
    parity_test="tests/test_pallas_ssm.py::TestStateUpdate")
register_oracle(
    "ssm1_state_update", kernel=ssm1_state_update,
    reference="paddle_tpu.ops.references:ssm1_state_update_reference",
    parity_test="tests/test_pallas_ssm.py::TestMamba1")
register_oracle(
    "ssm1_chunk_scan", kernel=ssm1_chunk_scan,
    reference="paddle_tpu.ops.references:ssm1_recurrence_reference",
    parity_test="tests/test_pallas_ssm.py::TestMamba1")
register_oracle(
    "ssm_state_put", kernel=ssm_state_put,
    reference="paddle_tpu.ops.references:ssm_state_put_reference",
    parity_test="tests/test_pallas_ssm.py::TestStatePut")
