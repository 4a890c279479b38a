"""Fused elementwise/norm Pallas kernels: rms_norm, rope, swiglu.

Reference capability (SURVEY §2.1 fused kernels): RmsNormKernel,
FusedRopeKernel, swiglu (paddle/phi/kernels/fusion/gpu/,
python/paddle/incubate/nn/functional/). Here the device kernels are Pallas
TPU kernels (the accepted ".cu analog"); on non-TPU backends they run in
Pallas interpret mode for correctness tests, and each op carries a custom
VJP whose backward is plain XLA math (fused by the compiler).

Kernel design notes (pallas_guide.md):
- blocks keep the last dim = hidden (lane-dim multiple of 128 for real
  models) and tile rows in the sublane dim;
- rms_norm reduces in f32 on the VPU, one HBM round-trip per block;
- rope loads cos/sin once per block (broadcast over batch rows).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_rms_norm", "fused_rope", "swiglu", "fused_layer_norm",
           "fused_bias_residual_layer_norm", "fused_moe_dispatch_combine",
           "fused_rope_append", "fused_append_rows", "fused_chunk_pool",
           "append_tile", "append_run_table", "append_slot_run_table",
           "append_run_count"]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"



def _row_block(n_rows: int) -> int:
    for b in (256, 128, 64, 32, 16, 8):
        if n_rows % b == 0:
            return b
    return 1


# ---------------------------------------------------------------------------
# rms_norm
# ---------------------------------------------------------------------------

def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rms_forward(x2, w, eps):
    T, H = x2.shape
    bt = _row_block(T)
    return pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, H), lambda i: (i, 0)),
                  pl.BlockSpec((H,), lambda i: (0,))],
        out_specs=pl.BlockSpec((bt, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, H), x2.dtype),
        interpret=_interpret(),
    )(x2, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm(x2, w, eps):
    return _rms_forward(x2, w, eps)


def _rms_fwd(x2, w, eps):
    return _rms_forward(x2, w, eps), (x2, w)


def _rms_bwd(eps, res, g):
    x2, w = res
    x = x2.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    H = x.shape[-1]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    xhat = x * r
    dw = jnp.sum(gf * xhat, axis=0).astype(w.dtype)
    gw = gf * wf
    dx = r * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    return dx.astype(x2.dtype), dw


_rms_norm.defvjp(_rms_fwd, _rms_bwd)


def fused_rms_norm(x, weight, eps: float = 1e-6):
    """x [..., H] * rms-normalized, scaled by weight [H]."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = _rms_norm(x2, weight, float(eps))
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# layer_norm (fused bias+scale)
# ---------------------------------------------------------------------------

def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * w_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def fused_layer_norm(x, weight, bias, eps: float = 1e-5):
    shape = x.shape
    H = shape[-1]
    x2 = x.reshape(-1, H)
    T = x2.shape[0]
    bt = _row_block(T)
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=float(eps)),
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, H), lambda i: (i, 0)),
                  pl.BlockSpec((H,), lambda i: (0,)),
                  pl.BlockSpec((H,), lambda i: (0,))],
        out_specs=pl.BlockSpec((bt, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, H), x2.dtype),
        interpret=_interpret(),
    )(x2, weight, bias)
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# bias + residual + layer_norm (ref: FusedBiasDropoutResidualLnKernel,
# paddle/phi/kernels/fusion/gpu/fused_bias_dropout_residual_layer_norm*.
# Eval-mode form — dropout is identity; the whole add+add+LN chain runs
# in ONE kernel / one HBM round-trip instead of three.)
# ---------------------------------------------------------------------------

def _brln_kernel(x_ref, r_ref, b_ref, w_ref, lb_ref, o_ref, *, eps: float):
    h = (x_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
         + r_ref[:].astype(jnp.float32))
    mu = jnp.mean(h, axis=-1, keepdims=True)
    hc = h - mu
    var = jnp.mean(hc * hc, axis=-1, keepdims=True)
    y = hc * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * w_ref[:].astype(jnp.float32)
                + lb_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _brln_forward(x2, r2, b, w, lb, eps):
    T, H = x2.shape
    bt = _row_block(T)
    return pl.pallas_call(
        functools.partial(_brln_kernel, eps=float(eps)),
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, H), lambda i: (i, 0)),
                  pl.BlockSpec((bt, H), lambda i: (i, 0)),
                  pl.BlockSpec((H,), lambda i: (0,)),
                  pl.BlockSpec((H,), lambda i: (0,)),
                  pl.BlockSpec((H,), lambda i: (0,))],
        out_specs=pl.BlockSpec((bt, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, H), x2.dtype),
        interpret=_interpret(),
    )(x2, r2, b, w, lb)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _brln(x2, r2, b, w, lb, eps):
    return _brln_forward(x2, r2, b, w, lb, eps)


def _brln_fwd(x2, r2, b, w, lb, eps):
    return _brln_forward(x2, r2, b, w, lb, eps), (x2, r2, b, w, lb)


def _brln_bwd(eps, res, g):
    # standard layer-norm backward over h = x + b + r, in plain XLA math
    x2, r2, b, w, lb = res
    h = (x2.astype(jnp.float32) + b.astype(jnp.float32)
         + r2.astype(jnp.float32))
    gf = g.astype(jnp.float32)
    mu = jnp.mean(h, -1, keepdims=True)
    hc = h - mu
    var = jnp.mean(hc * hc, -1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = hc * rstd
    wf = w.astype(jnp.float32)
    dlb = jnp.sum(gf, axis=0).astype(lb.dtype)
    dw = jnp.sum(gf * xhat, axis=0).astype(w.dtype)
    gx = gf * wf
    dh = rstd * (gx - jnp.mean(gx, -1, keepdims=True)
                 - xhat * jnp.mean(gx * xhat, -1, keepdims=True))
    dx = dh.astype(x2.dtype)
    db = jnp.sum(dh, axis=0).astype(b.dtype)
    return dx, dh.astype(r2.dtype), db, dw, dlb


_brln.defvjp(_brln_fwd, _brln_bwd)


def fused_bias_residual_layer_norm(x, residual, bias=None, weight=None,
                                   ln_bias=None, eps: float = 1e-5):
    """layer_norm((x + bias) + residual) in one Pallas kernel (custom
    VJP: plain-XLA LN backward). bias / weight / ln_bias optional
    (zeros/ones substituted)."""
    shape = x.shape
    H = shape[-1]
    x2 = x.reshape(-1, H)
    r2 = residual.reshape(-1, H)
    b = jnp.zeros((H,), x2.dtype) if bias is None else bias
    w = jnp.ones((H,), x2.dtype) if weight is None else weight
    lb = jnp.zeros((H,), x2.dtype) if ln_bias is None else ln_bias
    return _brln(x2, r2, b, w, lb, float(eps)).reshape(shape)


# ---------------------------------------------------------------------------
# MoE dispatch/combine mask build (ref: CINN fusing the GShard gate's
# one-hot/scale/einsum chain — paddle/cinn/operator_fusion; the two
# [T,k,E]x[T,k,C] contractions plus the gate-value scale run in ONE
# kernel, reading keep/one-hot once instead of twice.)
# ---------------------------------------------------------------------------

def _moe_dc_kernel(keep_ref, oh_ref, gv_ref, d_ref, c_ref):
    keep = keep_ref[:].astype(jnp.float32)      # [bt, k, E]
    oh = oh_ref[:].astype(jnp.float32)          # [bt, k, C]
    gv = gv_ref[:].astype(jnp.float32)          # [bt, k]
    disp = jax.lax.dot_general(
        keep, oh, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)     # [bt, E, C]
    comb = jax.lax.dot_general(
        keep * gv[..., None], oh, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    d_ref[:] = disp.astype(d_ref.dtype)
    c_ref[:] = comb.astype(c_ref.dtype)


def _moe_dc_forward(keep, oh_loc, gv):
    T, K, E = keep.shape
    C = oh_loc.shape[-1]
    bt = _row_block(T)
    return pl.pallas_call(
        _moe_dc_kernel,
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, K, E), lambda i: (i, 0, 0)),
                  pl.BlockSpec((bt, K, C), lambda i: (i, 0, 0)),
                  pl.BlockSpec((bt, K), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bt, E, C), lambda i: (i, 0, 0)),
                   pl.BlockSpec((bt, E, C), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, E, C), keep.dtype),
                   jax.ShapeDtypeStruct((T, E, C), keep.dtype)],
        interpret=_interpret(),
    )(keep, oh_loc, gv)


@jax.custom_vjp
def fused_moe_dispatch_combine(keep, oh_loc, gv):
    """keep [T,k,E], oh_loc [T,k,C], gv [T,k] ->
    (dispatch [T,E,C], combine [T,E,C]) — the GShard gate's final
    einsum pair in one kernel (custom VJP: the pair is bilinear, the
    backward is three small einsums XLA fuses)."""
    return _moe_dc_forward(keep, oh_loc, gv)


def _moe_dc_fwd(keep, oh_loc, gv):
    return _moe_dc_forward(keep, oh_loc, gv), (keep, oh_loc, gv)


def _moe_dc_bwd(res, gs):
    keep, oh, gv = res
    dd, dc = gs
    ddf = dd.astype(jnp.float32)
    dcf = dc.astype(jnp.float32)
    kf = keep.astype(jnp.float32)
    of = oh.astype(jnp.float32)
    gf = gv.astype(jnp.float32)
    kg = kf * gf[..., None]
    dkeep = (jnp.einsum("tec,tkc->tke", ddf, of)
             + gf[..., None] * jnp.einsum("tec,tkc->tke", dcf, of))
    doh = (jnp.einsum("tec,tke->tkc", ddf, kf)
           + jnp.einsum("tec,tke->tkc", dcf, kg))
    dgv = jnp.einsum("tke,tkc,tec->tk", kf, of, dcf)
    return (dkeep.astype(keep.dtype), doh.astype(oh.dtype),
            dgv.astype(gv.dtype))


fused_moe_dispatch_combine.defvjp(_moe_dc_fwd, _moe_dc_bwd)


# ---------------------------------------------------------------------------
# rope
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _rope(x, cos, sin):
    return _rope_forward(x, cos, sin)


def _rope_pallas_kernel(x_ref, c_ref, s_ref, o_ref):
    # block: [1, bs, H, D] — rotate half (Llama convention)
    x = x_ref[:].astype(jnp.float32)
    c = c_ref[:].astype(jnp.float32)   # [1, bs, 1, D/2]
    s = s_ref[:].astype(jnp.float32)
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    o = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    o_ref[:] = o.astype(o_ref.dtype)


def _rope_forward(x, cos, sin):
    """x [B, S, H, D]; cos/sin [S, D/2]."""
    B, S, H, D = x.shape
    bs = _row_block(S)
    c4 = cos[None, :, None, :]
    s4 = sin[None, :, None, :]
    return pl.pallas_call(
        _rope_pallas_kernel,
        grid=(B, S // bs),
        in_specs=[pl.BlockSpec((1, bs, H, D), lambda b, i: (b, i, 0, 0)),
                  pl.BlockSpec((1, bs, 1, D // 2), lambda b, i: (0, i, 0, 0)),
                  pl.BlockSpec((1, bs, 1, D // 2), lambda b, i: (0, i, 0, 0))],
        out_specs=pl.BlockSpec((1, bs, H, D), lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, D), x.dtype),
        interpret=_interpret(),
    )(x, c4, s4)


def _rope_fwd(x, cos, sin):
    return _rope_forward(x, cos, sin), (cos, sin)


def _rope_bwd(res, g):
    cos, sin = res
    # inverse rotation = rotation by -theta; cos/sin are non-diff buffers
    d2 = g.shape[-1] // 2
    g1, g2 = g[..., :d2].astype(jnp.float32), g[..., d2:].astype(jnp.float32)
    c = cos[None, :g.shape[1], None, :]
    s = sin[None, :g.shape[1], None, :]
    dx = jnp.concatenate([g1 * c + g2 * s, -g1 * s + g2 * c], axis=-1)
    return dx.astype(g.dtype), None, None


_rope.defvjp(_rope_fwd, _rope_bwd)


def fused_rope(q, k, cos, sin):
    """Fused rotary embedding on q [B,S,Hq,D] and k [B,S,Hk,D]
    (ref: fused_rotary_position_embedding)."""
    return _rope(q, cos, sin), _rope(k, cos, sin)


# ---------------------------------------------------------------------------
# rope + paged-cache append (serving decode path; no VJP — inference only)
# ---------------------------------------------------------------------------

def _put_row(page, off, row):
    """``page`` [psz, D] with row ``off`` replaced by ``row`` [1, D].
    Mosaic cannot lower a one-row store at a dynamic sublane offset it
    cannot prove tile-aligned, so the append lands through a full-block
    select (psz*D elements — one or two vregs per KV head); the f32
    round trip is exact for every pool dtype and keeps the select off
    packed-bf16 mask layouts."""
    hit = jax.lax.broadcasted_iota(jnp.int32, page.shape, 0) == off
    return jnp.where(hit, row.astype(jnp.float32),
                     page.astype(jnp.float32)).astype(page.dtype)


def append_tile(dtype, page_size: int) -> int:
    """Rows of the cache tile a run of `fused_rope_append` fills: one
    sublane tile of the pools' dtype (16 rows of bfloat16, 8 of
    float32), or the whole page where a page is not whole tiles (the
    tier-1 engines' pages of 8 under bfloat16)."""
    rows = 32 // jnp.dtype(dtype).itemsize
    return rows if page_size % rows == 0 else page_size


def _run_starts(xp, live, first, page_idx, page_off, tile: int):
    """[T] bool over `xp` (numpy on the host, jnp inside the step): the
    rows that open a run — a live row that is its sequence's first or
    whose (page, tile of the page) differs from the row before."""
    part = page_off // tile
    moved = xp.concatenate([
        xp.ones(1, bool), (page_idx[1:] != page_idx[:-1])
        | (part[1:] != part[:-1])])
    return live & (first | moved)


def append_run_count(live, first, page_idx, page_off, tile: int) -> int:
    """The host's count of the runs `append_run_table` makes of the same
    row tables (numpy; `live` / `first` [T] bool: the rows a sequence
    owns, and each sequence's first): the step record's `append_runs`."""
    return int(_run_starts(np, live, first, page_idx, page_off,
                           tile).sum())


def append_run_table(seq_start, num_tokens, page_idx, page_off, *,
                     tile: int, max_runs: int):
    """The work list of `fused_rope_append` and `fused_append_rows`, on
    the device: [5 * G] int32 (G = max_runs), five columns of G laid
    end to end — a run's first row of the flat buffer, its rows, its
    page, the tile of that page, the first row's offset inside the
    tile. A RUN is the consecutive live rows that land in one tile of
    one cache page (`_run_starts`); idle rows make none. Runs past the
    last live one have no rows and name the last live run's tile (the
    trash page's first when nothing is live), so the kernel's block
    does not move. `max_runs` bounds the runs the row tables can make
    (the engine: every decode row its own, a chunk of C rows
    ceil(C / tile) + 1); compare-and-sum over [G, T], no scatter."""
    T = page_idx.shape[0]
    i32 = jnp.int32
    page_idx, page_off = page_idx.astype(i32), page_off.astype(i32)
    row = jnp.arange(T, dtype=i32)[:, None]
    owned = (row >= seq_start) & (row < seq_start + num_tokens)
    live = owned.any(-1)
    return _run_table(live, (owned & (row == seq_start)).any(-1),
                      page_idx, page_off, tile, max_runs)


def append_slot_run_table(page_idx, page_off, *, tile: int, max_runs: int):
    """`append_run_table` of rows that belong to no sequence's span of
    the flat buffer (the chunk-summary engine's pooling slots): a row
    is live where its page is not the trash page 0, and a run opens
    wherever the (page, tile) moves — no two sequences write one
    page."""
    i32 = jnp.int32
    page_idx, page_off = page_idx.astype(i32), page_off.astype(i32)
    return _run_table(page_idx > 0, False, page_idx, page_off, tile,
                      max_runs)


def _run_table(live, first, page_idx, page_off, tile: int, G: int):
    T = page_idx.shape[0]
    i32 = jnp.int32
    start = _run_starts(jnp, live, first, page_idx, page_off, tile)
    run_of = jnp.cumsum(start.astype(i32)) - 1          # [T]
    g = jnp.arange(G, dtype=i32)[:, None]
    mine = run_of[None] == g                            # [G, T]
    opens = mine & start[None]

    def at_first(x):                                    # [T] -> [G]
        return jnp.sum(jnp.where(opens, x[None], 0), -1, dtype=i32)

    rows = jnp.sum(mine & live[None], -1, dtype=i32)
    last = jnp.maximum(jnp.sum(start, dtype=i32) - 1, 0)
    keep = jnp.minimum(g[:, 0], last)                   # padded -> last
    return jnp.concatenate([
        at_first(jnp.arange(T, dtype=i32)), rows,
        at_first(page_idx)[keep], at_first(page_off // tile)[keep],
        at_first(page_off % tile)])


def _append_runs_kernel(runs_ref,                     # scalar prefetch
                        k_ref, v_ref, kin_ref, vin_ref, kp_ref, vp_ref,
                        *, G: int):
    g = pl.program_id(0)
    n = runs_ref[G + g]

    # every live run is a tile of its own: read it, put the run's rows,
    # write it back. A run without rows repeats the last live run's
    # block, which stays resident and is not touched; only where nothing
    # is live does step 0 carry its (trash) tile through unchanged
    @pl.when((n > 0) | (g == 0))
    def _run():
        kp_ref[:] = kin_ref[:]
        vp_ref[:] = vin_ref[:]
        first = runs_ref[g]
        base = runs_ref[4 * G + g]

        def put(j, carry):
            kr = k_ref[first + j].astype(jnp.float32)  # [KV, D]
            v = v_ref[first + j]
            off = base + j
            for h in range(kr.shape[0]):
                kp_ref[h, 0] = _put_row(kp_ref[h, 0], off, kr[h:h + 1])
                vp_ref[h, 0] = _put_row(vp_ref[h, 0], off, v[h:h + 1])
            return carry

        jax.lax.fori_loop(0, n, put, 0)


def fused_rope_append(q, k, v, cos, sin, k_pages, v_pages, runs):
    """Rotary embedding (per-TOKEN cos/sin rows) on q and k plus the
    paged-cache K/V append of the LIVE rows — the serving engine's rope
    + append step. The rope is dense row-block work over all T rows
    (`_rope_forward`, float32 inside); the append works by RUNS, the
    consecutive rows that land in one sublane tile of one cache page
    (`append_run_table`): grid (G,), each step holding one
    (KV, 1, tile, D) block of K and of V.

    q [T, Hq, D]; k/v [T, KV, D]; cos/sin [T, D/2]; k/v_pages
    [KV, total_pages, page_size, D]; runs [5 * G] int32 from
    `append_run_table` over the same row tables, with `append_tile` of
    these pools. Returns (q_roped, k_pages, v_pages) with the page
    pools donated through input_output_aliases (the HBM buffers update
    in place on TPU — callers must use the RETURNED pools, never
    re-read the donated arguments; paddlelint's PF402 checks the caller
    side statically, and PE502 proves the kernel itself only reads each
    donated input before its first aliased write, so no defensive copy
    is ever needed here). The contract reaches the serving engine's own
    handle: `ServingEngine` builds its jitted programs with the pools
    donated, so XLA hands this kernel the live buffers and not a copy
    of them — the engine's pools are dead after the launch, and
    `ServingEngine._launch` rebinds them to the returned ones in the
    same statement.

    Idle rows write nothing: a tile no run names — the trash page's
    too — comes back bit for bit. Contract: no two runs name one tile
    (a sequence's rows are consecutive positions, and no two sequences
    write one page). Identity rope (cos=1, sin=0) turns this into a
    pure append for the GPT family."""
    q = _rope_forward(q[None], cos, sin)[0]
    k = _rope_forward(k[None], cos, sin)[0]
    kp, vp = _append_kv_runs(k, v, k_pages, v_pages, runs)
    return q, kp, vp


def _append_kv_runs(k, v, k_pages, v_pages, runs):
    """The K / V rows [T, KV, D] of a launch into their pools by the
    run table: `fused_rope_append`'s append, and `fused_append_rows`'s
    of a pair."""
    T, KV, D = k.shape
    total, psz = k_pages.shape[1], k_pages.shape[2]
    tile = append_tile(k_pages.dtype, psz)
    G = runs.shape[0] // 5

    def row_map(g, runs):
        return (0, 0, 0)

    def tile_map(g, runs):
        return (0, jnp.clip(runs[2 * G + g], 0, total - 1),
                jnp.clip(runs[3 * G + g], 0, psz // tile - 1), 0)

    # the roped K rows and the V rows of the whole launch stay resident
    # (fetched once); a run reads its rows at a dynamic LEADING index
    row_spec = pl.BlockSpec((T, KV, D), row_map)
    tile_spec = pl.BlockSpec((KV, 1, tile, D), tile_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                 # the run table
        grid=(G,),
        in_specs=[row_spec, row_spec, tile_spec, tile_spec],
        out_specs=[tile_spec, tile_spec],
    )
    return pl.pallas_call(
        functools.partial(_append_runs_kernel, G=G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        # flat-input indices INCLUDE the scalar-prefetch operand
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(runs.astype(jnp.int32), k, v, k_pages, v_pages)


def _append_row_runs_kernel(runs_ref, r_ref, pin_ref, po_ref, *,
                            G: int, KV: int):
    # `_append_runs_kernel` for ONE pool, its rows laid [T * KV, D]
    g = pl.program_id(0)
    n = runs_ref[G + g]

    @pl.when((n > 0) | (g == 0))
    def _run():
        po_ref[:] = pin_ref[:]
        first = runs_ref[g]
        base = runs_ref[4 * G + g]

        def put(j, carry):
            at = pl.multiple_of((first + j) * KV, KV)
            r = r_ref[pl.ds(at, KV), :]                # [KV, D]
            for h in range(KV):
                po_ref[h, 0] = _put_row(po_ref[h, 0], base + j, r[h:h + 1])
            return carry

        jax.lax.fori_loop(0, n, put, 0)


def fused_append_rows(pages, rows, runs, *, scope: Optional[str] = None,
                      _launch: bool = False):
    """Cache rows [T, KV, D] into a paged pool [KV, total_pages,
    page_size, D] by RUNS (`append_run_table` / `append_slot_run_table`
    over the rows' tables, with `append_tile` of the pool): grid (G,),
    each step holding one (KV, 1, tile, D) block — the latent engines'
    row append (their rope runs on split q_pe / k_pe shapes before the
    rows are concatenated). `pages` and `rows` may be PAIRS, the K and
    V pools and their rows (the chunk-summary engine's pooled rows):
    one walk of the table fills both, through `fused_rope_append`'s own
    append, and a pair comes back.

    The pools are donated as `fused_rope_append`'s are, and its
    contract holds: a tile no run names — the trash page's too — comes
    back bit for bit, and no two runs name one tile. `scope` (static)
    names the launch in the compiled program as
    `ragged_paged_attention`'s does: the launch is traced and lowered
    ONCE for equal shapes inside a jitted copy of this function, which
    a step's layers then share, and the instruction takes the innermost
    name."""
    if not _launch:
        return _append_rows_jit(pages, rows, runs, scope=scope)
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        if isinstance(pages, (tuple, list)):
            return _append_kv_runs(*rows, *pages, runs)
        T, KV, D = rows.shape
        total, psz = pages.shape[1], pages.shape[2]
        tile = append_tile(pages.dtype, psz)
        G = runs.shape[0] // 5
        # the launch's rows stay resident as [T * KV, D] (a [T, 1, D]
        # block would pad every row to a whole sublane tile): a run
        # reads a row's KV sublanes at a dynamic offset, which Mosaic
        # takes for a packed type only on whole tiles — fewer heads
        # than that (the latent row's one) ride as float32, which
        # `_put_row` makes of them anyway
        if KV % (32 // rows.dtype.itemsize):
            rows = rows.astype(jnp.float32)
        rows = rows.reshape(T * KV, D)

        def tile_map(g, runs):
            return (0, jnp.clip(runs[2 * G + g], 0, total - 1),
                    jnp.clip(runs[3 * G + g], 0, psz // tile - 1), 0)

        tile_spec = pl.BlockSpec((KV, 1, tile, D), tile_map)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                 # the run table
            grid=(G,),
            in_specs=[pl.BlockSpec((T * KV, D), lambda g, runs: (0, 0)),
                      tile_spec],
            out_specs=tile_spec,
        )
        return pl.pallas_call(
            functools.partial(_append_row_runs_kernel, G=G, KV=KV),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
            # flat-input indices INCLUDE the scalar-prefetch operand
            input_output_aliases={2: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=_interpret(),
        )(runs.astype(jnp.int32), rows, pages)


@functools.partial(jax.jit, static_argnames=("scope",))
def _append_rows_jit(pages, rows, runs, *, scope):
    return fused_append_rows(pages, rows, runs, scope=scope, _launch=True)


# ---------------------------------------------------------------------------
# chunk pooling (chunk-summary attention's cache rows; inference only)
# ---------------------------------------------------------------------------

def _chunk_pool_kernel(pg_ref, ck_ref, k_ref, v_ref, phi_ref, mu_ref,
                       ko_ref, vo_ref, *, scale: float):
    k = k_ref[:, 0].astype(jnp.float32)                # [KV, c, D]
    v = v_ref[:, 0].astype(jnp.float32)
    # every reduction keeps its axis: the weights stay [KV, c, 1], one a
    # sublane, and nothing moves between lanes and sublanes
    sc = jnp.sum(k * phi_ref[:], -1, keepdims=True) * scale
    e = jnp.exp(sc - jnp.max(sc, 1, keepdims=True))
    a = e / jnp.sum(e, 1, keepdims=True)
    ko_ref[0] = (jnp.sum(a * k, 1) + mu_ref[:, 0]).astype(ko_ref.dtype)
    vo_ref[0] = jnp.sum(a * v, 1).astype(vo_ref.dtype)


def fused_chunk_pool(k_pages, v_pages, phi, mu, page_idx, chunk_idx, *,
                     chunk: int, scale: float):
    """One pooled K and V row for each of P closed chunks of `chunk`
    cached tokens (EVA, arXiv:2302.04542): slot p reads rows
    [chunk_idx[p] * chunk, + chunk) of page page_idx[p] of every head,
    weights them by a = softmax_m(scale * phi_h . k_m) and returns
    (sum a_m k_m + mu_h, sum a_m v_m), each [P, KV, D] in the pools'
    type (float32 inside). k/v_pages [KV, total_pages, page_size, D];
    phi, mu [KV, D]; page_idx, chunk_idx [P] int32. A chunk never
    straddles a page (page_size % chunk == 0). On a TPU `chunk` is whole
    sublane tiles of the pools' type (16 rows of bfloat16). The pools
    are only read: the rows are written by `fused_append_rows`."""
    KV, total, psz, D = k_pages.shape
    P = page_idx.shape[0]
    if psz % chunk:
        raise ValueError(f"page_size {psz} is not whole chunks of {chunk}")

    def src_map(p, pg, ck):
        return (0, jnp.clip(pg[p], 0, total - 1),
                jnp.clip(ck[p], 0, psz // chunk - 1), 0)

    src = pl.BlockSpec((KV, 1, chunk, D), src_map)
    vec = pl.BlockSpec((KV, 1, D), lambda p, pg, ck: (0, 0, 0))
    row = pl.BlockSpec((1, KV, D), lambda p, pg, ck: (p, 0, 0))
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_chunk_pool_kernel, scale=float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(P,),
            in_specs=[src, src, vec, vec], out_specs=[row, row]),
        out_shape=[jax.ShapeDtypeStruct((P, KV, D), k_pages.dtype),
                   jax.ShapeDtypeStruct((P, KV, D), v_pages.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(page_idx.astype(jnp.int32), chunk_idx.astype(jnp.int32),
      k_pages, v_pages, phi.astype(f32)[:, None], mu.astype(f32)[:, None])


# ---------------------------------------------------------------------------
# swiglu
# ---------------------------------------------------------------------------

def _swiglu_kernel(g_ref, u_ref, o_ref):
    g = g_ref[:].astype(jnp.float32)
    u = u_ref[:].astype(jnp.float32)
    o_ref[:] = (g * jax.lax.logistic(g) * u).astype(o_ref.dtype)


@jax.custom_vjp
def _swiglu(g2, u2):
    return _swiglu_forward(g2, u2)


def _swiglu_forward(g2, u2):
    T, H = g2.shape
    bt = _row_block(T)
    return pl.pallas_call(
        _swiglu_kernel,
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, H), lambda i: (i, 0)),
                  pl.BlockSpec((bt, H), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bt, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, H), g2.dtype),
        interpret=_interpret(),
    )(g2, u2)


def _swiglu_fwd(g2, u2):
    return _swiglu_forward(g2, u2), (g2, u2)


def _swiglu_bwd(res, d):
    g, u = res
    gf = g.astype(jnp.float32)
    uf = u.astype(jnp.float32)
    df = d.astype(jnp.float32)
    sig = jax.lax.logistic(gf)
    silu = gf * sig
    dsilu = sig * (1 + gf * (1 - sig))
    return ((df * uf * dsilu).astype(g.dtype),
            (df * silu).astype(u.dtype))


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def swiglu(gate, up=None):
    """silu(gate) * up (ref: paddle.incubate.nn.functional.swiglu; when `up`
    is None the last dim of `gate` is split in half)."""
    if up is None:
        d = gate.shape[-1] // 2
        gate, up = gate[..., :d], gate[..., d:]
    shape = gate.shape
    g2 = gate.reshape(-1, shape[-1])
    u2 = up.reshape(-1, shape[-1])
    return _swiglu(g2, u2).reshape(shape)


# ---------------------------------------------------------------------------
# certification (ROADMAP item 5 / paddlelint PK105): every kernel entry
# names its XLA oracle and the parity test that pins them together
# ---------------------------------------------------------------------------

from .oracles import register_oracle  # noqa: E402  (registry is leaf-light)

register_oracle(
    "fused_rms_norm", kernel=fused_rms_norm,
    reference="paddle_tpu.ops.references:rms_norm_reference",
    parity_test="tests/test_fused_ops.py::TestRmsNorm")
register_oracle(
    "fused_layer_norm", kernel=fused_layer_norm,
    reference="paddle_tpu.ops.references:layer_norm_reference",
    parity_test="tests/test_fused_ops.py::TestLayerNorm")
register_oracle(
    "fused_bias_residual_layer_norm", kernel=fused_bias_residual_layer_norm,
    reference="paddle_tpu.ops.references:bias_residual_layer_norm_reference",
    parity_test="tests/test_oracles.py::TestOracleParity")
register_oracle(
    "fused_moe_dispatch_combine", kernel=fused_moe_dispatch_combine,
    reference="paddle_tpu.ops.references:moe_dispatch_combine_reference",
    parity_test="tests/test_oracles.py::TestOracleParity")
register_oracle(
    "fused_rope", kernel=fused_rope,
    reference="paddle_tpu.ops.references:rope_reference",
    parity_test="tests/test_fused_ops.py::TestRope")
register_oracle(
    "fused_rope_append", kernel=fused_rope_append,
    reference="paddle_tpu.ops.references:rope_append_reference",
    parity_test="tests/test_ragged_kernel.py::TestFusedRopeAppend")
register_oracle(
    "fused_append_rows", kernel=fused_append_rows,
    reference="paddle_tpu.ops.references:append_rows_reference",
    parity_test="tests/test_oracles.py::TestOracleParity")
register_oracle(
    "fused_chunk_pool", kernel=fused_chunk_pool,
    reference="paddle_tpu.ops.references:chunk_pool_reference",
    parity_test="tests/test_evabyte_serving.py::TestChunkPool")
register_oracle(
    "swiglu", kernel=swiglu,
    reference="paddle_tpu.ops.references:swiglu_reference",
    parity_test="tests/test_fused_ops.py::TestSwiglu")
