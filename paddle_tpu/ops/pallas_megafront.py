"""Mega-kernel decode front-half: qkv projection -> rope -> paged K/V
append in ONE pallas_call (ISSUE 20 tentpole; ROADMAP item 1).

The unified ragged step's front half used to be five launches per layer
(norm kernel, three qkv projection dots, rope+append kernel), with the
[T, (Hq+2KV)*D]-class qkv activations round-tripping HBM between every
one.  Here everything after the norm collapses to a single launch:

  fused_qkv_rope_append   qkv projection (fp, int8 or packed-int4 with
                          the dequant fused into the VMEM load — the
                          exact `(qw&0xF^8)-8` nibble chain from
                          pallas_megadecode), rotary embedding on q and
                          k, and the paged-pool K/V row scatter through
                          the PR-7 aliased first-visit-seed idiom.  The
                          MLA layout rides the same launch: q (+rope on
                          its rope tail), the kv_a projection, the
                          latent rms norm and the [latent | rope-key]
                          row append — the absorbed kv_b einsums stay
                          outside (they contract against the attention
                          OUTPUT, not the hidden stream).

The front half is norm + fused (2 launches, down from 5) and the whole
decode layer body lands at <=5 with the ISSUE-14 back half.  The PR-18
retile seam (fused_rms_norm emits 8 token rows per grid step, this
consumer takes 1) is solved by construction: q rows are EMITTED at the
consumer's one-token granularity — out_spec [1, Hq, D] swept by t — so
the only remaining front seam is norm->fused itself, re-registered as a
PF404 'retile' candidate for the <=4-launch follow-on.

The qkv weight slabs ride as ONE concatenated [H, (Hq+2KV)*D] operand
(a caller concatenates per-out-channel payloads AND scales once at
deploy time — column-wise identical math, zero extra HBM; ServingEngine
does not call this kernel since ISSUE 30, ROADMAP D11 deletes it) with an
index_map referencing no grid dim: fetched once, VMEM-resident across
the token sweep.  fp weights ride a ones scale (f32 * 1.0 is the
identity) so the fp path stays bitwise-equal to the plain dots, and the
greedy token stream is exact vs the unfused chain for all four
families.

Static-analysis contract (paddlelint PK/PF/PE lanes): each of the three
pallas_call sites below is a literal grid/BlockSpec launch owned by one
function (`_qkv_rope_append_fwd`, `_qkv_rope_append_int4`,
`_mla_qkv_rope_append_fwd`) with a CANONICAL binding in
analysis/vmemmodel.py; the cost registry carries matching byte formulas
(PF406/PE506 exact); the aliased page pools keep the fused.py append
contract (adjacent same-page tokens, one row per grid step at the
per-step table offset — written through `fused._put_row`'s page-block
select, since Mosaic lowers no one-row store at a dynamic sublane
offset — and `arbitrary` grid semantics) so PE501-PE503 certify the
append exactly as they do the PR-7 kernel.  Inference-only: no VJPs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused import _put_row

__all__ = ["fused_qkv_rope_append", "megafront_eligible"]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


from .pallas_megadecode import _VMEM_LIMIT, _VMEM_SLACK


# ---------------------------------------------------------------------------
# fp / int8 site (llama, moe, gpt — gpt rides identity trig)
# ---------------------------------------------------------------------------

def _qkv_rope_append_kernel(pg_ref, off_ref,          # scalar prefetch
                            h_ref, w_ref, s_ref, b_ref, c_ref, sn_ref,
                            kin_ref, vin_ref,
                            qo_ref, kp_ref, vp_ref, *,
                            heads: int, kv_heads: int):
    t = pl.program_id(0)
    # fp weights ride with a ones scale (f32 * 1.0 is the identity, so
    # the fp path stays bitwise-equal to the plain dot); int8 weights
    # dequantize here exactly like quant._wol_kernel
    w = w_ref[:].astype(jnp.float32) * s_ref[0].astype(jnp.float32)[None, :]
    p = jnp.dot(h_ref[0].astype(jnp.float32), w,
                preferred_element_type=jnp.float32) \
        + b_ref[0].astype(jnp.float32)[None, :]        # [1, (Hq+2KV)*D]
    D = qo_ref.shape[-1]
    c = c_ref[0].astype(jnp.float32)                   # [1, D/2]
    sn = sn_ref[0].astype(jnp.float32)

    def rot(x):                                        # [h, D] f32
        d2 = x.shape[-1] // 2
        x1, x2 = x[:, :d2], x[:, d2:]
        return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)

    # first visit of a page seeds the resident output block from the
    # aliased input fetch; consecutive same-page tokens keep the block
    # resident, so their earlier row writes survive (re-seeding would
    # clobber them with the stale pre-launch page)
    prev = pg_ref[jnp.maximum(t - 1, 0)]

    @pl.when((t == 0) | (pg_ref[t] != prev))
    def _seed():
        kp_ref[:] = kin_ref[:]
        vp_ref[:] = vin_ref[:]

    # column split of the fused projection row — the in-VMEM retile
    # stage.  Each head is a static lane window of p (a [1, N] ->
    # [heads, D] reshape moves lanes into sublanes, which Mosaic does
    # not lower): q heads leave at the consumer's one-token
    # granularity, k/v heads land in their page rows
    off = off_ref[t]
    for i in range(heads):
        qo_ref[0, i:i + 1, :] = \
            rot(p[:, i * D:(i + 1) * D]).astype(qo_ref.dtype)
    for i in range(kv_heads):
        ko, vo = (heads + i) * D, (heads + kv_heads + i) * D
        kp_ref[i, 0] = _put_row(kp_ref[i, 0], off, rot(p[:, ko:ko + D]))
        vp_ref[i, 0] = _put_row(vp_ref[i, 0], off, p[:, vo:vo + D])


def _qkv_rope_append_fwd(h, w, s, b, cos, sin, k_pages, v_pages,
                         page_idx, page_off, heads, kv_heads):
    T, H = h.shape
    N = w.shape[-1]
    KV, total, psz, D = (k_pages.shape[0], k_pages.shape[1],
                         k_pages.shape[2], k_pages.shape[3])
    d2 = D // 2

    def page_map(t, pg, off):
        return (0, jnp.clip(pg[t], 0, total - 1), 0, 0)

    page_spec = pl.BlockSpec((KV, 1, psz, D), page_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # page_idx, page_off
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, 1, H), lambda t, pg, off: (t, 0, 0)),
            # weight/scale/bias index_maps reference no grid dim:
            # fetched ONCE, VMEM-resident across the token sweep
            pl.BlockSpec((H, N), lambda t, pg, off: (0, 0)),
            pl.BlockSpec((1, N), lambda t, pg, off: (0, 0)),
            pl.BlockSpec((1, N), lambda t, pg, off: (0, 0)),
            pl.BlockSpec((1, 1, d2), lambda t, pg, off: (t, 0, 0)),
            pl.BlockSpec((1, 1, d2), lambda t, pg, off: (t, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=[pl.BlockSpec((1, heads, D), lambda t, pg, off: (t, 0, 0)),
                   page_spec, page_spec],
    )
    return pl.pallas_call(
        functools.partial(_qkv_rope_append_kernel, heads=heads,
                          kv_heads=kv_heads),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, heads, D), h.dtype),
                   jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        # flat-input indices INCLUDE the scalar-prefetch operands
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(page_idx.astype(jnp.int32), page_off.astype(jnp.int32),
      h[:, None, :], w, s, b, cos[:, None, :], sin[:, None, :],
      k_pages, v_pages)


# ---------------------------------------------------------------------------
# packed-int4 site (llama/moe int4 deploys)
# ---------------------------------------------------------------------------

def _qkv_rope_append_int4_kernel(pg_ref, off_ref,     # scalar prefetch
                                 he_ref, ho_ref, qw_ref, s_ref, b_ref,
                                 cs_ref, kin_ref, vin_ref,
                                 qo_ref, kp_ref, vp_ref, *,
                                 heads: int, kv_heads: int):
    t = pl.program_id(0)
    # packed-int4 qkv: the HBM weight read stays packed; nibble planes
    # unpack in VMEM with the exact quant._wol4_kernel int32 bit chain
    # and the even/odd split contraction (caller pre-splits h)
    s = s_ref[0].astype(jnp.float32)[None, :]
    qw = qw_ref[:].astype(jnp.int32)
    lo = (((qw & 0xF) ^ 8) - 8).astype(jnp.float32) * s
    hi = (qw >> 4).astype(jnp.float32) * s
    p = (jnp.dot(he_ref[0].astype(jnp.float32), lo,
                 preferred_element_type=jnp.float32)
         + jnp.dot(ho_ref[0].astype(jnp.float32), hi,
                   preferred_element_type=jnp.float32)) \
        + b_ref[0].astype(jnp.float32)[None, :]
    D = qo_ref.shape[-1]
    # trig rides as one [1, D] (cos | sin) row here: the packed-int4
    # lane rule (PF403) requires every block lane be 1 or a
    # 128-multiple, which the D/2-wide trig halves would break
    cs = cs_ref[0].astype(jnp.float32)
    c, sn = cs[:, :D // 2], cs[:, D // 2:]

    def rot(x):                                        # [h, D] f32
        d2 = x.shape[-1] // 2
        x1, x2 = x[:, :d2], x[:, d2:]
        return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)

    prev = pg_ref[jnp.maximum(t - 1, 0)]

    @pl.when((t == 0) | (pg_ref[t] != prev))
    def _seed():
        kp_ref[:] = kin_ref[:]
        vp_ref[:] = vin_ref[:]

    # column split by static lane windows, as in the fp/int8 kernel
    off = off_ref[t]
    for i in range(heads):
        qo_ref[0, i:i + 1, :] = \
            rot(p[:, i * D:(i + 1) * D]).astype(qo_ref.dtype)
    for i in range(kv_heads):
        ko, vo = (heads + i) * D, (heads + kv_heads + i) * D
        kp_ref[i, 0] = _put_row(kp_ref[i, 0], off, rot(p[:, ko:ko + D]))
        vp_ref[i, 0] = _put_row(vp_ref[i, 0], off, p[:, vo:vo + D])


def _qkv_rope_append_int4(he, ho, qw, s, b, trig, k_pages, v_pages,
                          page_idx, page_off, heads, kv_heads):
    T, H2 = he.shape
    N = qw.shape[-1]
    KV, total, psz, D = (k_pages.shape[0], k_pages.shape[1],
                         k_pages.shape[2], k_pages.shape[3])

    def page_map(t, pg, off):
        return (0, jnp.clip(pg[t], 0, total - 1), 0, 0)

    page_spec = pl.BlockSpec((KV, 1, psz, D), page_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, 1, H2), lambda t, pg, off: (t, 0, 0)),
            pl.BlockSpec((1, 1, H2), lambda t, pg, off: (t, 0, 0)),
            pl.BlockSpec((H2, N), lambda t, pg, off: (0, 0)),
            pl.BlockSpec((1, N), lambda t, pg, off: (0, 0)),
            pl.BlockSpec((1, N), lambda t, pg, off: (0, 0)),
            pl.BlockSpec((1, 1, D), lambda t, pg, off: (t, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=[pl.BlockSpec((1, heads, D), lambda t, pg, off: (t, 0, 0)),
                   page_spec, page_spec],
    )
    return pl.pallas_call(
        functools.partial(_qkv_rope_append_int4_kernel, heads=heads,
                          kv_heads=kv_heads),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, heads, D), he.dtype),
                   jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(page_idx.astype(jnp.int32), page_off.astype(jnp.int32),
      he[:, None, :], ho[:, None, :], qw, s, b, trig[:, None, :],
      k_pages, v_pages)


# ---------------------------------------------------------------------------
# MLA site (absorbed-decode front: q + kv_a + latent norm + row append)
# ---------------------------------------------------------------------------

def _mla_qkv_rope_append_kernel(pg_ref, off_ref,      # scalar prefetch
                                h_ref, w_ref, s_ref, g_ref, c_ref,
                                sn_ref, pin_ref,
                                qo_ref, pp_ref, *,
                                heads: int, nope_dim: int,
                                lora_rank: int, eps: float):
    t = pl.program_id(0)
    w = w_ref[:].astype(jnp.float32) * s_ref[0].astype(jnp.float32)[None, :]
    p = jnp.dot(h_ref[0].astype(jnp.float32), w,
                preferred_element_type=jnp.float32)    # [1, Nq + r + dr]
    c = c_ref[0].astype(jnp.float32)                   # [1, dr/2]
    sn = sn_ref[0].astype(jnp.float32)

    def rot(x):                                        # [h, dr] f32
        d2 = x.shape[-1] // 2
        x1, x2 = x[:, :d2], x[:, d2:]
        return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)

    dh = qo_ref.shape[-1]                              # dn + dr
    nq = heads * dh
    # each head is a static lane window of the row (a [1, N] ->
    # [heads, dh] reshape moves lanes into sublanes, which Mosaic does
    # not lower)
    for i in range(heads):
        q = p[:, i * dh:(i + 1) * dh]
        q = jnp.concatenate([q[:, :nope_dim], rot(q[:, nope_dim:])], -1)
        qo_ref[0, i:i + 1, :] = q.astype(qo_ref.dtype)
    # latent rms norm — the _rms_kernel op order ((x * rsqrt) * w) so
    # the fused latent bitwise-matches the unfused fused_rms_norm row
    lat = p[:, nq:nq + lora_rank]                      # [1, r]
    var = jnp.mean(lat * lat, axis=-1, keepdims=True)
    lat = lat * jax.lax.rsqrt(var + eps) \
        * g_ref[0].astype(jnp.float32)[None, :]
    k_pe = rot(p[:, nq + lora_rank:])                  # [1, dr]
    row = jnp.concatenate([lat, k_pe], -1)             # [1, r + dr]
    prev = pg_ref[jnp.maximum(t - 1, 0)]

    @pl.when((t == 0) | (pg_ref[t] != prev))
    def _seed():
        pp_ref[:] = pin_ref[:]

    pp_ref[0, 0] = _put_row(pp_ref[0, 0], off_ref[t], row)


def _mla_qkv_rope_append_fwd(h, w, s, g, cos, sin, pool, page_idx,
                             page_off, heads, nope_dim, rope_dim,
                             lora_rank, eps):
    T, H = h.shape
    N = w.shape[-1]
    total, psz, Dc = pool.shape[1], pool.shape[2], pool.shape[3]
    dh = nope_dim + rope_dim
    dd2 = rope_dim // 2
    r = lora_rank

    def page_map(t, pg, off):
        return (0, jnp.clip(pg[t], 0, total - 1), 0, 0)

    page_spec = pl.BlockSpec((1, 1, psz, Dc), page_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, 1, H), lambda t, pg, off: (t, 0, 0)),
            pl.BlockSpec((H, N), lambda t, pg, off: (0, 0)),
            pl.BlockSpec((1, N), lambda t, pg, off: (0, 0)),
            pl.BlockSpec((1, r), lambda t, pg, off: (0, 0)),
            pl.BlockSpec((1, 1, dd2), lambda t, pg, off: (t, 0, 0)),
            pl.BlockSpec((1, 1, dd2), lambda t, pg, off: (t, 0, 0)),
            page_spec,
        ],
        out_specs=[pl.BlockSpec((1, heads, dh), lambda t, pg, off: (t, 0, 0)),
                   page_spec],
    )
    return pl.pallas_call(
        functools.partial(_mla_qkv_rope_append_kernel, heads=heads,
                          nope_dim=nope_dim, lora_rank=lora_rank,
                          eps=eps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, heads, dh), h.dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(page_idx.astype(jnp.int32), page_off.astype(jnp.int32),
      h[:, None, :], w, s, g, cos[:, None, :], sin[:, None, :], pool)


# ---------------------------------------------------------------------------
# public wrapper
# ---------------------------------------------------------------------------

def fused_qkv_rope_append(h, w, scale, bias, cos, sin, k_pages, v_pages,
                          page_idx, page_off, *, heads: int,
                          kv_heads: int = 0, head_dim: int = 0,
                          algo: Optional[str] = None,
                          norm_weight=None, eps: float = 1e-6,
                          nope_dim: int = 0, rope_dim: int = 0,
                          lora_rank: int = 0):
    """qkv projection -> rope -> paged K/V append, one launch.

    ``h`` [T, H] is the NORMED hidden stream (fused_rms_norm /
    fused_layer_norm output rows); ``w``/``scale`` the concatenated
    qkv projection slab in any deploy layout: fp [H, N] (``algo`` None,
    scale ignored), int8 [H, N] + per-out-channel f32 scale [N], or
    packed int4 [H/2, N] + scale [N] — column order [q | k | v] (the
    GPT fused-qkv weight is already this layout; for llama/moe the
    caller concatenates the per-projection slabs and scales, which is
    column-wise identical math).  ``bias`` [N]
    or None rides a zeros row so the launch arity stays fixed.

    Standard layout (``lora_rank`` 0): N = (heads + 2*kv_heads) *
    head_dim; cos/sin [T, head_dim/2] per-token trig rows (identity
    cos=1/sin=0 for the GPT family); k/v_pages
    [kv_heads, total_pages, page_size, head_dim].  Returns
    ``(q_roped [T, heads, head_dim], k_pages, v_pages)`` with the pools
    donated through input_output_aliases.

    MLA layout (``lora_rank`` r > 0): ``w`` concatenates the q
    projection [H, heads*(nope_dim+rope_dim)] and kv_a
    [H, r+rope_dim]; ``norm_weight`` is the kv_a_layernorm weight [r]
    applied to the latent INSIDE the launch; cos/sin [T, rope_dim/2];
    ``k_pages`` the single [1, total, page_size, r+rope_dim] latent
    pool (``v_pages`` must be None).  Returns ``(q [T, heads,
    nope_dim+rope_dim] with its rope tail rotated, pool)`` — the
    absorbed kv_b einsums stay outside.

    Adjacency contract: tokens sharing a page are adjacent in t;
    callers must use the RETURNED pools, never re-read the donated
    arguments."""
    T, H = h.shape
    if lora_rank:
        if v_pages is not None:
            raise ValueError("MLA layout uses one latent pool: pass it "
                             "as k_pages and leave v_pages None")
        N = w.shape[-1]
        s2 = jnp.ones((1, N), jnp.float32) if algo is None \
            else scale.reshape(1, N).astype(jnp.float32)
        g2 = norm_weight.reshape(1, lora_rank)
        return _mla_qkv_rope_append_fwd(
            h, w, s2, g2, cos, sin, k_pages, page_idx, page_off,
            heads, nope_dim, rope_dim, lora_rank, float(eps))
    N = (heads + 2 * kv_heads) * head_dim
    fb = jnp.zeros((1, N), h.dtype) if bias is None else bias.reshape(1, N)
    if algo == "weight_only_int4":
        s2 = scale.reshape(1, N).astype(jnp.float32)
        # even/odd input-row split OUTSIDE the kernel (the TPU layout
        # cannot stride sublanes in-kernel) — same as _wol_int4_fwd_impl
        hs = h.reshape(T, H // 2, 2)
        trig = jnp.concatenate([cos, sin], axis=-1)    # [T, head_dim]
        return _qkv_rope_append_int4(
            hs[:, :, 0], hs[:, :, 1], w, s2, fb, trig,
            k_pages, v_pages, page_idx, page_off, heads, kv_heads)
    if algo == "weight_only_int8":
        s2 = scale.reshape(1, N).astype(jnp.float32)
    else:
        s2 = jnp.ones((1, N), jnp.float32)
    return _qkv_rope_append_fwd(
        h, w, s2, fb, cos, sin, k_pages, v_pages, page_idx, page_off,
        heads, kv_heads)


# ---------------------------------------------------------------------------
# eligibility: whether the kernel tiles and its weight slab fits VMEM
# (no caller: ServingEngine runs the projections + fused_rope_append)
# ---------------------------------------------------------------------------

def megafront_eligible(hidden: int, out_cols: int, head_dim: int, *,
                       int4: bool = False,
                       dtype_bytes: int = 2) -> bool:
    """True when the fused front-half tiling is launchable: interpret
    mode always (blocks are virtual); on a real TPU the matmul lane
    dims must be 128-aligned, the packed-int4 layout needs an even
    contraction dim, and the resident qkv slab must fit the
    scoped-VMEM limit: the slab as stored (``dtype_bytes`` per fp/int8
    element; constant index_map — single-buffered) plus, for packed
    int4, the 4-byte plane Mosaic materializes while unpacking it.
    The per-token blocks (one hidden row, trig rows, two page blocks,
    the q row) ride in the slack.  Callers fall back to the split
    norm/dots/rope-append chain when this is False — same math, more
    HBM round-trips."""
    if _interpret():
        return True
    if hidden % 128 or out_cols % 128:
        return False
    if int4 and hidden % 2:
        return False
    if int4:
        need = (1 + 4) * (hidden // 2) * out_cols
    else:
        need = hidden * out_cols * dtype_bytes
    return need <= _VMEM_LIMIT - _VMEM_SLACK


# ---------------------------------------------------------------------------
# certification (ROADMAP item 5 / paddlelint PK105): every kernel entry
# names its XLA oracle and the parity test that pins them together
# ---------------------------------------------------------------------------

from .oracles import register_oracle  # noqa: E402  (registry is leaf-light)

register_oracle(
    "fused_qkv_rope_append", kernel=fused_qkv_rope_append,
    reference="paddle_tpu.ops.references:qkv_rope_append_reference",
    parity_test="tests/test_megafront.py::TestQkvRopeAppendParity")
