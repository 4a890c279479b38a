"""Mega-kernel decode back-half: o-proj -> residual -> norm -> FFN in
at most TWO pallas_calls (ISSUE 14 tentpole; ROADMAP item 1).

The unified ragged step's layer body used to round-trip the attention
output through HBM between six launches (o-proj dot, residual add,
norm kernel, gate/up dots, activation kernel, down dot).  Here the
back half collapses to:

  kernel 1  fused_oproj_norm   o-proj + bias + residual add + rms/layer
                               norm — emits BOTH the new residual stream
                               and the normed FFN input, so the
                               attention output never re-crosses HBM;
  kernel 2  fused_ffn          gate/up matmul + activation (swiglu or
                               approximate gelu) + down-proj + residual
                               add — the activation lives only in VMEM
                               scratch.

Both kernels accumulate in f32 VMEM scratch and read fp, int8 or
packed-int4 weights with the dequant fused into the VMEM load — the
exact `_wol_kernel` / `_wol4_kernel` math from ops/quant.py, so the
fused path is bitwise-equal to the solo `_mm_w` chain on the greedy
token stream.  Two kernels, not one, on purpose: at the real family
shapes (H=4096, I=14336 even 8-way sharded) the o-proj slab plus all
three FFN slabs cannot be VMEM-co-resident, so the split keeps each
launch's weight set inside the 16 MiB budget while still eliding the
four intermediate activation round-trips (PF404's oproj->ffn "aligned"
advisory records the residual seam — it is the deliberate cut point,
not an oversight).

Static-analysis contract (paddlelint PK/PF lanes): each of the four
pallas_call sites below is a literal grid/BlockSpec launch owned by one
function (`_oproj_norm_forward`, `_oproj_norm_int4`, `_ffn_forward`,
`_ffn_int4`) with a CANONICAL binding in analysis/vmemmodel.py; the
cost registry carries matching byte formulas (PF406 exact).
Inference-only: no VJPs (the decode engine never differentiates).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_oproj_norm", "fused_ffn", "megadecode_eligible"]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


#: Mosaic's default scoped-VMEM limit on a v4/v5 TensorCore.  The
#: kernels here set no ``vmem_limit_bytes``, so this is what the
#: compiler holds one launch to; the eligibility check models each
#: kernel's resident bytes against it (tests/test_tpu_aot_compile.py
#: holds the model to the compiler's own answer).
_VMEM_LIMIT = 16 * 1024 * 1024
#: compiler-internal scratch plus the scale / bias / norm rows
_VMEM_SLACK = 1024 * 1024


def _row_block(n_rows: int) -> int:
    for b in (256, 128, 64, 32, 16, 8):
        if n_rows % b == 0:
            return b
    return 1


def _norm_f32(xn, nw, nb, eps: float = 1e-6, norm: str = "rms"):
    """rms (llama/moe/mla) or layer (gpt) norm of the f32 accumulator —
    same op order as _rms_kernel / _ln_kernel in ops/fused.py (ulp-level
    parity with the unfused chain)."""
    if norm == "rms":
        var = jnp.mean(xn * xn, axis=-1, keepdims=True)
        y = xn * jax.lax.rsqrt(var + eps)
    else:
        mu = jnp.mean(xn, axis=-1, keepdims=True)
        xc = xn - mu
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        y = xc * jax.lax.rsqrt(var + eps)
    return y * nw + nb


# ---------------------------------------------------------------------------
# kernel 1: o-proj + residual + norm
# ---------------------------------------------------------------------------

def _oproj_norm_kernel(o_ref, x_ref, w_ref, s_ref, b_ref, nw_ref, nb_ref,
                       xo_ref, h_ref, acc_ref, *, eps: float = 1e-6,
                       norm: str = "rms"):
    # fp weights ride with a ones scale (f32 * 1.0 is the identity, so
    # the fp path stays bitwise-equal to the plain dot); int8 weights
    # dequantize here exactly like quant._wol_kernel
    w = w_ref[:].astype(jnp.float32) * s_ref[0].astype(jnp.float32)[None, :]
    p = jnp.dot(o_ref[:].astype(jnp.float32), w,
                preferred_element_type=jnp.float32)
    p = p + b_ref[0].astype(jnp.float32)[None, :]
    # f32 residual accumulation in VMEM scratch (never stored narrow)
    acc_ref[:] = x_ref[:].astype(jnp.float32) + p
    xn = acc_ref[:]
    h = _norm_f32(xn, nw_ref[0].astype(jnp.float32)[None, :],
                  nb_ref[0].astype(jnp.float32)[None, :], eps, norm)
    xo_ref[:] = xn.astype(xo_ref.dtype)
    h_ref[:] = h.astype(h_ref.dtype)


def _oproj_norm_forward(o2, x2, w, s, b, nw, nb, eps, norm):
    T, H = x2.shape
    Ko = o2.shape[1]
    bt = _row_block(T)
    return pl.pallas_call(
        functools.partial(_oproj_norm_kernel, eps=eps, norm=norm),
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, Ko), lambda i: (i, 0)),
                  pl.BlockSpec((bt, H), lambda i: (i, 0)),
                  # weight/scale/bias index_maps reference no grid dim:
                  # fetched ONCE, VMEM-resident across the token sweep
                  pl.BlockSpec((Ko, H), lambda i: (0, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bt, H), lambda i: (i, 0)),
                   pl.BlockSpec((bt, H), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, H), x2.dtype),
                   jax.ShapeDtypeStruct((T, H), x2.dtype)],
        scratch_shapes=[pltpu.VMEM((bt, H), jnp.float32)],
        interpret=_interpret(),
    )(o2, x2, w, s, b, nw, nb)


def _oproj_norm_int4_kernel(oe_ref, oo_ref, x_ref, qw_ref, s_ref, b_ref,
                            nw_ref, nb_ref, xo_ref, h_ref, acc_ref, *,
                            eps: float = 1e-6, norm: str = "rms"):
    # packed-int4 o-proj: the HBM weight read stays packed; nibble
    # planes unpack in VMEM with the exact quant._wol4_kernel int32 bit
    # chain and the even/odd split contraction (caller pre-splits o)
    s = s_ref[0].astype(jnp.float32)[None, :]
    qw = qw_ref[:].astype(jnp.int32)
    lo = (((qw & 0xF) ^ 8) - 8).astype(jnp.float32) * s
    hi = (qw >> 4).astype(jnp.float32) * s
    p = (jnp.dot(oe_ref[:].astype(jnp.float32), lo,
                 preferred_element_type=jnp.float32)
         + jnp.dot(oo_ref[:].astype(jnp.float32), hi,
                   preferred_element_type=jnp.float32))
    p = p + b_ref[0].astype(jnp.float32)[None, :]
    acc_ref[:] = x_ref[:].astype(jnp.float32) + p
    xn = acc_ref[:]
    h = _norm_f32(xn, nw_ref[0].astype(jnp.float32)[None, :],
                  nb_ref[0].astype(jnp.float32)[None, :], eps, norm)
    xo_ref[:] = xn.astype(xo_ref.dtype)
    h_ref[:] = h.astype(h_ref.dtype)


def _oproj_norm_int4(oe, oo, x2, qw, s, b, nw, nb, eps, norm):
    T, H = x2.shape
    Ko2 = oe.shape[1]
    bt = _row_block(T)
    return pl.pallas_call(
        functools.partial(_oproj_norm_int4_kernel, eps=eps, norm=norm),
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, Ko2), lambda i: (i, 0)),
                  pl.BlockSpec((bt, Ko2), lambda i: (i, 0)),
                  pl.BlockSpec((bt, H), lambda i: (i, 0)),
                  pl.BlockSpec((Ko2, H), lambda i: (0, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bt, H), lambda i: (i, 0)),
                   pl.BlockSpec((bt, H), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, H), x2.dtype),
                   jax.ShapeDtypeStruct((T, H), x2.dtype)],
        scratch_shapes=[pltpu.VMEM((bt, H), jnp.float32)],
        interpret=_interpret(),
    )(oe, oo, x2, qw, s, b, nw, nb)


def fused_oproj_norm(o, x, w, scale=None, bias=None, norm_weight=None,
                     norm_bias=None, *, eps: float = 1e-6,
                     norm: str = "rms",
                     algo: Optional[str] = None):
    """o-proj -> (+bias) -> residual add -> rms/layer norm, one launch.

    ``o`` [..., Ko] is the attention output, ``x`` [..., H] the residual
    stream.  ``w``/``scale`` name the o-proj weight in any deploy
    layout: fp [Ko, H] (``algo`` None, scale ignored), int8 [Ko, H] +
    per-channel f32 scale [H] (``algo`` 'weight_only_int8'), or packed
    int4 [Ko/2, H] + scale [H] (``algo`` 'weight_only_int4'; Ko even).
    Returns ``(x_new, h)``: the post-residual stream and its normed copy
    — the FFN input — both [..., H], computed from ONE f32 VMEM
    accumulator so the attention output never round-trips HBM between
    the projection and the norm."""
    shape = x.shape
    H = shape[-1]
    x2 = x.reshape(-1, H)
    o2 = o.reshape(x2.shape[0], -1)
    T = x2.shape[0]
    fb = jnp.zeros((1, H), x2.dtype) if bias is None \
        else bias.reshape(1, H)
    nw = jnp.ones((1, H), x2.dtype) if norm_weight is None \
        else norm_weight.reshape(1, H)
    nb = jnp.zeros((1, H), x2.dtype) if norm_bias is None \
        else norm_bias.reshape(1, H)
    if algo == "weight_only_int4":
        Ko = o2.shape[1]
        s2 = scale.reshape(1, H).astype(jnp.float32)
        # even/odd input-row split OUTSIDE the kernel (the TPU layout
        # cannot stride sublanes in-kernel) — same as _wol_int4_fwd_impl
        os_ = o2.reshape(T, Ko // 2, 2)
        xn, h = _oproj_norm_int4(os_[:, :, 0], os_[:, :, 1], x2, w, s2,
                                 fb, nw, nb, float(eps), norm)
    else:
        if algo == "weight_only_int8":
            s2 = scale.reshape(1, H).astype(jnp.float32)
        else:
            s2 = jnp.ones((1, H), jnp.float32)
        xn, h = _oproj_norm_forward(o2, x2, w, s2, fb, nw, nb,
                                    float(eps), norm)
    return xn.reshape(shape), h.reshape(shape)


# ---------------------------------------------------------------------------
# kernel 2: gate/up matmul + activation + down-proj + residual
# ---------------------------------------------------------------------------

def _ffn_kernel(h_ref, x_ref, wg_ref, sg_ref, wu_ref, su_ref, wd_ref,
                sd_ref, b1_ref, b2_ref, xo_ref, acc_ref, *,
                act: str = "swiglu"):
    h = h_ref[:].astype(jnp.float32)
    wg = wg_ref[:].astype(jnp.float32) \
        * sg_ref[0].astype(jnp.float32)[None, :]
    g = jnp.dot(h, wg, preferred_element_type=jnp.float32) \
        + b1_ref[0].astype(jnp.float32)[None, :]
    if act == "swiglu":
        wu = wu_ref[:].astype(jnp.float32) \
            * su_ref[0].astype(jnp.float32)[None, :]
        u = jnp.dot(h, wu, preferred_element_type=jnp.float32)
        # silu(g) * u, the _swiglu_kernel op order; the [bt, I]
        # activation exists only in this f32 VMEM scratch
        acc_ref[:] = g * jax.lax.logistic(g) * u
    else:
        acc_ref[:] = jax.nn.gelu(g, approximate=True)
    t = acc_ref[:]
    wd = wd_ref[:].astype(jnp.float32) \
        * sd_ref[0].astype(jnp.float32)[None, :]
    d = jnp.dot(t, wd, preferred_element_type=jnp.float32) \
        + b2_ref[0].astype(jnp.float32)[None, :]
    xo_ref[:] = (x_ref[:].astype(jnp.float32) + d).astype(xo_ref.dtype)


def _ffn_forward(h2, x2, wg, sg, wu, su, wd, sd, b1, b2, act):
    T, H = x2.shape
    I = wg.shape[1]
    Ku = wu.shape[0]
    bt = _row_block(T)
    return pl.pallas_call(
        functools.partial(_ffn_kernel, act=act),
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, H), lambda i: (i, 0)),
                  pl.BlockSpec((bt, H), lambda i: (i, 0)),
                  # weight slabs fetched once (no grid-dim in index_map)
                  pl.BlockSpec((H, I), lambda i: (0, 0)),
                  pl.BlockSpec((1, I), lambda i: (0, 0)),
                  pl.BlockSpec((Ku, I), lambda i: (0, 0)),
                  pl.BlockSpec((1, I), lambda i: (0, 0)),
                  pl.BlockSpec((I, H), lambda i: (0, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0)),
                  pl.BlockSpec((1, I), lambda i: (0, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bt, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, H), x2.dtype),
        scratch_shapes=[pltpu.VMEM((bt, I), jnp.float32)],
        interpret=_interpret(),
    )(h2, x2, wg, sg, wu, su, wd, sd, b1, b2)


def _ffn_int4_kernel(he_ref, ho_ref, x_ref, qg_ref, sg_ref, qu_ref,
                     su_ref, qd_ref, sd_ref, b1_ref, b2_ref, xo_ref,
                     acc_ref, wd_ref):
    def planes(q_ref, s_ref):
        s = s_ref[0].astype(jnp.float32)[None, :]
        q = q_ref[:].astype(jnp.int32)
        lo = (((q & 0xF) ^ 8) - 8).astype(jnp.float32) * s
        hi = (q >> 4).astype(jnp.float32) * s
        return lo, hi

    he = he_ref[:].astype(jnp.float32)
    ho = ho_ref[:].astype(jnp.float32)
    glo, ghi = planes(qg_ref, sg_ref)
    g = (jnp.dot(he, glo, preferred_element_type=jnp.float32)
         + jnp.dot(ho, ghi, preferred_element_type=jnp.float32)) \
        + b1_ref[0].astype(jnp.float32)[None, :]
    ulo, uhi = planes(qu_ref, su_ref)
    u = (jnp.dot(he, ulo, preferred_element_type=jnp.float32)
         + jnp.dot(ho, uhi, preferred_element_type=jnp.float32))
    acc_ref[:] = g * jax.lax.logistic(g) * u
    # the down-proj contracts over I, whose even/odd rows sit in the
    # two nibble planes.  De-interleaving the [bt, I] activation would
    # stride LANES, which Mosaic does not lower; instead the planes
    # interleave back into an f32 VMEM scratch by sublane-strided
    # stores (Mosaic wants a 128-lane base for those, so the scratch is
    # [H/128, I, 128] column chunks) and the activation contracts
    # against each chunk whole
    I2 = qd_ref.shape[0]
    nc, _, cw = wd_ref.shape
    dlo, dhi = planes(qd_ref, sd_ref)
    t = acc_ref[:]
    for c in range(nc):
        cols = slice(c * cw, (c + 1) * cw)
        wd_ref[c, pl.ds(0, I2, stride=2), :] = dlo[:, cols]
        wd_ref[c, pl.ds(1, I2, stride=2), :] = dhi[:, cols]
        d = jnp.dot(t, wd_ref[c], preferred_element_type=jnp.float32) \
            + b2_ref[0, cols].astype(jnp.float32)[None, :]
        xo_ref[:, cols] = (x_ref[:, cols].astype(jnp.float32)
                           + d).astype(xo_ref.dtype)


def _ffn_int4(he, ho, x2, qg, sg, qu, su, qd, sd, b1, b2):
    T, H = x2.shape
    H2 = he.shape[1]
    I = qg.shape[1]
    I2 = qd.shape[0]
    bt = _row_block(T)
    cw = 128 if H % 128 == 0 else H     # interpret mode takes any H
    return pl.pallas_call(
        _ffn_int4_kernel,
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, H2), lambda i: (i, 0)),
                  pl.BlockSpec((bt, H2), lambda i: (i, 0)),
                  pl.BlockSpec((bt, H), lambda i: (i, 0)),
                  pl.BlockSpec((H2, I), lambda i: (0, 0)),
                  pl.BlockSpec((1, I), lambda i: (0, 0)),
                  pl.BlockSpec((H2, I), lambda i: (0, 0)),
                  pl.BlockSpec((1, I), lambda i: (0, 0)),
                  pl.BlockSpec((I2, H), lambda i: (0, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0)),
                  pl.BlockSpec((1, I), lambda i: (0, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bt, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, H), x2.dtype),
        scratch_shapes=[pltpu.VMEM((bt, I), jnp.float32),
                        pltpu.VMEM((H // cw, I, cw), jnp.float32)],
        interpret=_interpret(),
    )(he, ho, x2, qg, sg, qu, su, qd, sd, b1, b2)


def fused_ffn(h, x, wg, sg=None, wu=None, su=None, wd=None, sd=None,
              b1=None, b2=None, *, act: str = "swiglu",
              algo: Optional[str] = None):
    """Gate/up matmul -> activation -> down-proj -> residual add, one
    launch.  ``h`` [..., H] is the normed FFN input (fused_oproj_norm's
    second output), ``x`` [..., H] the residual stream (its first).

    ``act`` 'swiglu' (llama/moe/mla: silu(h@wg + b1) * (h@wu) @ wd + b2)
    or 'gelu' (gpt: gelu(h@wg + b1, approximate) @ wd + b2 — ``wu`` is
    ignored and may be None).  Weights in any deploy layout via
    ``algo`` as in :func:`fused_oproj_norm` (int4 is swiglu-only, and
    unpacks the [bt, I] scratch activation in VMEM for the down-proj's
    even/odd split).  Returns x + ffn(h), shaped like ``x``."""
    shape = x.shape
    H = shape[-1]
    x2 = x.reshape(-1, H)
    h2 = h.reshape(-1, H)
    T = x2.shape[0]
    I = wg.shape[-1]
    Hd = wd.shape[-1] if algo != "weight_only_int4" else H
    fb1 = jnp.zeros((1, I), x2.dtype) if b1 is None else b1.reshape(1, I)
    fb2 = jnp.zeros((1, Hd), x2.dtype) if b2 is None \
        else b2.reshape(1, Hd)
    if algo == "weight_only_int4":
        if act != "swiglu":
            raise NotImplementedError("int4 fused_ffn is swiglu-only")
        hs = h2.reshape(T, H // 2, 2)
        out = _ffn_int4(hs[:, :, 0], hs[:, :, 1], x2,
                        wg, sg.reshape(1, I).astype(jnp.float32),
                        wu, su.reshape(1, I).astype(jnp.float32),
                        wd, sd.reshape(1, H).astype(jnp.float32),
                        fb1, fb2)
        return out.reshape(shape)
    ones_i = jnp.ones((1, I), jnp.float32)
    sg2 = ones_i if sg is None else sg.reshape(1, I).astype(jnp.float32)
    if act == "swiglu":
        su2 = ones_i if su is None \
            else su.reshape(1, I).astype(jnp.float32)
    else:
        # gelu never reads the up operand; ride a sublane-minimal dummy
        # so the launch arity (and the static spec list) stays fixed
        wu = jnp.zeros((8, I), x2.dtype)
        su2 = jnp.zeros((1, I), jnp.float32)
    sd2 = jnp.ones((1, Hd), jnp.float32) if sd is None \
        else sd.reshape(1, Hd).astype(jnp.float32)
    out = _ffn_forward(h2, x2, wg, sg2, wu, su2, wd, sd2, fb1, fb2, act)
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# eligibility: whether the kernels tile and their weight slabs fit VMEM
# (no caller: ServingEngine runs o-proj, norm and _ffn_apply)
# ---------------------------------------------------------------------------

def megadecode_eligible(hidden: int, intermediate: int, o_width: int, *,
                        int4: bool = False, dtype_bytes: int = 2,
                        tokens: int = 8) -> bool:
    """True when the fused back-half tiling is launchable: interpret
    mode always (blocks are virtual); on a real TPU the lane dims must
    be 128-aligned (the packed-int4 layouts additionally halve their
    contraction dims, so those must stay even) and the larger kernel's
    resident set must fit the scoped-VMEM limit.  Resident, as the
    compiler counts it: the weight slabs as stored (constant index_map
    — single-buffered), the double-buffered ``_row_block(tokens)`` token
    blocks, the f32 scratch, and for packed int4 the one 4-byte plane
    Mosaic materializes while unpacking the largest slab plus
    fused_ffn's [I, H] f32 down-proj scratch.  ``dtype_bytes`` is the
    stored width of an fp/int8 weight element.  Callers fall back to
    the split per-kernel chain when this is False — same math, more
    HBM round-trips."""
    if _interpret():
        return True
    if hidden % 128 or intermediate % 128 or o_width % 128:
        return False
    if int4 and (o_width % 2 or hidden % 2 or intermediate % 2):
        return False
    H, I, Ko = hidden, intermediate, o_width
    bt = _row_block(tokens)
    wb = 0.5 if int4 else dtype_bytes
    # o-proj + norm: o, x in; x, h out (2-byte activations, two buffers
    # each) + the [bt, H] f32 accumulator
    k1 = Ko * H * wb + 2 * 2 * bt * (Ko + 3 * H) + 4 * bt * H
    # ffn: h, x in; x out + the [bt, I] f32 activation scratch
    k2 = 3 * H * I * wb + 2 * 2 * bt * 3 * H + 4 * bt * I
    if int4:
        k1 += 4 * (Ko // 2) * H
        k2 += 4 * (H // 2) * I + 4 * I * H
    return max(k1, k2) <= _VMEM_LIMIT - _VMEM_SLACK


# ---------------------------------------------------------------------------
# certification (ROADMAP item 5 / paddlelint PK105): every kernel entry
# names its XLA oracle and the parity test that pins them together
# ---------------------------------------------------------------------------

from .oracles import register_oracle  # noqa: E402  (registry is leaf-light)

register_oracle(
    "fused_oproj_norm", kernel=fused_oproj_norm,
    reference="paddle_tpu.ops.references:oproj_norm_reference",
    parity_test="tests/test_megadecode.py::TestOprojNormParity")
register_oracle(
    "fused_ffn", kernel=fused_ffn,
    reference="paddle_tpu.ops.references:megadecode_ffn_reference",
    parity_test="tests/test_megadecode.py::TestFfnParity")
