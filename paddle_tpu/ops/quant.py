"""Weight-only quantized linear (int8/int4) for serving.

Reference capability (SURVEY §2.1 fused kernels): WeightOnlyLinearKernel +
python/paddle/incubate/nn/functional weight_only_linear / weight_quantize.

TPU-native: per-output-channel symmetric int8 (or packed int4) weights
dequantized in-kernel; a Pallas kernel tiles the matmul onto the MXU with
dequant fused into the VMEM load (one HBM pass over the quantized weights —
the bandwidth win is the point of weight-only quant). Interpret mode keeps
it testable on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["weight_quantize", "weight_dequantize", "weight_only_linear",
           "int4_planes", "int4_dequantize"]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def weight_quantize(w, algo: str = "weight_only_int8"):
    """w [K, N] -> (quantized weight, per-channel scale [N]).
    int8: symmetric absmax; int4: packed two nibbles per int8 byte."""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=0)
    if algo == "weight_only_int8":
        scale = absmax / 127.0
        q = jnp.clip(jnp.round(wf / jnp.maximum(scale, 1e-8)), -127, 127)
        return q.astype(jnp.int8), scale
    if algo == "weight_only_int4":
        scale = absmax / 7.0
        q = jnp.clip(jnp.round(wf / jnp.maximum(scale, 1e-8)), -7, 7)
        qi = q.astype(jnp.int8)
        K = qi.shape[0]
        if K % 2:
            raise ValueError("int4 pack needs even K")
        lo = qi[0::2] & 0xF
        hi = (qi[1::2] & 0xF) << 4
        return (lo | hi).astype(jnp.int8), scale
    raise ValueError(f"unknown algo: {algo}")


def int4_planes(qw):
    """Sign-extended nibble planes of a packed int4 weight: (lo, hi)
    int8 arrays, lo = even source rows, hi = odd. The ONE place the
    packing format is decoded — weight_dequantize and the decode path's
    split-contraction (generation._int4_halves) both consume it."""
    lo = (qw << 4).astype(jnp.int8) >> 4          # sign-extend low nibble
    hi = qw.astype(jnp.int8) >> 4
    return lo, hi


def weight_dequantize(qw, scale, algo: str = "weight_only_int8"):
    if algo == "weight_only_int8":
        return qw.astype(jnp.float32) * scale[None, :]
    if algo == "weight_only_int4":
        lo, hi = int4_planes(qw)
        K2, N = qw.shape
        out = jnp.zeros((K2 * 2, N), jnp.int8)
        out = out.at[0::2].set(lo).at[1::2].set(hi)
        return out.astype(jnp.float32) * scale[None, :]
    raise ValueError(f"unknown algo: {algo}")


def _dq4_kernel(qw_ref, s_ref, o_ref):
    # same in-VMEM nibble unpack as _wol4_kernel (int32 bit ops — Mosaic
    # cannot legalize shifts on int8 vectors), but emitting the f32
    # weight block instead of a matmul: the HBM weight read stays int4
    s = s_ref[0].astype(jnp.float32)[None, :]
    qw = qw_ref[:].astype(jnp.int32)
    lo = (((qw & 0xF) ^ 8) - 8).astype(jnp.float32) * s
    hi = (qw >> 4).astype(jnp.float32) * s
    K2, bn = lo.shape
    # interleave planes back to source-row order (lo = even rows,
    # hi = odd) via a sublane-merging reshape — lane dim untouched
    o_ref[:] = jnp.stack([lo, hi], axis=1).reshape(K2 * 2, bn)


def int4_dequantize(qw, scale):
    """Packed-int4 [K/2, N] + per-channel scale [N] -> f32 [K, N],
    unpacked in VMEM. For WHOLE-tensor consumers that reshape/slice the
    weight (the MLA absorbed kv_b) where the split-contraction matmul
    (_wol4_kernel) doesn't apply. Non-128-multiple N is zero-padded
    inside the launch and sliced back, mirroring _wol_int4_fwd_impl.
    Must match weight_dequantize(..., 'weight_only_int4') exactly."""
    K2, N = qw.shape
    pad_n = (-N) % 128
    if pad_n:
        qw = jnp.pad(qw, ((0, 0), (0, pad_n)))
        scale = jnp.pad(scale.reshape(-1), (0, pad_n))
    Np = N + pad_n
    bn = next((c for c in (2048, 1024, 512, 256, 128) if Np % c == 0), Np)
    out = pl.pallas_call(
        _dq4_kernel,
        grid=(Np // bn,),
        in_specs=[pl.BlockSpec((K2, bn), lambda j: (0, j)),
                  # scale rides 2-D, same layout clash as _wol4
                  pl.BlockSpec((1, bn), lambda j: (0, j))],
        out_specs=pl.BlockSpec((K2 * 2, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((K2 * 2, Np), jnp.float32),
        interpret=_interpret(),
    )(qw, scale.reshape(1, Np).astype(jnp.float32))
    return out[:, :N]


def _wol_kernel(x_ref, qw_ref, s_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)
    w = qw_ref[:].astype(jnp.float32) * s_ref[:].astype(jnp.float32)[None, :]
    o_ref[:] = jnp.dot(
        x, w, preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _wol_int8(x2, qw, scale):
    return _wol_int8_fwd_impl(x2, qw, scale)


def _wol_int8_fwd_impl(x2, qw, scale):
    M, K = x2.shape
    N = qw.shape[1]
    bm = 128 if M % 128 == 0 else (8 if M % 8 == 0 else 1)
    return pl.pallas_call(
        _wol_kernel,
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0)),
                  pl.BlockSpec((K, N), lambda i: (0, 0)),
                  pl.BlockSpec((N,), lambda i: (0,))],
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), x2.dtype),
        interpret=_interpret(),
    )(x2, qw, scale)


def _wol_int8_fwd(x2, qw, scale):
    return _wol_int8_fwd_impl(x2, qw, scale), (qw, scale)


def _wol_int8_bwd(res, g):
    qw, scale = res
    w = qw.astype(jnp.float32) * scale[None, :]
    dx = (g.astype(jnp.float32) @ w.T).astype(g.dtype)
    return dx, None, None


_wol_int8.defvjp(_wol_int8_fwd, _wol_int8_bwd)


def _wol4_kernel(xe_ref, xo_ref, qw_ref, s_ref, o_ref):
    # nibble planes unpacked IN VMEM: the HBM read stays packed int4
    # (XLA cannot fuse the shift chain into the MXU feed — measured: the
    # materialized-plane path runs at bf16 speed, r5)
    # int32 bit ops (Mosaic cannot legalize shifts on int8 vectors),
    # f32 planes + f32 dots: measured FASTER than bf16 planes (17.4k vs
    # 14.9k tok/s on the 8B decode row) — the unpack is VPU-bound at
    # int32 width and the extra converts outweigh the halved MXU feed
    s = s_ref[0].astype(jnp.float32)[None, :]
    qw = qw_ref[:].astype(jnp.int32)
    lo = (((qw & 0xF) ^ 8) - 8).astype(jnp.float32) * s
    hi = (qw >> 4).astype(jnp.float32) * s
    o = (jnp.dot(xe_ref[:].astype(jnp.float32), lo,
                 preferred_element_type=jnp.float32)
         + jnp.dot(xo_ref[:].astype(jnp.float32), hi,
                   preferred_element_type=jnp.float32))
    o_ref[:] = o.astype(o_ref.dtype)


def _wol_int4_fwd_impl(x2, qw, scale):
    M, K = x2.shape
    N = qw.shape[1]
    pad_m = (-M) % 8      # TPU blocks need 8-divisible sublanes
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
    Mp = M + pad_m
    # non-lane-aligned N (e.g. the vocab-16032 lm head): pad the packed
    # weight and its scales with zero columns to the next 128 multiple —
    # the pad columns dequantize to 0 and are sliced off the output, so
    # the hot decode path keeps the int4-bandwidth kernel instead of
    # falling back to dequantize-then-matmul (bf16 weight bytes)
    pad_n = (-N) % 128
    if pad_n:
        qw = jnp.pad(qw, ((0, 0), (0, pad_n)))
        scale = jnp.pad(scale.reshape(-1), (0, pad_n))
    Np = N + pad_n
    xs = x2.reshape(Mp, K // 2, 2)
    xe, xo = xs[:, :, 0], xs[:, :, 1]
    bm = 128 if Mp % 128 == 0 else 8
    bn = next((c for c in (2048, 1024, 512, 256, 128) if Np % c == 0), Np)
    out = pl.pallas_call(
        _wol4_kernel,
        grid=(Mp // bm, Np // bn),
        in_specs=[pl.BlockSpec((bm, K // 2), lambda i, j: (i, 0)),
                  pl.BlockSpec((bm, K // 2), lambda i, j: (i, 0)),
                  pl.BlockSpec((K // 2, bn), lambda i, j: (0, j)),
                  # scale rides 2-D: XLA's 1-D f32 tile layout clashes
                  # with blocked Mosaic operands (T(1024) vs T(bn))
                  pl.BlockSpec((1, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x2.dtype),
        interpret=_interpret(),
    )(xe, xo, qw, scale.reshape(1, Np))
    return out[:M, :N]


@jax.custom_vjp
def _wol_int4(x2, qw, scale):
    return _wol_int4_fwd_impl(x2, qw, scale)


def _wol_int4_fwd(x2, qw, scale):
    return _wol_int4_fwd_impl(x2, qw, scale), (qw, scale)


def _wol_int4_bwd(res, g):
    qw, scale = res
    w = weight_dequantize(qw, scale, "weight_only_int4")
    dx = (g.astype(jnp.float32) @ w.T).astype(g.dtype)
    return dx, None, None


_wol_int4.defvjp(_wol_int4_fwd, _wol_int4_bwd)


def weight_only_linear(x, qweight, scale, bias=None,
                       algo: str = "weight_only_int8"):
    """x [..., K] @ dequant(qweight [K, N]) + bias.

    Both paths run fused dequant+matmul Pallas kernels — the packed
    weights are the ONLY weight bytes that cross HBM. int4 contracts the
    even/odd input rows against the in-VMEM-unpacked nibble planes
    (_wol4_kernel).
    """
    shape = x.shape
    K = shape[-1]
    x2 = x.reshape(-1, K)
    if algo == "weight_only_int4":
        # any N: _wol_int4_fwd_impl zero-pads non-128-aligned N (e.g. the
        # vocab-16032 head) inside the kernel launch and slices it back
        out = _wol_int4(x2, qweight, scale)
    else:
        out = _wol_int8(x2, qweight, scale)
    if bias is not None:
        out = out + bias
    return out.reshape(*shape[:-1], out.shape[-1])


def weight_only_linear_reference(x, qweight, scale, bias=None,
                                 algo: str = "weight_only_int8"):
    """Plain-XLA oracle for weight_only_linear: whole-tensor dequant then
    a dense f32 matmul."""
    shape = x.shape
    w = weight_dequantize(qweight, scale, algo)
    out = (x.reshape(-1, shape[-1]).astype(jnp.float32) @ w).astype(x.dtype)
    if bias is not None:
        out = out + bias
    return out.reshape(*shape[:-1], out.shape[-1])


# certification (ROADMAP item 5 / paddlelint PK105)
from .oracles import register_oracle  # noqa: E402

register_oracle(
    "int4_dequantize", kernel=int4_dequantize,
    reference=lambda qw, scale: weight_dequantize(
        qw, scale, "weight_only_int4"),
    parity_test="tests/test_int8_families.py::TestLlamaInt4")
register_oracle(
    "weight_only_linear", kernel=weight_only_linear,
    reference=weight_only_linear_reference,
    parity_test="tests/test_fused_ops.py::TestWeightOnly")
