"""FlashMask block-skipping attention kernel (splash-attention class).

Reference capability: FlashMask sparse-mask attention — paddle's
flashmask_attention (python/paddle/nn/functional/flash_attention.py,
FlashMask variant of paddle/phi/kernels/gpu/flash_attn_kernel.cu;
SURVEY §5.7 item 1). The mask is encoded per KEY COLUMN as row-index
bands — O(S) memory — and the kernel must never materialize the dense
[B,H,Sq,Sk] mask. This in-tree Pallas kernel (authored, tunable) does
flash attention with:

  - a per-(q_block, k_block) SKIP map computed from block-level min/max
    of the column bands (+ the causal diagonal): fully-masked and
    above-diagonal blocks cost zero MXU work;
  - the exact elementwise band mask applied inside surviving blocks from
    broadcasted iota vs the column bands (VPU-cheap, block-local — the
    dense mask never exists outside one [bq, bk] tile in VMEM);
  - online-softmax forward emitting logsumexp, and flash-style backward
    kernels (dq sweep over k blocks; dkv sweep over q blocks) reusing
    the same skip map.

Band normal form: every paddle startend encoding reduces to two masked
row bands per column, [s1, e1) ∪ [s2, e2); `allow(i, j) =
(causal -> j <= i) and i not in band1(j) and i not in band2(j)`.

Fully-masked query rows produce 0 output (l == 0 guard; the composite
oracle yields an arbitrary uniform average there — such rows are
don't-care by definition).

Block sizes default to 128x128 and are caller-tunable. Runs in Pallas
interpret mode off-TPU so the same kernel logic is covered by the CPU
test suite.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flashmask_sdpa", "flashmask_block_kinds", "bands_from_startend"]

_NEG = -1e30

# B/H/outer-block dims are independent; only the innermost dim carries
# the online-softmax / accumulator state (paddlelint PE501: every
# revisited output axis must be declared). Parallel outer dims let
# Mosaic split them across TensorCores (megacore parts), same as flash.
_CPARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def bands_from_startend(se, Sq: int, Sk: int, causal: bool):
    """paddle startend_row_indices [B, Hm, Sk, C] -> two masked bands
    (s1, e1, s2, e2), each [B, Hm, Sk] int32."""
    C = se.shape[-1]
    se = se.astype(jnp.int32)
    big = jnp.full(se.shape[:-1], Sq, jnp.int32)
    zero = jnp.zeros(se.shape[:-1], jnp.int32)
    if C == 1:
        if not causal:
            raise ValueError("C=1 FlashMask (LTS) requires causal=True")
        return se[..., 0], big, zero, zero          # [start, Sq)
    if C == 2 and causal:
        return se[..., 0], se[..., 1], zero, zero   # [start, end)
    if C == 2:
        # [LTStart, UTEnd]: lower band [lt_start, Sq), upper band [0, ut)
        return se[..., 0], big, zero, se[..., 1]
    if C == 4:
        if causal:
            raise ValueError("C=4 FlashMask requires causal=False")
        return se[..., 0], se[..., 1], se[..., 2], se[..., 3]
    raise ValueError(f"startend_row_indices last dim must be 1, 2 or 4, "
                     f"got {C}")


def flashmask_block_kinds(bands, Sq: int, Sk: int, bq: int, bk: int,
                          causal: bool):
    """[B, Hm, nq, nk] int32 skip map: 0 = block contributes nothing
    (above the causal diagonal, or every column's bands cover the whole
    row range), 1 = compute. Conservative on mixed blocks (computes)."""
    s1, e1, s2, e2 = bands
    nq, nk = Sq // bq, Sk // bk
    q0 = jnp.arange(nq, dtype=jnp.int32)[:, None] * bq        # [nq,1]
    q1 = q0 + bq
    kb = lambda a, red: red(a.reshape(a.shape[:-1] + (nk, bk)), axis=-1)
    s1x, e1n = kb(s1, jnp.max), kb(e1, jnp.min)               # [B,Hm,nk]
    s2x, e2n = kb(s2, jnp.max), kb(e2, jnp.min)
    full1 = jnp.logical_and(s1x[..., None, :] <= q0,
                            e1n[..., None, :] >= q1)          # [B,Hm,nq,nk]
    full2 = jnp.logical_and(s2x[..., None, :] <= q0,
                            e2n[..., None, :] >= q1)
    masked = jnp.logical_or(full1, full2)
    if causal:
        k0 = jnp.arange(nk, dtype=jnp.int32)[None, :] * bk
        above = q1 <= k0                                      # [nq,nk]
        masked = jnp.logical_or(masked, above)
    return jnp.logical_not(masked).astype(jnp.int32)


def _fwd_kernel(kind_ref, s1_ref, e1_ref, s2_ref, e2_ref,
                q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, bq, bk, causal):
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    qi = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(kind_ref[0, 0, qi, kj] > 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                   # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                   # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bk]
        rows = qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        band = lambda lo, hi: jnp.logical_and(
            rows >= lo[0, 0][None, :], rows < hi[0, 0][None, :])
        masked = jnp.logical_or(band(s1_ref, e1_ref),
                                band(s2_ref, e2_ref))
        if causal:
            cols = kj * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            masked = jnp.logical_or(masked, cols > rows)
        s = jnp.where(masked, _NEG, s)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        # exp(_NEG - m) underflows to exactly 0, so fully-masked entries
        # never pollute l; m_new stays at _NEG only when nothing is
        # visible yet, and alpha = exp(0) = 1 keeps that stable
        p = jnp.exp(s - m_new)
        p = jnp.where(masked, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, -1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(kj == nk - 1)
    def _emit():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        # +1e30 sentinel for empty rows: bwd's exp(s - lse) then
        # underflows to 0 instead of exploding on a -inf lse
        lse_ref[0, 0] = jnp.where(
            l == 0.0, -_NEG, m_ref[:] + jnp.log(l_safe))


def _bwd_dq_kernel(kind_ref, s1_ref, e1_ref, s2_ref, e2_ref,
                   q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
                   dq_acc, *, scale, bq, bk, causal):
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    qi = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(kind_ref[0, 0, qi, kj] > 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        band = lambda lo, hi: jnp.logical_and(
            rows >= lo[0, 0][None, :], rows < hi[0, 0][None, :])
        masked = jnp.logical_or(band(s1_ref, e1_ref),
                                band(s2_ref, e2_ref))
        if causal:
            cols = kj * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            masked = jnp.logical_or(masked, cols > rows)
        p = jnp.exp(s - lse_ref[0, 0])
        p = jnp.where(masked, 0.0, p)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0, 0]) * scale
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _emit():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(kind_ref, s1_ref, e1_ref, s2_ref, e2_ref,
                    q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, bq, bk,
                    causal):
    qi = pl.program_id(3)
    nq = pl.num_programs(3)
    kj = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(kind_ref[0, 0, qi, kj] > 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bk]
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        band = lambda lo, hi: jnp.logical_and(
            rows >= lo[0, 0][None, :], rows < hi[0, 0][None, :])
        masked = jnp.logical_or(band(s1_ref, e1_ref),
                                band(s2_ref, e2_ref))
        if causal:
            cols = kj * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            masked = jnp.logical_or(masked, cols > rows)
        p = jnp.exp(s - lse_ref[0, 0])
        p = jnp.where(masked, 0.0, p)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, bk]
        ds = p * (dp - di_ref[0, 0]) * scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, D]

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _specs(B, H, Hm, Sq, Sk, D, bq, bk, order: str):
    """Common in_specs for (kind, s1, e1, s2, e2, q, k, v). order='qk':
    grid (B, H, nq, nk); order='kq': grid (B, H, nk, nq)."""
    nq, nk = Sq // bq, Sk // bk
    hm = (lambda h: h) if Hm > 1 else (lambda h: 0)
    if order == "qk":
        semap = lambda b, h, i, j: (b, hm(h), j)
        qmap = lambda b, h, i, j: (b, h, i, 0)
        kmap = lambda b, h, i, j: (b, h, j, 0)
    else:
        semap = lambda b, h, i, j: (b, hm(h), i)
        qmap = lambda b, h, i, j: (b, h, j, 0)
        kmap = lambda b, h, i, j: (b, h, i, 0)
    se_spec = pl.BlockSpec((1, 1, bk), semap)
    # the skip map is control flow: scalars belong in SMEM. The block
    # keeps the full trailing [nq, nk] table (TPU requires trailing
    # block dims to equal the array dims unless (8,128)-divisible);
    # kernels index it [0, 0, qi, kj] directly.
    kind_spec = pl.BlockSpec((1, 1, nq, nk),
                             lambda b, h, i, j: (b, hm(h), 0, 0),
                             memory_space=pltpu.SMEM)
    return ([kind_spec] + [se_spec] * 4 +
            [pl.BlockSpec((1, 1, bq, D), qmap),
             pl.BlockSpec((1, 1, bk, D), kmap),
             pl.BlockSpec((1, 1, bk, D), kmap)], qmap, kmap)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _flashmask_core(q, k, v, s1, e1, s2, e2, scale, causal, bq, bk):
    o, _ = _flashmask_fwd_impl(q, k, v, s1, e1, s2, e2, scale, causal,
                               bq, bk)
    return o


def _flashmask_fwd_impl(q, k, v, s1, e1, s2, e2, scale, causal, bq, bk):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    Hm = s1.shape[1]
    kinds = flashmask_block_kinds((s1, e1, s2, e2), Sq, Sk, bq, bk,
                                  causal)
    nq, nk = Sq // bq, Sk // bk
    in_specs, qmap, _ = _specs(B, H, Hm, Sq, Sk, D, bq, bk, "qk")
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal),
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, 1, bq, D), qmap),
                   pl.BlockSpec((1, 1, bq, 1),
                                lambda b, h, i, j: (b, h, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32)],
        # acc/m/l persist across the sequential innermost (nk) grid dim
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        compiler_params=_CPARAMS,
        interpret=_interpret(),
    )(kinds, s1, e1, s2, e2, q, k, v)
    return o, (lse, kinds)


def _flashmask_vjp_fwd(q, k, v, s1, e1, s2, e2, scale, causal, bq, bk):
    o, (lse, kinds) = _flashmask_fwd_impl(q, k, v, s1, e1, s2, e2, scale,
                                          causal, bq, bk)
    return o, (q, k, v, s1, e1, s2, e2, o, lse, kinds)


def _flashmask_vjp_bwd(scale, causal, bq, bk, res, do):
    q, k, v, s1, e1, s2, e2, o, lse, kinds = res
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    Hm = s1.shape[1]
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1, keepdims=True)                     # [B,H,Sq,1]
    nq, nk = Sq // bq, Sk // bk

    in_specs, qmap, kmap = _specs(B, H, Hm, Sq, Sk, D, bq, bk, "qk")
    row_spec = pl.BlockSpec((1, 1, bq, 1),
                            lambda b, h, i, j: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal),
        grid=(B, H, nq, nk),
        in_specs=in_specs + [pl.BlockSpec((1, 1, bq, D), qmap),
                             row_spec, row_spec],
        out_specs=pl.BlockSpec((1, 1, bq, D), qmap),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_CPARAMS,
        interpret=_interpret(),
    )(kinds, s1, e1, s2, e2, q, k, v, do, lse, di)

    in_specs2, qmap2, kmap2 = _specs(B, H, Hm, Sq, Sk, D, bq, bk, "kq")
    row_spec2 = pl.BlockSpec((1, 1, bq, 1),
                             lambda b, h, i, j: (b, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal),
        grid=(B, H, nk, nq),
        in_specs=in_specs2 + [pl.BlockSpec((1, 1, bq, D), qmap2),
                              row_spec2, row_spec2],
        out_specs=[pl.BlockSpec((1, 1, bk, D), kmap2),
                   pl.BlockSpec((1, 1, bk, D), kmap2)],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Sk, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_CPARAMS,
        interpret=_interpret(),
    )(kinds, s1, e1, s2, e2, q, k, v, do, lse, di)
    return dq, dk, dv, None, None, None, None


_flashmask_core.defvjp(_flashmask_vjp_fwd, _flashmask_vjp_bwd)


def flashmask_sdpa(q, k, v, startend_row_indices, causal: bool = True,
                   scale=None, block_q: int = 128, block_k: int = 128):
    """[B,S,H,D] FlashMask attention through the block-skipping kernel.
    startend_row_indices [B, Hm, Sk, C], C in {1,2,4} (paddle encoding).
    Returns [B,Sq,H,D]; differentiable (flash-style bwd kernels)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if scale is None:
        scale = D ** -0.5
    bands = bands_from_startend(startend_row_indices, Sq, Sk, causal)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    out = _flashmask_core(qh, kh, vh, *bands, float(scale), bool(causal),
                          block_q, block_k)
    return jnp.swapaxes(out, 1, 2)


def flashmask_kernel_eligible(Sq: int, Sk: int, D: int,
                              block_q: int = 128,
                              block_k: int = 128) -> bool:
    return (Sq % block_q == 0 and Sk % block_k == 0
            and (D % 128 == 0 or (D <= 128 and D % 64 == 0)))


# certification (ROADMAP item 5 / paddlelint PK105): the dense-mask
# composite is the oracle; lazy string — flash_attention imports us
from .oracles import register_oracle  # noqa: E402

register_oracle(
    "flashmask_sdpa", kernel=flashmask_sdpa,
    reference="paddle_tpu.ops.flash_attention:sdpa_reference",
    parity_test="tests/test_flashmask_kernel.py::"
                "test_kernel_matches_dense_oracle")
