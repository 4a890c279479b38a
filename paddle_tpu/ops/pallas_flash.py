"""In-tree flash attention kernel (fwd + bwd), authored and tunable.

Reference capability: FlashAttention2 fwd/bwd —
paddle/phi/kernels/gpu/flash_attn_kernel.cu and
python/paddle/nn/functional/flash_attention.py (VERDICT r2 item 9: own
the kernel the serving/pretrain benches spend their time in, instead of
wrapping jax.experimental.pallas.ops.tpu.flash_attention).

Same machinery as ops/pallas_flashmask.py (that kernel proved the
pattern; this one drops the band encodings and adds what the bundled
kernel refuses):

  - causal with UNEQUAL Sq/Sk, bottom-right aligned: query row i sees
    key j iff j <= i + (Sk - Sq) — exactly sdpa_reference's
    jnp.tril(..., k=Sk-Sq) convention, so the composite stays the oracle;
  - optional q/kv segment ids (varlen packing, key-padding routing) as
    an elementwise block-local mask;
  - block-level skip for fully-above-diagonal blocks, computed from
    program ids (static — no skip-map array needed);
  - online-softmax forward emitting logsumexp; flash-style backward
    (dq sweep over k blocks, dk/dv sweep over q blocks);
  - caller-tunable block sizes (default 128x128), f32 accumulation,
    interpret mode off-TPU so the CPU suite covers the kernel logic.

Fully-hidden query rows (causal offset < 0 at the sequence head, or an
unmatched segment) produce zero output and a +1e30 lse sentinel, so the
backward underflows to zero instead of producing NaN.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .on_mesh import on_mesh

__all__ = ["flash_sdpa", "flash_kernel_eligible"]

_NEG = -1e30

# B/H/outer-block grid dims are independent; only the innermost dim
# carries the online-softmax / accumulator state. Marking them parallel
# lets Mosaic split them across TensorCores (megacore parts)
_CPARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _mask_for_block(qi, kj, bq, bk, causal, off, use_seg, sq_ref, sk_ref):
    """[bq, bk] bool mask of HIDDEN entries for this block."""
    masked = None
    if causal:
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        masked = cols > rows + off
    if use_seg:
        seg = sq_ref[0, 0][:, None] != sk_ref[0, 0][None, :]
        masked = seg if masked is None else jnp.logical_or(masked, seg)
    return masked


def _block_visible(qi, kj, bq, bk, off):
    """Causal block skip: the block's lowest row sees its first column?"""
    return kj * bk <= qi * bq + (bq - 1) + off


def _fwd_kernel(sq_ref, sk_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, bq, bk, causal, off,
                use_seg):
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    qi = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    visible = _block_visible(qi, kj, bq, bk, off) if causal \
        else (kj == kj)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0]                                       # [bq, D]
        k = k_ref[0, 0]                                       # [bk, D]
        # inputs stay bf16 on the MXU (full throughput); accumulation is
        # f32 via preferred_element_type — same contract as the bundled
        # kernel (casting inputs to f32 halves MXU throughput)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bk]
        masked = _mask_for_block(qi, kj, bq, bk, causal, off, use_seg,
                                 sq_ref, sk_ref)
        if masked is not None:
            s = jnp.where(masked, _NEG, s)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked is not None:
            p = jnp.where(masked, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, -1, keepdims=True)
        v = v_ref[0, 0]
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(kj == nk - 1)
    def _emit():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            l == 0.0, -_NEG, m_ref[:] + jnp.log(l_safe))


def _bwd_dq_kernel(sq_ref, sk_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   di_ref, dq_ref, dq_acc, *, scale, bq, bk, causal, off,
                   use_seg):
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    qi = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    visible = _block_visible(qi, kj, bq, bk, off) if causal \
        else (kj == kj)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        masked = _mask_for_block(qi, kj, bq, bk, causal, off, use_seg,
                                 sq_ref, sk_ref)
        p = jnp.exp(s - lse_ref[0, 0])
        if masked is not None:
            p = jnp.where(masked, 0.0, p)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - di_ref[0, 0]) * scale).astype(k.dtype)
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _emit():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(sq_ref, sk_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    di_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale, bq,
                    bk, causal, off, use_seg):
    qi = pl.program_id(3)
    nq = pl.num_programs(3)
    kj = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    visible = _block_visible(qi, kj, bq, bk, off) if causal \
        else (qi == qi)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bk]
        masked = _mask_for_block(qi, kj, bq, bk, causal, off, use_seg,
                                 sq_ref, sk_ref)
        p = jnp.exp(s - lse_ref[0, 0])
        if masked is not None:
            p = jnp.where(masked, 0.0, p)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, bk]
        ds = (p * (dp - di_ref[0, 0]) * scale).astype(q.dtype)
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, D]

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _specs(bq, bk, D, order: str):
    """in_specs for (seg_q, seg_kv, q, k, v). order='qk': grid
    (B, H, nq, nk) with q indexed by i; order='kq': grid (B, H, nk, nq)
    with q indexed by j (the dkv sweep)."""
    if order == "qk":
        sqmap = lambda b, h, i, j: (b, 0, i)
        skmap = lambda b, h, i, j: (b, 0, j)
        qmap = lambda b, h, i, j: (b, h, i, 0)
        kmap = lambda b, h, i, j: (b, h, j, 0)
    else:
        sqmap = lambda b, h, i, j: (b, 0, j)
        skmap = lambda b, h, i, j: (b, 0, i)
        qmap = lambda b, h, i, j: (b, h, j, 0)
        kmap = lambda b, h, i, j: (b, h, i, 0)
    # segment ids ride as [B, 1, S] so the (1, 1, blk) block satisfies the
    # Mosaic trailing-dims rule (second-to-last block dim == full dim 1)
    return ([pl.BlockSpec((1, 1, bq), sqmap),
             pl.BlockSpec((1, 1, bk), skmap),
             pl.BlockSpec((1, 1, bq, D), qmap),
             pl.BlockSpec((1, 1, bk, D), kmap),
             pl.BlockSpec((1, 1, bk, D), kmap)], qmap, kmap)


def _out(shape, dtype, *like):
    """out_shape entry varying over the manual mesh axes its inputs vary
    over — inside a vma-checked shard_map (on_mesh under the pipeline's
    pp region) pallas_call has to be told."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# the kernel launches run per shard of the step's mesh, INSIDE the
# custom_vjp rules (see ops/on_mesh.py)
_QKV = ("bhsd", "bhsd", "bhsd", "b1s", "b1s")


def _fwd_on_mesh(q, k, v, seg_q, seg_kv, scale, causal, bq, bk, use_seg):
    fwd = functools.partial(_flash_fwd_impl, scale=scale, causal=causal,
                            bq=bq, bk=bk, use_seg=use_seg)
    return on_mesh(fwd, (q, k, v, seg_q, seg_kv), _QKV, ("bhsd", "bhs1"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_core(q, k, v, seg_q, seg_kv, scale, causal, bq, bk, use_seg):
    o, _ = _fwd_on_mesh(q, k, v, seg_q, seg_kv, scale, causal, bq, bk,
                        use_seg)
    return o


def _flash_fwd_impl(q, k, v, seg_q, seg_kv, scale, causal, bq, bk,
                    use_seg):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    off = Sk - Sq
    nq, nk = Sq // bq, Sk // bk
    in_specs, qmap, _ = _specs(bq, bk, D, "qk")
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal, off=off, use_seg=use_seg),
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, 1, bq, D), qmap),
                   pl.BlockSpec((1, 1, bq, 1),
                                lambda b, h, i, j: (b, h, i, 0))],
        out_shape=[_out((B, H, Sq, D), q.dtype, q, k, v),
                   _out((B, H, Sq, 1), jnp.float32, q, k, v)],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        compiler_params=_CPARAMS,
        interpret=_interpret(),
    )(seg_q, seg_kv, q, k, v)
    return o, lse


def _flash_vjp_fwd(q, k, v, seg_q, seg_kv, scale, causal, bq, bk,
                   use_seg):
    o, lse = _fwd_on_mesh(q, k, v, seg_q, seg_kv, scale, causal, bq, bk,
                          use_seg)
    return o, (q, k, v, seg_q, seg_kv, o, lse)


def _flash_vjp_bwd(scale, causal, bq, bk, use_seg, res, do):
    bwd = functools.partial(_flash_bwd_impl, scale=scale, causal=causal,
                            bq=bq, bk=bk, use_seg=use_seg)
    dq, dk, dv = on_mesh(bwd, (*res, do),
                         _QKV + ("bhsd", "bhs1", "bhsd"), ("bhsd",) * 3)
    return dq, dk, dv, None, None


def _flash_bwd_impl(q, k, v, seg_q, seg_kv, o, lse, do, scale, causal,
                    bq, bk, use_seg):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    off = Sk - Sq
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1, keepdims=True)                     # [B,H,Sq,1]
    nq, nk = Sq // bq, Sk // bk

    in_specs, qmap, kmap = _specs(bq, bk, D, "qk")
    row_spec = pl.BlockSpec((1, 1, bq, 1),
                            lambda b, h, i, j: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal, off=off, use_seg=use_seg),
        grid=(B, H, nq, nk),
        in_specs=in_specs + [pl.BlockSpec((1, 1, bq, D), qmap),
                             row_spec, row_spec],
        out_specs=pl.BlockSpec((1, 1, bq, D), qmap),
        out_shape=_out((B, H, Sq, D), q.dtype, q, k, v, do),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_CPARAMS,
        interpret=_interpret(),
    )(seg_q, seg_kv, q, k, v, do, lse, di)

    in_specs2, qmap2, kmap2 = _specs(bq, bk, D, "kq")
    row_spec2 = pl.BlockSpec((1, 1, bq, 1),
                             lambda b, h, i, j: (b, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal, off=off, use_seg=use_seg),
        grid=(B, H, nk, nq),
        in_specs=in_specs2 + [pl.BlockSpec((1, 1, bq, D), qmap2),
                              row_spec2, row_spec2],
        out_specs=[pl.BlockSpec((1, 1, bk, D), kmap2),
                   pl.BlockSpec((1, 1, bk, D), kmap2)],
        out_shape=[_out((B, H, Sk, D), k.dtype, q, k, v, do),
                   _out((B, H, Sk, D), v.dtype, q, k, v, do)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_CPARAMS,
        interpret=_interpret(),
    )(seg_q, seg_kv, q, k, v, do, lse, di)
    return dq, dk, dv


_flash_core.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_kernel_eligible(Sq: int, Sk: int, D: int, block_q: int = 128,
                          block_k: int = 128) -> bool:
    """Unlike the bundled kernel's gate, causal Sq != Sk IS eligible."""
    return (Sq % block_q == 0 and Sk % block_k == 0
            and (D % 128 == 0 or (D <= 128 and D % 64 == 0)))


def flash_sdpa(q, k, v, causal: bool = False, segment_ids_q=None,
               segment_ids_kv=None, scale: Optional[float] = None,
               block_q: int = 512, block_k: int = 512):
    """[B,S,H,D] flash attention through the in-tree kernel. Causal is
    bottom-right aligned for Sq != Sk (sdpa_reference convention).
    Differentiable (flash-style bwd kernels). Default 512x512 blocks
    (tools/flash_bench.py sweep on the v5e: 512-class blocks beat 128 by
    ~1.2-1.7x at seq >= 4096); blocks clamp to the sequence lengths so
    short sequences still run."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"flash_sdpa: Sq={Sq}/Sk={Sk} not divisible by blocks "
            f"{block_q}x{block_k} (see flash_kernel_eligible)")
    if scale is None:
        scale = D ** -0.5
    use_seg = segment_ids_q is not None or segment_ids_kv is not None
    if use_seg:
        seg_q = (segment_ids_q if segment_ids_q is not None
                 else jnp.ones((B, Sq))).astype(jnp.int32)
        seg_kv = (segment_ids_kv if segment_ids_kv is not None
                  else jnp.ones((B, Sk))).astype(jnp.int32)
    else:
        # placeholders keep the kernel signature static; use_seg=False
        # compiles the masking out entirely
        seg_q = jnp.zeros((B, Sq), jnp.int32)
        seg_kv = jnp.zeros((B, Sk), jnp.int32)
    seg_q = seg_q[:, None, :]                 # [B, 1, S]: see _specs
    seg_kv = seg_kv[:, None, :]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    out = _flash_core(qh, kh, vh, seg_q, seg_kv, float(scale),
                      bool(causal), block_q, block_k, use_seg)
    return jnp.swapaxes(out, 1, 2)


# certification (ROADMAP item 5 / paddlelint PK105): the dense-softmax
# composite is the oracle; lazy string — flash_attention imports us
from .oracles import register_oracle  # noqa: E402

register_oracle(
    "flash_sdpa", kernel=flash_sdpa,
    reference="paddle_tpu.ops.flash_attention:sdpa_reference",
    parity_test="tests/test_flash_kernel.py::TestForwardParity")
