"""In-tree grouped GEMM (megablocks "gmm") kernel, authored and tunable.

Reference capability: CUTLASS grouped-GEMM fused-MoE kernels
(paddle/phi/kernels/fusion/cutlass_kernels/moe_gemm — SURVEY §2.3 P7;
completes the kernel-ownership sweep of VERDICT r2 Missing #7: flash,
flashmask, paged decode, and now grouped GEMM are all in-tree).

Contract (matches ops/grouped_gemm.py): lhs [M, K] with rows grouped
CONTIGUOUSLY, rhs [G, K, N], group_sizes [G] (sum <= M; rows past the
last group — e.g. padding added to reach a block multiple — match no
group and produce zero rows). out[m] = lhs[m] @ rhs[g(m)].

Design:
  - group offsets ride as SCALAR PREFETCH; grid (nm, nn, G) with the
    group dim innermost and a [bm, bn] f32 scratch accumulator —
    m-blocks that a group does not intersect are skipped (pl.when), so
    each out block costs ~(groups overlapping its rows) dots, not G;
  - rows outside the current group are zeroed on the VPU before the
    dot (a block may straddle a group boundary);
  - inputs stay bf16 on the MXU with f32 accumulation;
  - custom VJP: dlhs is the SAME kernel against swapaxes(rhs) (grouping
    is preserved), drhs is the transpose-grouped kernel `tgmm` (grid
    (G, nn, nm), [K, bn] accumulator per group);
  - interpret mode off-TPU so the CPU suite covers the kernel logic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gmm", "gmm_kernel_eligible"]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _gmm_kernel(offs_ref, lo_ref, hi_ref, lhs_ref, rhs_ref, out_ref,
                acc_ref, *, bm):
    i = pl.program_id(0)
    g = pl.program_id(2)
    ng = pl.num_programs(2)

    @pl.when(g == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    start = offs_ref[g]
    end = offs_ref[g + 1]
    overlap = jnp.logical_and(start < (i + 1) * bm, end > i * bm)

    @pl.when(overlap)
    def _compute():
        rows = i * bm + jax.lax.broadcasted_iota(
            jnp.int32, (bm, 1), 0)
        inside = jnp.logical_and(rows >= start, rows < end)
        lhs = jnp.where(inside, lhs_ref[...], 0)
        acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
            lhs, rhs_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(g == ng - 1)
    def _emit():
        out_ref[...] = acc_ref[:].astype(out_ref.dtype)


def _tgmm_kernel(offs_ref, lo_ref, hi_ref, lhs_ref, dout_ref, drhs_ref,
                 acc_ref, *, bm):
    g = pl.program_id(0)
    i = pl.program_id(2)
    nm = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    start = offs_ref[g]
    end = offs_ref[g + 1]
    overlap = jnp.logical_and(start < (i + 1) * bm, end > i * bm)

    @pl.when(overlap)
    def _compute():
        rows = i * bm + jax.lax.broadcasted_iota(
            jnp.int32, (bm, 1), 0)
        inside = jnp.logical_and(rows >= start, rows < end)
        lhs = jnp.where(inside, lhs_ref[...], 0)       # [bm, K]
        dout = dout_ref[...]                            # [bm, bn]
        acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
            lhs, dout, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [K, bn]

    @pl.when(i == nm - 1)
    def _emit():
        drhs_ref[0] = acc_ref[:].astype(drhs_ref.dtype)


def gmm_kernel_eligible(M: int, K: int, N: int, block_m: int = 128,
                        block_n: int = 128) -> bool:
    """N must tile; M is padded by the wrapper; K rides whole."""
    return N % block_n == 0 and K % 128 == 0


# Index maps clamp the data-dependent grid coordinate so that grid steps
# a block is pl.when-skipped on re-reference the PREVIOUS block and
# Pallas elides their DMA. The clamp bounds are computed with plain XLA
# before the kernel and ride as scalar prefetch (searchsorted et al.
# do not lower inside Mosaic index maps).


def _offsets(group_sizes, M):
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])


def _gmm_fwd_impl(lhs, rhs, group_sizes, bm, bn):
    M, K = lhs.shape
    G, _, N = rhs.shape
    pad = (-M) % bm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    Mp = M + pad
    nm, nn = Mp // bm, N // bn
    offs = _offsets(group_sizes, M)
    row0 = jnp.arange(nm, dtype=jnp.int32) * bm
    blk_lo = jnp.clip(
        jnp.searchsorted(offs[1:], row0, side="right"), 0, G - 1)
    blk_hi = jnp.clip(
        jnp.searchsorted(offs[1:], row0 + bm - 1, side="right"), 0, G - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nm, nn, G),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j, g, offs, lo, hi: (i, 0)),
            pl.BlockSpec((1, K, bn),
                         lambda i, j, g, offs, lo, hi:
                         (jnp.clip(g, lo[i], hi[i]), 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn),
                               lambda i, j, g, offs, lo, hi: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, bm=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(offs, blk_lo, blk_hi, lhs, rhs)
    return out[:M] if pad else out


def _tgmm_impl(lhs, dout, group_sizes, bm, bn):
    """drhs[g] = lhs[rows of g].T @ dout[rows of g] -> [G, K, N]."""
    M, K = lhs.shape
    N = dout.shape[1]
    G = group_sizes.shape[0]
    pad = (-M) % bm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        dout = jnp.pad(dout, ((0, pad), (0, 0)))
    Mp = M + pad
    nm, nn = Mp // bm, N // bn
    offs = _offsets(group_sizes, M)
    i_lo = jnp.clip(offs[:-1] // bm, 0, nm - 1)
    i_hi = jnp.clip(jnp.maximum(jnp.maximum(offs[1:], 1) - 1, 0) // bm,
                    0, nm - 1)
    i_hi = jnp.maximum(i_hi, i_lo)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(G, nn, nm),
        in_specs=[
            pl.BlockSpec((bm, K),
                         lambda g, j, i, offs, lo, hi:
                         (jnp.clip(i, lo[g], hi[g]), 0)),
            pl.BlockSpec((bm, bn),
                         lambda g, j, i, offs, lo, hi:
                         (jnp.clip(i, lo[g], hi[g]), j)),
        ],
        out_specs=pl.BlockSpec((1, K, bn),
                               lambda g, j, i, offs, lo, hi: (g, 0, j)),
        scratch_shapes=[pltpu.VMEM((K, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, bm=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, K, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(offs, i_lo.astype(jnp.int32), i_hi.astype(jnp.int32), lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gmm(lhs, rhs, group_sizes, block_m: int = 128, block_n: int = 128):
    """Grouped matmul: rows of lhs hit their group's rhs (see module
    docstring). Differentiable; bf16-in/f32-accumulate.

    Shapes must satisfy :func:`gmm_kernel_eligible` (N % block_n == 0 and
    K % 128 == 0): the kernel floor-divides N by block_n, so a ragged N
    would leave trailing columns unwritten, and the backward pass re-runs
    the kernel with K in the N position."""
    _, K = lhs.shape
    _, _, N = rhs.shape
    if not gmm_kernel_eligible(lhs.shape[0], K, N, block_m, block_n):
        raise ValueError(
            f"gmm: shapes K={K}, N={N} not eligible for the in-tree kernel "
            f"(need N % {block_n} == 0 and K % 128 == 0, both fwd and bwd); "
            "use ops.grouped_gemm.grouped_matmul for the routed fallback")
    return _gmm_fwd_impl(lhs, rhs, group_sizes, block_m, block_n)


def _gmm_vjp_fwd(lhs, rhs, group_sizes, block_m, block_n):
    out = _gmm_fwd_impl(lhs, rhs, group_sizes, block_m, block_n)
    return out, (lhs, rhs, group_sizes)


def _gmm_vjp_bwd(block_m, block_n, res, dout):
    lhs, rhs, group_sizes = res
    # dlhs: same grouped matmul against rhs^T (K<->N swap); K plays N's
    # role so it must tile — guaranteed by gmm_kernel_eligible's K%128
    dlhs = _gmm_fwd_impl(dout, jnp.swapaxes(rhs, 1, 2), group_sizes,
                         block_m, min(block_n, rhs.shape[1]))
    drhs = _tgmm_impl(lhs, dout, group_sizes, block_m, block_n)
    return dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype), None


gmm.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


# certification (ROADMAP item 5 / paddlelint PK105)
from .oracles import register_oracle  # noqa: E402

register_oracle(
    "gmm", kernel=gmm,
    reference="paddle_tpu.ops.references:gmm_reference",
    parity_test="tests/test_gmm_kernel.py::TestGmmParity")
