"""In-tree fused MLA (multi-head latent attention) decode kernel.

Reference capability: DeepSeek-V2 absorbed-latent decode (PaddleNLP
deepseek_v2 modeling, SURVEY §2.4 row 5; the fused masked-MHA decode
kernels under paddle/phi/kernels/fusion/gpu/ are the CUDA analogue).

Absorbed MLA decode is structurally MULTI-QUERY attention: every q head
attends to the SAME latent stream — K[t] = (c_lat[t] ⊕ c_pe[t]) with
dim r+dr and V[t] = c_lat[t] with dim r. The XLA einsum path reads the
latent cache TWICE per step (score einsum, then output einsum after the
softmax barrier — XLA cannot fuse across it), which is exactly the
~0.09 roofline residual recorded in docs/SERVING_BENCH.json r5. This
kernel streams each cache byte ONCE: one pass over time-blocks with
online-softmax accumulators, scores and the weighted latent sum computed
from the same VMEM tile.

Machinery mirrors ops/pallas_paged.py v1: grid (B, T-blocks), innermost
sequential with m/l/acc scratch; lengths ride as scalar prefetch and the
c_lat/c_pe index maps CLAMP dead trailing blocks onto the last live one
(their compute is pl.when-skipped); f32 accumulation; decode-only (no
backward — serving path); interpret mode off-TPU so the CPU suite covers
the kernel logic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["mla_decode_attention", "mla_kernel_eligible"]

_NEG = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def mla_kernel_eligible(nh: int, r: int, dr: int) -> bool:
    """Lane-dim friendliness: the latent rank r is the contracting AND
    output lane dim (wants 128-multiples); dr only contracts (8 ok)."""
    return r % 128 == 0 and dr % 8 == 0 and nh >= 1


def _kernel(lens_ref, qe_ref, qp_ref, cl_ref, cp_ref, o_ref,
            acc_ref, m_ref, l_ref, *, block_t, scale):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    seq = lens_ref[b]

    @pl.when(j * block_t < seq)
    def _compute():
        qe = qe_ref[0]                                 # [nh, r]
        qp = qp_ref[0]                                 # [nh, dr]
        cl = cl_ref[0]                                 # [Tb, r]
        cp = cp_ref[0]                                 # [Tb, dr]
        s = (jax.lax.dot_general(
                qe, cl, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
             + jax.lax.dot_general(
                qp, cp, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)) * scale   # [nh, Tb]
        pos = j * block_t + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        masked = pos >= seq
        s = jnp.where(masked, _NEG, s)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(masked, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, -1, keepdims=True)
        # the SAME cl tile feeds the output accumulation — this is the
        # single-read fusion the XLA path cannot express. Rows past seq
        # must be ZEROED, not just given p=0: a tail block that overruns
        # T holds uninitialized data, and 0 * NaN would poison the dot.
        rowdead = (j * block_t + jax.lax.broadcasted_iota(
            jnp.int32, (cl.shape[0], 1), 0)) >= seq
        cl_v = jnp.where(rowdead, jnp.zeros_like(cl), cl)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(cl.dtype), cl_v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(j == nj - 1)
    def _emit():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_t"))
def mla_decode_attention(q_eff, q_pe, c_lat, c_pe, lengths, *,
                         scale: float, block_t: int = 1024):
    """One MLA decode step over the absorbed latent cache.

    q_eff  [B, nh, r]  — q_nope with W_uk absorbed (latent-space query)
    q_pe   [B, nh, dr] — rope-rotated positional query
    c_lat  [B, T, r]   — normalized latent cache (doubles as K-nope & V)
    c_pe   [B, T, dr]  — rope key cache (shared across heads)
    lengths[B] int32   — valid tokens per sequence (mask + block clamp)
    Returns the softmax-weighted latent read-out, [B, nh, r].
    """
    B, nh, r = q_eff.shape
    dr = q_pe.shape[-1]
    T = c_lat.shape[1]
    block_t = min(block_t, T)
    nj = -(-T // block_t)
    lens = lengths.astype(jnp.int32)

    def live_map(b, j, lens_ref):
        # clamp trailing dead blocks onto the last live one — their DMA
        # re-reads hot data instead of dead cache, compute is skipped
        last = jnp.maximum((lens_ref[b] + block_t - 1) // block_t - 1, 0)
        return (b, jnp.minimum(j, last), 0)

    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        functools.partial(_kernel, block_t=block_t, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nj),
            in_specs=[
                pl.BlockSpec((1, nh, r), lambda b, j, L: (b, 0, 0)),
                pl.BlockSpec((1, nh, dr), lambda b, j, L: (b, 0, 0)),
                pl.BlockSpec((1, block_t, r), live_map),
                pl.BlockSpec((1, block_t, dr), live_map),
            ],
            out_specs=pl.BlockSpec((1, nh, r), lambda b, j, L: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((nh, r), jnp.float32),
                pltpu.VMEM((nh, 1), jnp.float32),
                pltpu.VMEM((nh, 1), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, nh, r), c_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(lens, q_eff, q_pe, c_lat, c_pe)


# certification (ROADMAP item 5 / paddlelint PK105)
from .oracles import register_oracle  # noqa: E402

register_oracle(
    "mla_decode_attention", kernel=mla_decode_attention,
    reference="paddle_tpu.ops.references:mla_decode_reference",
    parity_test="tests/test_pallas_mla.py::TestKernelParity")
