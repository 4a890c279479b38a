"""Ragged mixed prefill+decode paged-attention kernel.

Reference capability: Ragged Paged Attention (arXiv 2604.15464) — ONE
`pallas_call` serves a mixed batch of prefill chunks and decode tokens
over the paged KV cache, replacing the engine's alternating
`_prefill_chunk` / `_decode` dispatches.

Layout: the step's new tokens ride in a FLAT buffer q [T, H, D] with
per-sequence row tables as scalar prefetch:

  - seq_start [S]:  first flat row of sequence i's new tokens;
  - num_tokens [S]: how many new tokens sequence i contributes this step
    (1 for a decode slot, the chunk length for a prefill row, 0 for an
    inactive slot — its rows emit zeros);
  - kv_lengths [S]: sequence i's KV length INCLUDING its new tokens
    (append-then-attend: the new K/V rows are already in the pages);
  - page_tables [S, pages_per_seq]: physical pages, sentinel entries
    clamped like pallas_paged._page_map.

Causality is per sequence over its new tokens: local token t (0-based)
attends KV positions 0 .. kv_lengths[i] - num_tokens[i] + t. A decode
row (num_tokens=1) therefore sees its whole context; a prefill chunk is
causal within the chunk and sees everything before it (shared-prefix
pages included).

Same machinery family as pallas_paged.py: grid (KV, S, pages), page
gather through the BlockSpec index_map (never materialized), GQA-native
[T*rep, D] query groups per KV head, online-softmax f32 scratch,
pl.when skips for dead pages/slots, interpret mode off-TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_paged import paged_kernel_eligible

__all__ = ["ragged_paged_attention", "ragged_attention_reference",
           "ragged_kernel_eligible"]

_NEG = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def ragged_kernel_eligible(H: int, KV: int, D: int,
                           page_size: int) -> bool:
    """Same tiling constraints as the decode kernel: the [rows, D] query
    group wants MXU-friendly D; any page_size >= 8 works (masks handle
    partial pages and ragged chunk tails)."""
    return paged_kernel_eligible(H, KV, D, page_size)


def _ragged_page_map(h, i, j, ss, nt, kvl, tab, *, page_size,
                     total_pages):
    # clamp j to the last LIVE page of sequence i and the table value to
    # a real physical page: dead pages then re-reference the previous
    # block (Pallas elides the copy) and sentinel/-1 entries never emit
    # an out-of-range DMA, even though compute is pl.when-skipped
    jmax = jnp.maximum(kvl[i] - 1, 0) // page_size
    phys = jnp.clip(tab[i, jnp.minimum(j, jmax)], 0, total_pages - 1)
    return (h, phys, 0, 0)


def _ragged_kernel(ss_ref, nt_ref, kvl_ref, tab_ref,    # scalar prefetch
                   q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, page_size, rep, scale):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    # the whole [T*rep, D] output block stays resident for one KV head's
    # full (i, j) sweep; zero it once so inactive rows read as zeros and
    # each sequence's emit only merges its own rows
    @pl.when((i == 0) & (j == 0))
    def _zero_out():
        o_ref[:] = jnp.zeros_like(o_ref)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    start = ss_ref[i]
    nt = nt_ref[i]
    kvl = kvl_ref[i]
    rows = q_ref.shape[1]
    # flat token index of each query row ([T*rep, 1]: rep query heads of
    # one token are adjacent rows of the same KV head's group)
    tok = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // rep
    row_valid = (tok >= start) & (tok < start + nt)

    @pl.when((nt > 0) & (j * page_size < kvl))
    def _compute():
        q = q_ref[0]                                     # [T*rep, D]
        k = k_ref[0, 0]                                  # [psz, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [T*rep, psz]
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        # local token t of this sequence attends positions <= limit
        limit = kvl - nt + (tok - start)
        masked = jnp.logical_not(row_valid & (pos <= limit))
        s = jnp.where(masked, _NEG, s)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(masked, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(j == nj - 1)
    def _emit():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        vals = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        o_ref[0] = jnp.where(row_valid, vals, o_ref[0])


def ragged_paged_attention(q, k_pages, v_pages, seq_start, num_tokens,
                           kv_lengths, page_tables,
                           scale: Optional[float] = None):
    """q [T, H, D] flat new-token buffer; k/v_pages [KV, total_pages,
    page_size, D]; seq_start/num_tokens/kv_lengths [S] int32;
    page_tables [S, pages_per_seq] int32. Sequences own DISJOINT row
    ranges [seq_start[i], seq_start[i]+num_tokens[i]); rows covered by
    no sequence return zeros. Returns [T, H, D].

    VMEM residency note: the whole [T*rep, D] query group and output
    block of one KV head stay resident across that head's page sweep —
    T is an engine-step batch (max_slots + prefill_chunk), not a full
    sequence, so the block is small by construction."""
    T, H, D = q.shape
    KV, total, psz, _ = k_pages.shape
    rep = H // KV
    S, nj = page_tables.shape
    if scale is None:
        scale = D ** -0.5
    # [T, H, D] -> [KV, T*rep, D]: one grid cell owns one KV head's
    # whole flat query group (rep rows per token, token-major)
    qg = (q.reshape(T, KV, rep, D).transpose(1, 0, 2, 3)
          .reshape(KV, T * rep, D))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # seq_start, num_tokens, kv_lengths,
        grid=(KV, S, nj),           # page tables
        in_specs=[
            pl.BlockSpec((1, T * rep, D),
                         lambda h, i, j, ss, nt, kvl, tab: (h, 0, 0)),
            pl.BlockSpec((1, 1, psz, D), functools.partial(
                _ragged_page_map, page_size=psz, total_pages=total)),
            pl.BlockSpec((1, 1, psz, D), functools.partial(
                _ragged_page_map, page_size=psz, total_pages=total)),
        ],
        out_specs=pl.BlockSpec(
            (1, T * rep, D),
            lambda h, i, j, ss, nt, kvl, tab: (h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((T * rep, D), jnp.float32),
                        pltpu.VMEM((T * rep, 1), jnp.float32),
                        pltpu.VMEM((T * rep, 1), jnp.float32)],
    )
    # i is sequential ("arbitrary"): every sequence read-modify-writes
    # the same resident output block
    cparams = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"))
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, page_size=psz, rep=rep,
                          scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KV, T * rep, D), q.dtype),
        compiler_params=cparams,
        interpret=_interpret(),
    )(seq_start.astype(jnp.int32), num_tokens.astype(jnp.int32),
      kv_lengths.astype(jnp.int32), page_tables.astype(jnp.int32),
      qg, k_pages, v_pages)
    return (out.reshape(KV, T, rep, D).transpose(1, 0, 2, 3)
            .reshape(T, H, D))


def ragged_attention_reference(q, k_pages, v_pages, seq_start,
                               num_tokens, kv_lengths, page_tables,
                               scale: Optional[float] = None):
    """Plain-XLA oracle with the same ragged semantics (full-softmax,
    gathered pages, jnp.repeat GQA — everything the kernel avoids)."""
    T, H, D = q.shape
    KV, total, psz, _ = k_pages.shape
    rep = H // KV
    S, nj = page_tables.shape
    if scale is None:
        scale = D ** -0.5
    ss = seq_start.astype(jnp.int32)
    nt = num_tokens.astype(jnp.int32)
    kvl = kv_lengths.astype(jnp.int32)
    tabs = jnp.clip(page_tables.astype(jnp.int32), 0, total - 1)
    Tk = nj * psz
    ks = k_pages[:, tabs].transpose(1, 0, 2, 3, 4).reshape(S, KV, Tk, D)
    vs = v_pages[:, tabs].transpose(1, 0, 2, 3, 4).reshape(S, KV, Tk, D)
    kr = jnp.repeat(ks, rep, axis=1)                      # [S, H, Tk, D]
    vr = jnp.repeat(vs, rep, axis=1)
    logits = jnp.einsum("thd,shld->shtl", q.astype(jnp.float32),
                        kr.astype(jnp.float32)) * scale   # [S,H,T,Tk]
    t_idx = jnp.arange(T)
    rv = (t_idx[None, :] >= ss[:, None]) & \
        (t_idx[None, :] < (ss + nt)[:, None])             # [S, T]
    limit = (kvl - nt)[:, None] + (t_idx[None, :] - ss[:, None])
    pos = jnp.arange(Tk)
    mask = rv[:, None, :, None] & \
        (pos[None, None, None, :] <= limit[:, None, :, None])
    logits = jnp.where(mask, logits, _NEG)
    m = jnp.max(logits, -1, keepdims=True)
    p = jnp.where(mask, jnp.exp(logits - m), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    o = jnp.einsum("shtl,shld->shtd", p / jnp.where(l == 0.0, 1.0, l),
                   vr.astype(jnp.float32))                # [S, H, T, D]
    out = jnp.sum(jnp.where(rv[:, None, :, None], o, 0.0), axis=0)
    return out.transpose(1, 0, 2).astype(q.dtype)


# certification (ROADMAP item 5 / paddlelint PK105)
from .oracles import register_oracle  # noqa: E402

register_oracle(
    "ragged_paged_attention", kernel=ragged_paged_attention,
    reference=ragged_attention_reference,
    parity_test="tests/test_ragged_kernel.py::TestRaggedKernelParity")
