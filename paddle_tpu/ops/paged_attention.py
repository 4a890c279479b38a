"""Paged / block KV-cache attention for serving decode.

Reference capability (SURVEY §2.1 fused kernels): BlockMultiheadAttention /
masked_multihead_attention (paged KV cache decoding kernels,
paddle/phi/kernels/fusion/gpu/block_multi_head_attention*).

TPU-native: `paged_attention` is the in-tree AUTHORED Pallas decode
kernel (ops/pallas_paged.py — scalar-prefetched page table, grouped
double-buffered page DMAs, online softmax, GQA-native query groups)
wherever the shapes tile (`paged_kernel_eligible`), and the gather-based
XLA reference otherwise; the reference is also the correctness oracle.
The serving engine does not come through here (its one step program
calls `ops.pallas_ragged.ragged_paged_attention`); this is the
`incubate.nn.functional.block_multihead_attention` surface.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .. import observability as _obs
from .flash_attention import _count_kernel

__all__ = ["paged_attention", "paged_attention_reference"]

# serving KV-cache visibility: fraction of allocated page capacity that
# holds live tokens, sampled at each EAGER paged-attention call (traced
# calls have abstract lengths and are skipped)
_KV_UTIL = _obs.registry().gauge(
    "pt_serving_kv_page_utilization",
    "mean(lengths) / (pages_per_seq * page_size) at the last eager call")


def _sample_kv_utilization(lengths, page_indices, page_size: int) -> None:
    if not _obs.enabled() or isinstance(lengths, jax.core.Tracer):
        return
    try:
        import numpy as np
        cap = page_indices.shape[1] * page_size
        if cap:
            _KV_UTIL.set(float(np.asarray(lengths).mean()) / cap)
    except Exception:
        pass  # metrics must never break the serving path


def paged_attention_reference(q, k_pages, v_pages, lengths, page_indices,
                              scale: Optional[float] = None):
    """Decode-step attention against a paged KV cache.

    q:            [B, H, D]           (one query token per sequence)
    k/v_pages:    [num_kv_heads, total_pages, page_size, D]
    lengths:      [B] int32           current KV length per sequence
    page_indices: [B, pages_per_seq]  page table
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    B, H, D = q.shape
    KV = k_pages.shape[0]
    page_size = k_pages.shape[2]
    pages_per_seq = page_indices.shape[1]
    rep = H // KV

    # gather each sequence's pages: [B, KV, pages_per_seq*page_size, D]
    def per_seq(pi):
        k = k_pages[:, pi]                      # [KV, pages, psize, D]
        v = v_pages[:, pi]
        return (k.reshape(KV, pages_per_seq * page_size, D),
                v.reshape(KV, pages_per_seq * page_size, D))
    ks, vs = jax.vmap(per_seq)(page_indices)

    if rep > 1:
        ks = jnp.repeat(ks, rep, axis=1)
        vs = jnp.repeat(vs, rep, axis=1)

    s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                   ks.astype(jnp.float32)) * scale
    pos = jnp.arange(pages_per_seq * page_size)
    mask = pos[None, None, :] < lengths[:, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhk,bhkd->bhd", p, vs.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention(q, k_pages, v_pages, lengths, page_indices,
                    scale: Optional[float] = None):
    """Paged decode attention, routed from the shapes: the in-tree
    grouped-DMA kernel (ops/pallas_paged.py) where they tile, the XLA
    gather composite otherwise."""
    from .pallas_paged import (paged_decode_attention_v2,
                               paged_kernel_eligible)
    H, D = q.shape[1], q.shape[2]
    KV, page_size = k_pages.shape[0], k_pages.shape[2]
    _sample_kv_utilization(lengths, page_indices, page_size)
    if paged_kernel_eligible(H, KV, D, page_size):
        _count_kernel("paged_intree")
        return paged_decode_attention_v2(q, k_pages, v_pages, lengths,
                                         page_indices, scale)
    _count_kernel("paged_reference")
    return paged_attention_reference(q, k_pages, v_pages, lengths,
                                     page_indices, scale)
