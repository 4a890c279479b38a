"""Attention kernels.

Reference parity: paddle/phi/kernels/gpu/flash_attn_kernel.cu (FlashAttention2
fwd/bwd) and python/paddle/nn/functional/flash_attention.py. On TPU the fused
path defaults to the IN-TREE authored Pallas flash kernel
(ops/pallas_flash.py — causal incl. unequal Sq/Sk, segment ids, tunable
blocks); FLAGS_flash_impl selects 'bundled'
(jax.experimental.pallas.ops.tpu.flash_attention) or 'composite' instead.
This module always provides `sdpa_reference`, the XLA composite that (a) is
the correctness oracle for the Pallas kernels per SURVEY §4.1, and (b) is
already MXU-efficient for moderate sequence lengths because XLA fuses the
softmax chain.

Layout convention (paddle): [batch, seq, num_heads, head_dim].
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .. import observability as _obs

__all__ = ["sdpa_reference", "flash_attention", "sdpa_path"]

# per-kernel dispatch counters (ISSUE 1). Inside a jit trace each site
# counts once per compile, eagerly once per call — either way the label
# answers "which implementation did this config actually route to".
_KERNEL = _obs.registry().counter(
    "pt_kernel_launch_total",
    "fused-kernel dispatches by implementation route", labels=("kernel",))


def _count_kernel(kernel: str) -> None:
    if _obs.enabled():
        _KERNEL.labels(kernel=kernel).inc()


# what the causal mask leaves of an in-tree flash launch's (query block,
# key block) pairs, from the launch's own visit table (ops/pallas_flash.py)
# as it is traced: `interior` pairs are grid steps with no mask arithmetic,
# `masked` ones pay it, `skipped` ones are no grid step and no DMA.
_BLOCK_PAIRS = _obs.registry().counter(
    "pt_flash_block_pairs_total",
    "in-tree flash (query block, key block) pairs by sweep and kind",
    labels=("kernel", "kind"))


def count_block_pairs(kernel: str, pairs: dict) -> None:
    if _obs.enabled():
        for kind, n in pairs.items():
            _BLOCK_PAIRS.labels(kernel=kernel, kind=kind).inc(n)


def sdpa_reference(q, k, v, mask=None, causal: bool = False,
                   dropout_p: float = 0.0, scale: Optional[float] = None,
                   window: Optional[int] = None):
    """[B,S,H,D] scaled-dot-product attention, bf16-safe (f32 softmax).
    `window` W (with `causal`): query i sees keys j with i - W < j <= i."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if scale is None:
        scale = D ** -0.5
    qh = jnp.swapaxes(q, 1, 2)  # [B,H,S,D]
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    logits = logits.astype(jnp.float32)
    if causal:
        cm = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        if window is not None:
            cm &= ~jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq - window)
        logits = jnp.where(cm, logits, jnp.asarray(-1e30, logits.dtype))
    if mask is not None:
        m = jnp.asarray(mask)
        # only BOOL (B,Sk) masks are key-padding (matching the fused
        # _as_key_padding gate); a float (Sq,Sk) additive mask with
        # B == Sq must keep its broadcast meaning
        if m.ndim == 2 and m.shape == (B, Sk) and m.dtype == jnp.bool_:
            m = m[:, None, None, :]
        if m.dtype == jnp.bool_:
            logits = jnp.where(m, logits, jnp.asarray(-1e30, logits.dtype))
        else:
            logits = logits + m.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0:
        from ..framework.random import next_key
        keep = jax.random.bernoulli(next_key(), 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p),
                          jnp.zeros((), probs.dtype))
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)  # back to [B,S,H,D]


def _tpu_flash_available() -> bool:
    return jax.default_backend() == "tpu"


def _largest_dividing_block(S: int) -> int:
    """Largest multiple-of-128 block <= 512 that divides S (kernel contract:
    seq must be divisible by the chosen block)."""
    for b in (512, 384, 256, 128):
        if S % b == 0:
            return b
    return 0


def _flash_block_sizes(Sq: int, Sk: int):
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes
    bq = _largest_dividing_block(Sq)
    bk = _largest_dividing_block(Sk)
    return BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk,
        block_k_dkv=bk, block_q_dkv=bq,
        block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq)


def _flash_impl() -> str:
    """FLAGS_flash_impl: 'intree' (default; ops/pallas_flash.py) /
    'bundled' / 'composite'."""
    from ..flags import flag
    return flag("FLAGS_flash_impl")


def _flash_eligible(q, k, causal: bool = False) -> bool:
    """Pallas-kernel eligibility gate for the selected impl: TPU backend,
    block-divisible seq lengths, MXU-friendly head dim. The in-tree
    kernel accepts causal Sq != Sk (bottom-right aligned); the bundled
    kernel's causal offset assumes aligned diagonals, so unequal lengths
    are only eligible under FLAGS_flash_impl='intree'."""
    impl = _flash_impl()
    if impl == "composite":
        return False
    D = q.shape[-1]
    if causal and q.shape[1] != k.shape[1] and impl != "intree":
        return False
    return (_tpu_flash_available()
            and _largest_dividing_block(q.shape[1]) > 0
            and _largest_dividing_block(k.shape[1]) > 0
            and ((D <= 128 and D % 64 == 0) or D % 128 == 0))


def _as_key_padding(mask, B, Sq, Sk):
    """If `mask` is a boolean KEY mask ([B,Sk], [B,1,Sk] or [B,1,1,Sk]),
    return it as [B,Sk] bool; else None. This is the shape every padded
    fine-tune batch produces — routable to the fused segment-id kernel
    instead of the O(S^2) composite. ([B,1,Sq,Sk] masks are not
    detected: whether their rows are identical is runtime data.)"""
    m = jnp.asarray(mask)
    if m.dtype != jnp.bool_:
        return None
    if m.shape == (B, Sk):
        return m
    if m.shape in ((B, 1, Sk), (B, 1, 1, Sk)):
        return m.reshape(B, Sk)
    return None  # [B,1,Sq,Sk] forms can't be shape-checked as padding


def sdpa_path(q, k, mask=None, causal: bool = False,
              dropout_p: float = 0.0) -> str:
    """Which implementation `sdpa` will take for this config — so tests
    and users can ASSERT the fused kernel is actually hit ("flash",
    "flash_segmented", or "composite"). Mirrors sdpa's routing exactly."""
    B, Sq = q.shape[0], q.shape[1]
    Sk = k.shape[1]
    if dropout_p != 0.0 or not _flash_eligible(q, k, causal):
        return "composite"
    if mask is None:
        return "flash"
    if _as_key_padding(mask, B, Sq, Sk) is not None:
        return "flash_segmented"
    return "composite"


def sdpa(q, k, v, mask=None, causal: bool = False, dropout_p: float = 0.0,
         scale: Optional[float] = None, window: Optional[int] = None):
    """Routing SDPA on raw [B,S,H,D] arrays: Pallas flash kernel on TPU
    (ref parity: FlashAttnKernel, paddle/phi/kernels/gpu/flash_attn_kernel.cu
    — here the fused device kernel is the in-tree Pallas TPU flash attention
    rather than a .cu file), XLA composite elsewhere. The XLA composite
    (`sdpa_reference`) is the correctness oracle per SURVEY §4.1.

    Boolean key-padding masks route through the fused segment-id kernel
    (masked keys get segment 0, every query row segment 1) — NOT the
    composite; all query rows match the composite's semantics (masked
    keys are excluded for everyone).

    `window` W (static, with `causal`: query i sees keys j with
    i - W < j <= i) is a band of the in-tree kernel's block-pair table;
    the bundled and the segmented paths have none and give way to the
    composite."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if scale is None:
        scale = D ** -0.5
    if window is not None and not causal:
        raise ValueError("sdpa: a window needs causal=True")
    path = sdpa_path(q, k, mask=mask, causal=causal, dropout_p=dropout_p)
    if window is not None and not (path == "flash"
                                   and _flash_impl() == "intree"):
        path = "composite"
    if path == "flash":
        if _flash_impl() == "intree":
            _count_kernel("flash_intree")
            from .pallas_flash import flash_sdpa
            return flash_sdpa(q, k, v, causal=causal, scale=scale,
                              block_q=_largest_dividing_block(Sq),
                              block_k=_largest_dividing_block(Sk),
                              window=window)
        _count_kernel("flash_bundled")
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as _pallas_flash)
        from .on_mesh import on_mesh

        def bundled(q, k, v):
            out = _pallas_flash(
                jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),  # [B,H,S,D]
                jnp.swapaxes(v, 1, 2), causal=causal, sm_scale=scale,
                block_sizes=_flash_block_sizes(Sq, Sk))
            return jnp.swapaxes(out, 1, 2)
        # the bundled kernel owns its custom_vjp, so autodiff transposes
        # this shard_map: fine under GSPMD, not inside the pp region
        return on_mesh(bundled, (q, k, v), ("bshd",) * 3, "bshd")
    if path == "flash_segmented":
        _count_kernel("flash_segmented")
        pad = _as_key_padding(mask, B, Sq, Sk)
        seg_kv = pad.astype(jnp.int32)
        # every QUERY row keeps segment 1: a key mask excludes keys for
        # ALL queries (composite semantics) — tying seg_q to the mask
        # would make masked-position queries attend ONLY excluded keys
        seg_q = jnp.ones((B, Sq), jnp.int32)
        return sdpa_segmented(q, k, v, seg_q, kv_segment_ids=seg_kv,
                              causal=causal, scale=scale)
    _count_kernel("sdpa_composite")
    if mask is not None:
        pad = _as_key_padding(mask, B, Sq, Sk)
        if pad is not None:  # normalize [B,Sk] forms for broadcasting
            mask = pad[:, None, None, :]
    return sdpa_reference(q, k, v, mask=mask, causal=causal,
                          dropout_p=dropout_p, scale=scale, window=window)


def sdpa_prefill(q, k, v, *, causal: bool = True,
                 scale: Optional[float] = None,
                 pad_to_flash_min: int = 1024):
    """Prefill-shaped SDPA ([B,S,H,D], self-attention, no mask). `sdpa`
    silently falls back to the O(S^2) f32 composite whenever S is not
    block-divisible (a 12289-token prompt misses the flash gate by one
    token); here the window is zero-padded to the next 128-multiple and
    routed through the segment-id flash kernel — real tokens segment 1,
    padding segment 0. Numerically exact: causal + same-segment masking
    means no real query row ever attends a padded key, and the padded
    output rows are sliced off. Prompts shorter than `pad_to_flash_min`
    (or already divisible, or flash-ineligible configs) take the plain
    `sdpa` route unchanged."""
    B, S, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    Sp = -(-S // 128) * 128
    if (Sp == S or S < pad_to_flash_min
            or k.shape[1] != S
            or not _tpu_flash_available()
            or _flash_impl() == "composite"
            or not ((D <= 128 and D % 64 == 0) or D % 128 == 0)):
        return sdpa(q, k, v, causal=causal, scale=scale)
    pad = [(0, Sp - S) if i == 1 else (0, 0) for i in range(4)]
    qp = jnp.pad(q, pad)
    kp = jnp.pad(k, pad)
    vp = jnp.pad(v, pad)
    seg = jnp.broadcast_to(
        (jnp.arange(Sp) < S).astype(jnp.int32)[None, :], (B, Sp))
    _count_kernel("flash_prefill_padded")
    out = sdpa_segmented(qp, kp, vp, seg, causal=causal, scale=scale)
    return out[:, :S]


def sdpa_padded_heads(q, k, v, *, causal: bool = True,
                      scale: Optional[float] = None):
    """SDPA for MLA-geometry heads where the q/k head dim differs from
    the v head dim (DeepSeek: dn+dr=192 vs dv=128) and neither is
    lane-aligned for the flash gate. Zero-pads q/k AND v to the next
    128-multiple — exactly score- and output-preserving (padded q/k dims
    contribute 0 to every logit; padded v dims emit 0s that are sliced
    off) — so the O(S) flash kernel applies instead of the O(S^2) f32
    score composite that OOMs long-context prefill. The scale MUST be
    the caller's true 1/sqrt(d_qk); the default uses q's unpadded dim."""
    D, Dv = q.shape[-1], v.shape[-1]
    if scale is None:
        scale = D ** -0.5
    Dp = -(-max(D, Dv) // 128) * 128
    if D != Dp:
        pad = [(0, 0)] * (q.ndim - 1) + [(0, Dp - D)]
        q, k = jnp.pad(q, pad), jnp.pad(k, pad)
    if Dv != Dp:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, Dp - Dv)])
    # prefill route: also rescues non-128-multiple prompt lengths (pads
    # the seq dim through the segment-id kernel) — MLA long-context
    # prefill hits both misalignments at once
    out = sdpa_prefill(q, k, v, causal=causal, scale=scale)
    return out[..., :Dv]


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, name=None):
    """paddle.nn.functional.flash_attention.flash_attention parity wrapper."""
    from ..core.dispatch import apply
    def impl(q, k, v):
        return sdpa(q, k, v, causal=causal, dropout_p=dropout)
    out = apply("flash_attention", impl, [query, key, value])
    return out, None  # (out, softmax) — softmax only materialized on request


# ---------------------------------------------------------------------------
# varlen (packed / unpadded) attention — ref parity:
# FlashAttnUnpaddedKernel (paddle/phi/kernels/gpu/flash_attn_kernel.cu) and
# paddle.nn.functional.flash_attention.flash_attn_unpadded. TPU-native
# mechanism: segment IDs into the Pallas flash kernel (same-segment
# blocks attend, cross-segment blocks are skipped) instead of cu_seqlens
# pointer arithmetic into a varlen CUDA kernel.
# ---------------------------------------------------------------------------
def sdpa_segmented(q, k, v, segment_ids, kv_segment_ids=None, causal=True,
                   scale=None, dropout_p: float = 0.0):
    """[B,S,H,D] with [B,S] int32 segment ids; rows attend only within
    their segment. kv_segment_ids defaults to segment_ids (self-attention).
    Pallas path on TPU, masked XLA composite elsewhere."""
    D = q.shape[-1]
    if scale is None:
        scale = D ** -0.5
    seg_q = segment_ids.astype(jnp.int32)
    seg_kv = (seg_q if kv_segment_ids is None
              else kv_segment_ids.astype(jnp.int32))
    if dropout_p == 0.0 and _flash_eligible(q, k, causal):
        if _flash_impl() == "intree":
            from .pallas_flash import flash_sdpa
            return flash_sdpa(q, k, v, causal=causal, scale=scale,
                              segment_ids_q=seg_q, segment_ids_kv=seg_kv,
                              block_q=_largest_dividing_block(q.shape[1]),
                              block_k=_largest_dividing_block(k.shape[1]))
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as _pallas_flash, SegmentIds)
        out = _pallas_flash(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2),
            segment_ids=SegmentIds(q=seg_q, kv=seg_kv),
            causal=causal, sm_scale=scale,
            block_sizes=_flash_block_sizes(q.shape[1], k.shape[1]))
        return jnp.swapaxes(out, 1, 2)
    same = seg_q[:, :, None] == seg_kv[:, None, :]  # [B,Sq,Sk]
    mask = same[:, None, :, :]
    return sdpa_reference(q, k, v, mask=mask, causal=causal, scale=scale,
                          dropout_p=dropout_p)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q=None, max_seqlen_k=None, scale=None,
                        dropout=0.0, causal=False, return_softmax=False,
                        name=None):
    """paddle.nn.functional.flash_attention.flash_attn_unpadded parity:
    packed [total_tokens, H, D] + cu_seqlens → per-sequence attention.
    cu_seqlens are converted to segment IDs (static total length)."""
    from ..core.dispatch import apply as _apply

    def impl(q, k, v, cu_q, cu_k):
        # segment id of token t = number of sequence starts <= t
        seg_q = jnp.searchsorted(cu_q, jnp.arange(q.shape[0]),
                                 side="right").astype(jnp.int32)
        seg_k = jnp.searchsorted(cu_k, jnp.arange(k.shape[0]),
                                 side="right").astype(jnp.int32)
        out = sdpa_segmented(q[None], k[None], v[None], seg_q[None],
                             kv_segment_ids=seg_k[None], causal=causal,
                             scale=scale, dropout_p=dropout)
        return out[0]
    out = _apply("flash_attn_unpadded", impl,
                 [query, key, value, cu_seqlens_q, cu_seqlens_k])
    return out, None


# ---------------------------------------------------------------------------
# FlashMask — ref parity: FlashMask sparse-mask attention (flashmask_
# attention in paddle.nn.functional.flash_attention; SURVEY §5.7 item 1).
# The mask is described per key column by start/end row indices instead of
# a dense [S,S] bool tensor; memory is O(S) not O(S^2).
# ---------------------------------------------------------------------------
def flashmask_attention(query, key, value, startend_row_indices,
                        dropout=0.0, causal=False, name=None):
    """startend_row_indices: [B, Hm, S_k, C] int32, Hm in {1, H}
    (paddle's FlashMask column encoding):
      causal, C=1: LTS — key j masked for query rows i >= start[j].
      causal, C=2: [LTStart, LTEnd] — masked for start[j] <= i < end[j].
      non-causal, C=2: [LTStart, UTEnd] — masked for i >= lt_start[j]
        (lower triangle) OR i < ut_end[j] (upper triangle).
      non-causal, C=4: [LTStart, LTEnd, UTStart, UTEnd] — masked inside
        either band.
    Block-divisible shapes (and dropout=0) run the in-tree Pallas
    block-skipping kernel (ops/pallas_flashmask.py): O(S) mask memory
    end-to-end, fully-masked key blocks skipped on the MXU, flash-style
    backward. Other shapes fall back to a row-index comparison mask into
    the f32-softmax composite.
    """
    from ..core.dispatch import apply as _apply
    from .pallas_flashmask import flashmask_kernel_eligible, flashmask_sdpa

    def impl(q, k, v, se):
        B, Sq, H, D = q.shape
        Sk = k.shape[1]
        if dropout == 0.0 and flashmask_kernel_eligible(Sq, Sk, D):
            return flashmask_sdpa(q, k, v, se, causal=causal)
        rows = jnp.arange(Sq, dtype=jnp.int32)[:, None]      # [Sq,1]
        C = se.shape[-1]
        se_b = se  # [B,Hm,Sk,C]
        def band(lo, hi):
            # masked-out where lo[j] <= i < hi[j]
            return jnp.logical_and(rows >= lo[..., None, :],
                                   rows < hi[..., None, :])
        if C == 1:
            if not causal:
                raise ValueError("C=1 FlashMask (LTS) requires causal=True")
            masked = rows >= se_b[..., 0][..., None, :]
        elif C == 2 and causal:
            masked = band(se_b[..., 0], se_b[..., 1])
        elif C == 2:
            # [LTStart, UTEnd]: lower triangle from lt_start down, upper
            # triangle above ut_end
            masked = jnp.logical_or(
                rows >= se_b[..., 0][..., None, :],
                rows < se_b[..., 1][..., None, :])
        elif C == 4:
            if causal:
                raise ValueError("C=4 FlashMask requires causal=False")
            masked = jnp.logical_or(band(se_b[..., 0], se_b[..., 1]),
                                    band(se_b[..., 2], se_b[..., 3]))
        else:
            raise ValueError(f"startend_row_indices last dim must be "
                             f"1, 2 or 4, got {C}")
        allow = jnp.logical_not(masked)  # [B,Hm,Sq,Sk]
        return sdpa_reference(q, k, v, mask=allow, causal=causal,
                              dropout_p=dropout)
    out = _apply("flashmask_attention", impl,
                 [query, key, value, startend_row_indices])
    return out, None


__all__ += ["sdpa_segmented", "sdpa_prefill", "flash_attn_unpadded",
            "flashmask_attention"]
