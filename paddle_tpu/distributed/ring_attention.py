"""Context parallelism: ring flash attention + Ulysses all-to-all attention.

Reference capability (SURVEY §2.3 P8/P9, §5.7):
- Ring attention: PaddleNLP RingFlashAttention — a PyLayer that p2p-rotates
  KV blocks around the cp group with online-softmax accumulation
  (context_parallel_degree in llm/run_pretrain.py).
- Ulysses "sep": segment-parallel all-to-all swapping seq-shard <-> head-shard
  around attention (DeepSpeed-Ulysses pattern,
  fleet/meta_parallel/segment_parallel.py).

TPU-native rework: both are single compiled shard_map programs on the `sep`
mesh axis. The KV rotation is `jax.lax.ppermute` riding ICI (the NCCL
send/recv ring becomes a collective-permute XLA schedules and overlaps with
the per-block attention compute); Ulysses is two `lax.all_to_all`s. No actor
runtime, no handshakes — the schedule is in the program.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.dispatch import apply
from ..core.tensor import Tensor
from .mesh import get_mesh

__all__ = ["ring_attention", "ring_attention_raw", "ulysses_attention",
           "RingFlashAttention", "split_for_context_parallel"]


def _block_update(q, k, v, o, m, l, scale, mask=None):
    """One online-softmax block accumulation step (flash-attention update).
    q [B,Sq,H,D], k/v [B,Sk,H,D]; o [B,Sq,H,D]; m,l [B,Sq,H]."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale       # [B,H,Sq,Sk]
    if mask is not None:
        s = jnp.where(mask, s, jnp.asarray(-1e30, s.dtype))
    m_blk = jnp.max(s, axis=-1)                            # [B,H,Sq]
    m_blk = jnp.moveaxis(m_blk, 1, -1)                     # [B,Sq,H]
    m_new = jnp.maximum(m, m_blk)
    # p in [B,H,Sq,Sk]
    p = jnp.exp(s - jnp.moveaxis(m_new, -1, 1)[..., None])
    corr = jnp.exp(m - m_new)                              # [B,Sq,H]
    l_new = l * corr + jnp.moveaxis(jnp.sum(p, axis=-1), 1, -1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    o_new = o * corr[..., None] + pv
    return o_new, m_new, l_new


def _ring_body(q, k, v, *, axis: str, n: int, causal: bool, scale: float):
    """shard_map body: q/k/v are the local seq shards [B, S/n, H, D]."""
    my = jax.lax.axis_index(axis)
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    o = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full((B, Sq, H), -1e30, jnp.float32)
    l = jnp.zeros((B, Sq, H), jnp.float32)
    qf = q.astype(jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]  # pass KV to the next rank

    def step(i, carry):
        o, m, l, kc, vc = carry
        src = (my - i) % n  # which rank's KV block we now hold
        if causal:
            # block-level: src > my fully masked; src == my causal; else full
            qpos = my * Sq + jnp.arange(Sq)
            kpos = src * Sk + jnp.arange(Sk)
            mask = (kpos[None, :] <= qpos[:, None])[None, None]
        else:
            mask = None
        o2, m2, l2 = _block_update(qf, kc.astype(jnp.float32),
                                   vc.astype(jnp.float32), o, m, l, scale,
                                   mask)
        kn = jax.lax.ppermute(kc, axis, perm)
        vn = jax.lax.ppermute(vc, axis, perm)
        return o2, m2, l2, kn, vn

    o, m, l, _, _ = jax.lax.fori_loop(0, n, step, (o, m, l, k, v))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_attention_raw(qa, ka, va, *, axis: str = "sep",
                       causal: bool = False, scale: Optional[float] = None,
                       mesh=None):
    """Raw-array ring attention (for use inside other ops' impls, e.g. the
    Llama attention path under context parallelism)."""
    mesh = mesh or get_mesh()
    scale = scale if scale is not None else qa.shape[-1] ** -0.5
    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return _dense(qa, ka, va, causal, scale)
    n = mesh.shape[axis]
    body = partial(_ring_body, axis=axis, n=n, causal=causal, scale=scale)
    spec = P(None, axis, None, None)
    return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(qa, ka, va)


def ring_attention(q, k, v, *, axis: str = "sep", causal: bool = False,
                   scale: Optional[float] = None, mesh=None):
    """Ring flash attention over the context axis.

    q/k/v: [B, S, H, D] GLOBAL tensors (or Tensor wrappers). The seq dim is
    sharded on `axis` by shard_map; output is the full attention result,
    exact (online softmax), with KV rotating n-1 hops around the ring.
    Degrades to plain attention when the mesh/axis is absent.
    """
    mesh = mesh or get_mesh()
    arrs = [x._data if isinstance(x, Tensor) else jnp.asarray(x)
            for x in (q, k, v)]
    D = arrs[0].shape[-1]
    scale = scale if scale is not None else D ** -0.5

    def impl(qa, ka, va):
        return ring_attention_raw(qa, ka, va, axis=axis, causal=causal,
                                  scale=scale, mesh=mesh)

    return apply("ring_attention", impl, [q, k, v])


def _dense(q, k, v, causal, scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Sq, Sk), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def ulysses_attention(q, k, v, *, axis: str = "sep", causal: bool = False,
                      scale: Optional[float] = None, mesh=None):
    """DeepSpeed-Ulysses: all-to-all seq-shard <-> head-shard, full attention
    on the head shard, all-to-all back. Requires num_heads % axis_size == 0.
    q/k/v: [B, S, H, D] global tensors."""
    mesh = mesh or get_mesh()
    D = (q.shape if not isinstance(q, Tensor) else q.shape)[-1]
    scale = scale if scale is not None else D ** -0.5

    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] == 1:
        def impl(qa, ka, va):
            return _dense(qa, ka, va, causal, scale)
        return apply("ulysses_attention", impl, [q, k, v])

    n = mesh.shape[axis]
    spec = P(None, axis, None, None)

    def body(qa, ka, va):
        # local [B, S/n, H, D] -> [B, S, H/n, D]
        def to_heads(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        def to_seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)
        qh, kh, vh = to_heads(qa), to_heads(ka), to_heads(va)
        oh = _dense(qh, kh, vh, causal, scale)
        return to_seq(oh)

    def impl(qa, ka, va):
        return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(qa, ka, va)

    return apply("ulysses_attention", impl, [q, k, v])


class RingFlashAttention:
    """API-parity shim for PaddleNLP's RingFlashAttention PyLayer: call
    RingFlashAttention.apply(q, k, v, causal=...)."""

    @staticmethod
    def apply(q, k, v, attn_mask=None, causal=False, axis="sep"):
        if attn_mask is not None:
            raise NotImplementedError(
                "ring attention supports causal/full masks; arbitrary masks "
                "need the dense path")
        return ring_attention(q, k, v, axis=axis, causal=causal)


def split_for_context_parallel(x, axis: str = "sep", seq_dim: int = 1,
                               mesh=None):
    """Annotate the sequence dim as sharded on the context axis (the
    zig-zag/load-balance splitting of the reference is subsumed by the exact
    block-masked ring — every rank does the same block count)."""
    mesh = mesh or get_mesh()
    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return x
    from .auto_parallel import mark_sharding
    spec = [None] * (x.ndim if not isinstance(x, Tensor) else len(x.shape))
    spec[seq_dim] = axis
    return mark_sharding(x, *spec)
