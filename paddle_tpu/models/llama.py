"""Llama model family (ref capability: PaddleNLP
paddlenlp/transformers/llama/modeling.py — the Llama-3-8B pretrain baseline,
SURVEY §2.4 config 2).

TPU-first design:
- weights carry Megatron-pattern sharding specs (qkv/up: column on mp;
  o/down: row on mp; embeddings: vocab on mp) — GSPMD derives the per-layer
  collectives the reference's ColumnParallelLinear/RowParallelLinear issue.
- between a row-parallel product and the next column-parallel one the
  activations are sequence-sharded on mp where the mesh and the length
  allow (P5; `distributed.parallel_layers.seq_sharded_on` chooses, no
  switch), and get a dp/fsdp batch constraint at the top.
- attention is GQA through scaled_dot_product_attention (flash-routable);
  rope is fused-ready (paddle_tpu.ops).
- fsdp (ZeRO-3) is a spec choice on the same weights (dim-0 on "sharding").
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..distributed.parallel_layers import (MP_AXIS,
                                           annotate_column_parallel,
                                           annotate_sequence_parallel,
                                           seq_layout_engages, seq_sharded,
                                           seq_whole)
from ..observability.attribution import keeps as _keeps, \
    residual as _residual, scope as _scope

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "llama3_8b_config", "llama_tiny_config", "apply_rope",
           "precompute_rope"]


class LlamaConfig:
    """`sequence_parallel` is INERT: the keyword is accepted and stored
    because callers still pass it (`benchmarks/systems/llama_pretrain.py`
    among them, which pinned it to False), and selects nothing — whether
    the activations between a row-parallel and the next column-parallel
    product are sequence-sharded on `mp` is chosen from the mesh and the
    sequence length (`distributed.parallel_layers.seq_sharded_on`), two
    layouts of the same mathematics not being a user's to pick."""

    def __init__(self, vocab_size=128256, hidden_size=4096,
                 intermediate_size=14336, num_hidden_layers=32,
                 num_attention_heads=32, num_key_value_heads=8,
                 max_position_embeddings=8192, rope_theta=500000.0,
                 rms_norm_eps=1e-5, initializer_range=0.02,
                 tie_word_embeddings=False, use_flash_attention=True,
                 sequence_parallel=True, recompute=False,
                 context_parallel=False, fuse_attention_qkv=False,
                 fuse_attention_ffn=False, fuse_pack_groups=1,
                 head_dim=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.max_position_embeddings = max_position_embeddings
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.tie_word_embeddings = tie_word_embeddings
        self.use_flash_attention = use_flash_attention
        self.sequence_parallel = sequence_parallel
        self.recompute = recompute
        self.context_parallel = context_parallel
        # PaddleNLP parity knobs: pack q/k/v (and gate/up) into single
        # matmuls — fewer kernel launches, one MXU pass over the activations
        self.fuse_attention_qkv = fuse_attention_qkv
        self.fuse_attention_ffn = fuse_attention_ffn
        # rank-interleave group count for the packed layouts. Set it to the
        # mp degree when training with TP so the packed q|k|v (and gate|up)
        # slice boundaries stay shard-local. An EXPLICIT config knob — not
        # sniffed from the ambient mesh — so rebuilding a model from the
        # same config always reproduces the same weight layout
        # (checkpoints are layout-compatible iff fuse_pack_groups matches).
        self.fuse_pack_groups = fuse_pack_groups
        # explicit head_dim decouples attention width from hidden_size —
        # needed to model a TP shard (heads/mp heads of the ORIGINAL
        # head_dim over the full hidden residual stream)
        self.head_dim = head_dim if head_dim is not None \
            else hidden_size // num_attention_heads


def llama3_8b_config(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


def llama3_8b_shard_config(mp: int = 8, pp: int = 4, **kw) -> LlamaConfig:
    """The per-chip model an mp×pp-partitioned Llama-3-8B places on ONE
    chip (ref: PaddleNLP llm/run_pretrain.py hybrid configs): layers/pp
    decoder layers whose attention holds heads/mp query heads (kv heads
    likewise, min 1) of the true head_dim 128, FFN width 14336/mp, and a
    vocab-parallel slice 128256/mp of the embedding/CE. Benchmarking this
    config single-chip measures the MXU efficiency of the flagship's
    per-chip computation (collectives excluded — accounted separately in
    docs/FLAGSHIP.md)."""
    full = llama3_8b_config()
    base = dict(
        vocab_size=full.vocab_size // mp,
        hidden_size=full.hidden_size,
        intermediate_size=full.intermediate_size // mp,
        num_hidden_layers=full.num_hidden_layers // pp,
        num_attention_heads=max(full.num_attention_heads // mp, 1),
        num_key_value_heads=max(full.num_key_value_heads // mp, 1),
        head_dim=full.head_dim,
        max_position_embeddings=full.max_position_embeddings,
        rope_theta=full.rope_theta)
    base.update(kw)
    return LlamaConfig(**base)


def llama_tiny_config(**kw) -> LlamaConfig:
    base = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=256,
                rope_theta=10000.0)
    base.update(kw)
    return LlamaConfig(**base)


def precompute_rope(head_dim: int, max_seq: int, theta: float):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # [S, D/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x, cos, sin):
    """x: [B, S, H, D] raw array; fused-rope parity
    (ref: fused_rotary_position_embedding / FusedRopeKernel)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[None, :x.shape[1], None, :].astype(x.dtype)
    s = sin[None, :x.shape[1], None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _mp_linear(in_f, out_f, spec):
    """Bias-free linear with a Megatron TP sharding spec attached."""
    l = nn.Linear(in_f, out_f, bias_attr=False)
    l.weight._sharding_spec = spec
    return l


def _init_packed_segments(weight, segments):
    """Re-initialize a packed [in, sum(widths)] weight per column segment.
    segments: [(width, logical_fan_out)] — each segment gets the Xavier std
    of the LOGICAL unfused projection it belongs to (q segments use fan
    H*D regardless of grouping), so flipping the fuse knobs is
    numerics-neutral at init (a single XavierNormal over the packed width
    would under-scale every segment)."""
    import math as _m
    in_f = weight.shape[0]
    dt = weight._data.dtype
    cols = []
    for w, fan_out in segments:
        std = _m.sqrt(2.0 / (in_f + fan_out))
        cols.append(I.Normal(0.0, std)([in_f, w], "float32"))
    weight._data = jnp.concatenate(cols, axis=1).astype(dt)


class LlamaAttention(nn.Layer):
    def __init__(self, c: LlamaConfig):
        super().__init__()
        self.c = c
        H, D = c.num_attention_heads, c.head_dim
        KV = c.num_key_value_heads
        if c.fuse_attention_qkv:
            # One packed projection, RANK-INTERLEAVED layout
            # [g blocks of (H/g q-heads | KV/g k-heads | KV/g v-heads)]
            # with g = cfg.fuse_pack_groups (set to the mp degree for TP):
            # the q|k|v slice boundaries then fall on shard boundaries, so
            # under tensor parallelism the slices stay shard-local
            # (Megatron's fused-qkv layout rationale — a column-major
            # [all-q|all-k|all-v] pack would force GSPMD to reshard
            # activations at every slice). Weights are framework-native
            # (not PaddleNLP-binary-compatible; a converter must re-pack).
            g = c.fuse_pack_groups
            if H % g or KV % g:
                raise ValueError(
                    f"fuse_attention_qkv requires heads divisible by "
                    f"fuse_pack_groups: H={H}, KV={KV}, groups={g}")
            self._qkv_groups = g
            self.qkv_proj = _mp_linear(c.hidden_size, (H + 2 * KV) * D,
                                       P(None, MP_AXIS))
            _init_packed_segments(
                self.qkv_proj.weight,
                [(H // g * D, H * D), (KV // g * D, KV * D),
                 (KV // g * D, KV * D)] * g)
        else:
            # Megatron TP: qkv column-sharded, o row-sharded on mp
            self.q_proj = _mp_linear(c.hidden_size, H * D, P(None, MP_AXIS))
            self.k_proj = _mp_linear(c.hidden_size, KV * D, P(None, MP_AXIS))
            self.v_proj = _mp_linear(c.hidden_size, KV * D, P(None, MP_AXIS))
        self.o_proj = _mp_linear(H * D, c.hidden_size, P(MP_AXIS, None))

    def forward(self, x, cos, sin, attn_mask=None, window=None):
        """`window` W (static): a sliding-window layer — query i sees
        keys j with i - W < j <= i — a band of the flash kernel's
        block-pair table, run under the scope `window_attention`."""
        c = self.c
        B, S, _ = x.shape
        H, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        from ..core.dispatch import apply as _apply
        from ..core.tensor import Tensor as _T
        # mask is data (non-diff): closed over, not a tape input. Boolean
        # key-padding masks route to the fused segment-id kernel in sdpa.
        mask_arr = attn_mask._data if isinstance(attn_mask, _T) \
            else (None if attn_mask is None else jnp.asarray(attn_mask))
        if mask_arr is not None and c.context_parallel:
            raise NotImplementedError(
                "attn_mask with context_parallel ring attention: pack "
                "sequences via sdpa_segmented/flashmask instead")
        if window is not None and c.context_parallel:
            raise NotImplementedError(
                "a sliding window with context_parallel ring attention")
        banded = {} if window is None else {"window": int(window)}

        def finish(q, k, v, wo):
            """rope → attention → output projection (shared tail)."""
            with _scope("attention") if window is None \
                    else jax.named_scope("window_attention"):
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
                rep = H // KV
                if rep > 1:
                    k = jnp.repeat(k, rep, axis=2)
                    v = jnp.repeat(v, rep, axis=2)
                from ..ops.flash_attention import sdpa, sdpa_reference
                if c.context_parallel:
                    # ring attention over the sep axis (P9): seq stays
                    # sharded, KV blocks rotate via collective-permute
                    from ..distributed.ring_attention import \
                        ring_attention_raw
                    o = ring_attention_raw(q, k, v, axis="sep", causal=True)
                elif c.use_flash_attention:
                    o = sdpa(q, k, v, mask=mask_arr, causal=True, **banded)
                else:
                    o = sdpa_reference(q, k, v, mask=mask_arr, causal=True,
                                       **banded)
            with _scope("attn_out"):
                # the row product's input is held to every row and this
                # chip's heads (and so is its cotangent: the backward
                # gathers the rows of d out, not the weight); its output
                # is constrained, then named: the kept residual is the
                # scattered [B, S/mp, H], not the replicated product
                return _residual(
                    seq_sharded(seq_whole(o.reshape(B, S, -1)) @ wo),
                    "attn_out")

        if c.fuse_attention_qkv:
            g = self._qkv_groups
            Hg, KVg = H // g, KV // g

            def impl(h, wqkv, wo):
                # [B,S,g,(Hg+2KVg),D]: dim 2 is the shard (rank) dim, so
                # the q|k|v slices below are shard-local under mp
                with _scope("qkv_proj"):
                    qkv = _residual(seq_whole(h @ wqkv), "qkv").reshape(
                        B, S, g, Hg + 2 * KVg, D)
                    q = qkv[:, :, :, :Hg].reshape(B, S, H, D)
                    k = qkv[:, :, :, Hg:Hg + KVg].reshape(B, S, KV, D)
                    v = qkv[:, :, :, Hg + KVg:].reshape(B, S, KV, D)
                # head order is group-major for q AND kv consistently, and
                # jnp.repeat on the flat kv axis maps q head (g_i, h_j) to
                # kv head (g_i, h_j // (Hg/KVg)) — GQA grouping preserved
                return finish(q, k, v, wo)
            return _apply("llama_attention", impl,
                          [x, self.qkv_proj.weight, self.o_proj.weight])

        def impl(h, wq, wk, wv, wo):
            with _scope("qkv_proj"):
                q = _residual(seq_whole(h @ wq), "qkv").reshape(B, S, H, D)
                k = _residual(seq_whole(h @ wk), "qkv").reshape(B, S, KV, D)
                v = _residual(seq_whole(h @ wv), "qkv").reshape(B, S, KV, D)
            return finish(q, k, v, wo)
        return _apply("llama_attention", impl,
                      [x, self.q_proj.weight, self.k_proj.weight,
                       self.v_proj.weight, self.o_proj.weight])


class LlamaMLP(nn.Layer):
    def __init__(self, c: LlamaConfig):
        super().__init__()
        self.c = c
        if c.fuse_attention_ffn:
            # packed rank-interleaved [g blocks of (gate_g | up_g)] — same
            # grouping rationale as fused qkv: the silu(gate)*up elementwise
            # product pairs columns within one shard block, so no cross-
            # shard resharding of the intermediate activation under mp
            # (capability parity: PaddleNLP fuse_attention_ffn; layout is
            # framework-native)
            g = c.fuse_pack_groups
            if c.intermediate_size % g:
                raise ValueError(
                    f"fuse_attention_ffn requires intermediate_size "
                    f"divisible by fuse_pack_groups={g}")
            self._ffn_groups = g
            self.gate_up_proj = _mp_linear(c.hidden_size,
                                           2 * c.intermediate_size,
                                           P(None, MP_AXIS))
            I_ = c.intermediate_size
            _init_packed_segments(
                self.gate_up_proj.weight,
                [(I_ // g, I_), (I_ // g, I_)] * g)
        else:
            self.gate_proj = _mp_linear(c.hidden_size, c.intermediate_size,
                                        P(None, MP_AXIS))
            self.up_proj = _mp_linear(c.hidden_size, c.intermediate_size,
                                      P(None, MP_AXIS))
        self.down_proj = _mp_linear(c.intermediate_size, c.hidden_size,
                                    P(MP_AXIS, None))

    def forward(self, x):
        from ..core.dispatch import apply as _apply

        def named(t):
            """The raw gate / up product, every row and this chip's
            columns (`seq_whole`), under its residual's name where a
            checkpoint around the layer keeps it."""
            if not (_keeps("gate_up") or seq_layout_engages(t)):
                return t
            return _apply("gate_up",
                          lambda a: _residual(seq_whole(a), "gate_up"), [t])

        def down(t):
            """The row product of the swiglu output, which is held (and
            its cotangent with it) to every row and this chip's columns:
            the backward gathers the rows of d out, not the weight."""
            return annotate_sequence_parallel(
                self.down_proj(annotate_column_parallel(t)))

        if self.c.fuse_attention_ffn:
            c = self.c
            g, Ig = self._ffn_groups, c.intermediate_size // self._ffn_groups
            gu = named(self.gate_up_proj(x))
            if g == 1:
                # single-arg swiglu splits [gate | up] internally
                return down(F.swiglu(gu))
            # grouped layout: split per block, then flatten back to [.., I]
            shp = gu.shape[:-1]
            gu = gu.reshape(list(shp) + [g, 2 * Ig])
            gate = gu[..., :Ig].reshape(list(shp) + [c.intermediate_size])
            up = gu[..., Ig:].reshape(list(shp) + [c.intermediate_size])
            return down(F.swiglu(gate, up))
        return down(F.swiglu(named(self.gate_proj(x)),
                             named(self.up_proj(x))))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, c: LlamaConfig):
        super().__init__()
        self.c = c
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = LlamaAttention(c)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        self.mlp = LlamaMLP(c)

    def forward(self, x, cos, sin, attn_mask=None):
        # the step scopes of `observability.attribution`: the attention
        # layer names its own three parts.  Where the sequence layout
        # engages the norms and the residual adds run on S/mp rows; a
        # norm's OUTPUT is held to those rows too, so that the gather
        # sits between it and the column product (before the norm it
        # would keep the duplicate work)
        with _scope("attn_norm"):
            hn = annotate_sequence_parallel(self.input_layernorm(x))
        h = annotate_sequence_parallel(
            x + self.self_attn(hn, cos, sin, attn_mask))
        with _scope("ffn_norm"):
            hn = annotate_sequence_parallel(self.post_attention_layernorm(h))
        with _scope("ffn"):
            out = annotate_sequence_parallel(h + self.mlp(hn))
        return out


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        init = I.Normal(0.0, config.initializer_range)
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.embed_tokens.weight._data = init(
            [config.vocab_size, config.hidden_size], "float32")
        self.embed_tokens.weight._sharding_spec = P(MP_AXIS, None)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        cos, sin = precompute_rope(config.head_dim,
                                   config.max_position_embeddings,
                                   config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, attn_mask=None):
        x = annotate_sequence_parallel(self.embed_tokens(input_ids))
        cos, sin = self.rope_cos._data, self.rope_sin._data
        for layer in self.layers:
            if self.config.recompute and self.training:
                from ..distributed.recompute import recompute
                x = recompute(layer, x, cos, sin, attn_mask)
            else:
                x = layer(x, cos, sin, attn_mask)
        return annotate_sequence_parallel(self.norm(x))


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)
            self.lm_head.weight._sharding_spec = P(None, MP_AXIS)

    def forward(self, input_ids, labels=None, attn_mask=None):
        h = self.llama(input_ids, attn_mask)
        if self.lm_head is not None:
            logits = self.lm_head(h)
        else:
            logits = F.linear(h, self.llama.embed_tokens.weight.T)
        if labels is not None:
            from ..distributed.parallel_layers import ParallelCrossEntropy
            loss_fn = ParallelCrossEntropy()
            tok_loss = loss_fn(logits, labels)
            return tok_loss.mean(), logits
        return logits
