"""DeepSeek-V2 family: MLA (multi-head latent attention) + MoE FFN.

Reference capability: PaddleNLP paddlenlp/transformers/deepseek_v2/
modeling.py (SURVEY §2.4 — DeepSeekMoE baseline row). The defining feature
over the Qwen2-MoE pattern (models/moe_llm.py) is MLA: queries and KV are
low-rank compressed (q_lora_rank / kv_lora_rank) and position information
travels in a small decoupled rope sub-head — a single shared k_pe head
(MQA-style) plus per-head q_pe — so the KV cache is the compressed latent
instead of full K/V.

TPU-first notes: the compressions are small dense matmuls (MXU-friendly);
the decoupled-rope concat keeps the big nope dims rope-free so XLA fuses
the kv_b expansion into the attention einsum; attention math is einsum-based
because q/k head dim (nope+rope) differs from the v head dim — the flash
kernel path applies when they match.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..distributed.parallel_layers import MP_AXIS, ParallelCrossEntropy
from .llama import LlamaMLP, apply_rope, precompute_rope
from .moe_llm import MoEConfig
from ..incubate.moe import MoELayer

__all__ = ["DeepSeekV2Config", "MLAttention", "DeepSeekV2DecoderLayer",
           "DeepSeekV2Model", "DeepSeekV2ForCausalLM",
           "deepseek_v2_tiny_config"]


class DeepSeekV2Config(MoEConfig):
    """MoEConfig + the latent-attention ranks, and what the family's
    later members add (each default is the V2 behaviour): yarn
    ``rope_scaling`` (the published group: factor,
    original_max_position_embeddings, beta_fast / beta_slow, mscale,
    mscale_all_dim), a router that is not softmax top-k
    (``scoring_func`` sigmoid, ``n_group`` / ``topk_group``,
    ``norm_topk_prob``, ``routed_scaling_factor``), one chip's share of
    an expert-parallel layer (``experts_held = (first, count)``) and
    ``rope_positions`` (rows of the rotary table the model builds; the
    published maximum if None), a per-expert correction bias of the
    router's choice (``correction_bias``: ``topk_method`` ``noaux_tc``)
    and a residual of ``hc_mult`` streams mixed by hyper-connections
    (`models/xing.py`: ``hc_sinkhorn_iters``, ``hc_eps``,
    ``mhc_h_res_clamp`` = (min, max))."""

    def __init__(self, q_lora_rank=None, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, rope_scaling=None,
                 scoring_func="softmax", n_group=1, topk_group=1,
                 norm_topk_prob=True, routed_scaling_factor=1.0,
                 experts_held=None, rope_positions=None,
                 correction_bias=False, hc_mult=1, hc_sinkhorn_iters=20,
                 hc_eps=1e-6, mhc_h_res_clamp=(-30.0, 30.0), **kw):
        super().__init__(**kw)
        self.correction_bias = bool(correction_bias)
        self.hc_mult = int(hc_mult)
        self.hc_sinkhorn_iters = int(hc_sinkhorn_iters)
        self.hc_eps = float(hc_eps)
        self.mhc_h_res_clamp = tuple(float(v) for v in mhc_h_res_clamp)
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.qk_head_dim = qk_nope_head_dim + qk_rope_head_dim
        if rope_scaling is not None and \
                rope_scaling.get("type", rope_scaling.get("rope_type")) \
                != "yarn":
            raise NotImplementedError(f"rope_scaling {rope_scaling}")
        self.rope_scaling = rope_scaling
        self.scoring_func = scoring_func
        self.n_group, self.topk_group = n_group, topk_group
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.experts_held = tuple(experts_held) if experts_held else None
        self.rope_positions = min(
            int(rope_positions or self.max_position_embeddings),
            self.max_position_embeddings)

    @property
    def softmax_scale(self) -> float:
        """The attention scores' scale: qk_head_dim^-1/2, times yarn's
        mscale^2 where the rope is scaled (m = 0.1 mscale_all_dim
        ln(factor) + 1, DeepSeek-V2/V3's reading of the key)."""
        scale = 1.0 / math.sqrt(self.qk_head_dim)
        rs = self.rope_scaling
        if rs and rs.get("mscale_all_dim"):
            m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
            scale *= m * m
        return scale

    def rope_table(self, n: int):
        """(cos, sin) float32 [n, qk_rope_head_dim / 2]: the default
        table, or yarn's blended frequencies with cos and sin times
        mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
        rs = self.rope_scaling
        if rs is None:
            return precompute_rope(self.qk_rope_head_dim, n,
                                   self.rope_theta)
        from .laguna import rope_table
        af = yarn_mscale(rs["factor"], rs.get("mscale", 1)) \
            / yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0))
        return rope_table(
            dict(rope_type="yarn", rope_theta=self.rope_theta,
                 factor=rs["factor"], attention_factor=af,
                 original_max_position_embeddings=rs[
                     "original_max_position_embeddings"],
                 beta_fast=rs.get("beta_fast", 32),
                 beta_slow=rs.get("beta_slow", 1)),
            self.qk_rope_head_dim, n)


def yarn_mscale(factor: float, mscale: float) -> float:
    if factor <= 1 or not mscale:
        return 1.0
    return 0.1 * float(mscale) * math.log(float(factor)) + 1.0


def deepseek_v2_tiny_config(**kw) -> DeepSeekV2Config:
    base = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=4,
                intermediate_size=128, max_position_embeddings=64,
                q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16,
                num_experts=4, top_k=2, moe_intermediate_size=32,
                shared_expert_intermediate_size=32,
                first_k_dense_replace=1)
    base.update(kw)
    return DeepSeekV2Config(**base)


def _linear(in_f, out_f, spec=None):
    l = nn.Linear(in_f, out_f, bias_attr=False)
    if spec is not None:
        l.weight._sharding_spec = spec
    return l


class MLAttention(nn.Layer):
    """Multi-head latent attention (DeepSeek-V2).

    x → [q_a → RMSNorm → q_b]              per-head (nope ‖ rope) queries
    x → kv_a → (c_kv ‖ k_pe)               latent + shared rope key head
        c_kv → RMSNorm → kv_b              per-head (k_nope ‖ v)
    attn over (nope ‖ rope) q·k, value dim v_head_dim, then o_proj.
    """

    def __init__(self, c: DeepSeekV2Config):
        super().__init__()
        self.c = c
        nh = c.num_attention_heads
        dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        if c.q_lora_rank:
            self.q_a_proj = _linear(c.hidden_size, c.q_lora_rank)
            self.q_a_layernorm = nn.RMSNorm(c.q_lora_rank, c.rms_norm_eps)
            self.q_b_proj = _linear(c.q_lora_rank, nh * (dn + dr),
                                    P(None, MP_AXIS))
        else:
            self.q_proj = _linear(c.hidden_size, nh * (dn + dr),
                                  P(None, MP_AXIS))
        self.kv_a_proj_with_mqa = _linear(c.hidden_size,
                                          c.kv_lora_rank + dr)
        self.kv_a_layernorm = nn.RMSNorm(c.kv_lora_rank, c.rms_norm_eps)
        self.kv_b_proj = _linear(c.kv_lora_rank, nh * (dn + dv),
                                 P(None, MP_AXIS))
        self.o_proj = _linear(nh * dv, c.hidden_size, P(MP_AXIS, None))

    def forward(self, x, cos, sin, attn_mask=None):
        c = self.c
        B, S, _ = x.shape
        nh = c.num_attention_heads
        dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        eps = c.rms_norm_eps
        mask = attn_mask._data if isinstance(attn_mask, Tensor) else attn_mask
        from ..core.dispatch import apply as _apply

        def _rms(h, w):
            var = jnp.mean(jnp.square(h.astype(jnp.float32)), -1,
                           keepdims=True)
            return (h * jax.lax.rsqrt(var + eps).astype(h.dtype)) * w

        # the whole latent-attention computation runs inside ONE dispatch
        # apply so the tape sees every projection weight (the llama.py
        # convention — raw-array math outside apply would be invisible to
        # autograd)
        def impl(h, w_kv_a, g_kv, w_kv_b, w_o, *q_weights):
            if c.q_lora_rank:
                w_q_a, g_q, w_q_b = q_weights
                q = _rms(h @ w_q_a, g_q) @ w_q_b
            else:
                (w_q,) = q_weights
                q = h @ w_q
            q = q.reshape(B, S, nh, dn + dr)
            q_nope, q_pe = q[..., :dn], q[..., dn:]

            kv_a = h @ w_kv_a
            c_kv, k_pe = kv_a[..., :c.kv_lora_rank], \
                kv_a[..., c.kv_lora_rank:]
            kv = (_rms(c_kv, g_kv) @ w_kv_b).reshape(B, S, nh, dn + dv)
            k_nope, v = kv[..., :dn], kv[..., dn:]

            q_pe = apply_rope(q_pe, cos, sin)
            k_pe = apply_rope(k_pe[:, :, None, :], cos, sin)
            k_pe = jnp.broadcast_to(k_pe, (B, S, nh, dr))

            qh = jnp.concatenate([q_nope, q_pe], -1)
            kh = jnp.concatenate([k_nope, k_pe], -1)

            if c.use_flash_attention and mask is None:
                if dv == dn + dr and c.rope_scaling is None:
                    from ..ops.flash_attention import sdpa
                    o = sdpa(qh, kh, v, causal=True)
                else:
                    # real DeepSeek geometry (dv != dn+dr, e.g. 128 vs
                    # 192): zero-pad heads to the lane so the O(S) flash
                    # route applies — the dense path below OOMs
                    # long-context prefill on [B,nh,S,S] f32 scores
                    from ..ops.flash_attention import sdpa_padded_heads
                    o = sdpa_padded_heads(
                        qh, kh, v, causal=True, scale=c.softmax_scale)
            else:
                scores = jnp.einsum("bsnd,btnd->bnst", qh, kh) \
                    * c.softmax_scale
                scores = scores.astype(jnp.float32)
                causal = jnp.tril(jnp.ones((S, S), bool))
                neg = jnp.asarray(-1e30, scores.dtype)
                scores = jnp.where(causal[None, None], scores, neg)
                if mask is not None:  # compose, never replace (gpt.py conv.)
                    m = jnp.asarray(mask)
                    if m.dtype == jnp.bool_:
                        scores = jnp.where(m, scores, neg)
                    else:
                        scores = scores + m.astype(scores.dtype)
                w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
                o = jnp.einsum("bnst,btnv->bsnv", w, v)
            return o.reshape(B, S, nh * dv) @ w_o

        inputs = [x, self.kv_a_proj_with_mqa.weight,
                  self.kv_a_layernorm.weight, self.kv_b_proj.weight,
                  self.o_proj.weight]
        if c.q_lora_rank:
            inputs += [self.q_a_proj.weight, self.q_a_layernorm.weight,
                       self.q_b_proj.weight]
        else:
            inputs += [self.q_proj.weight]
        return _apply("mla_attention", impl, inputs)


class DeepSeekV2DecoderLayer(nn.Layer):
    def __init__(self, c: DeepSeekV2Config, layer_idx: int = 0):
        super().__init__()
        self.c = c
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = MLAttention(c)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        if layer_idx < c.first_k_dense_replace:
            self.mlp = LlamaMLP(c)
        else:
            self.mlp = MoELayer(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                top_k=c.top_k, capacity_factor=c.capacity_factor,
                activation="swiglu", dropless=c.moe_dropless,
                shared_expert_hidden=c.shared_expert_intermediate_size,
                z_loss_weight=c.router_z_loss_weight,
                renormalize=c.norm_topk_prob,
                experts_held=c.experts_held,
                routed_scale=c.routed_scaling_factor,
                score=c.scoring_func, n_group=c.n_group,
                topk_group=c.topk_group,
                correction_bias=c.correction_bias)

    def forward(self, x, cos, sin, attn_mask=None):
        h = x + self.self_attn(self.input_layernorm(x), cos, sin, attn_mask)
        return h + self.mlp(self.post_attention_layernorm(h))


class DeepSeekV2Model(nn.Layer):
    #: the layer a member of the family builds (`models/xing.py`)
    layer_cls = DeepSeekV2DecoderLayer

    def __init__(self, config: DeepSeekV2Config):
        super().__init__()
        self.config = config
        init = I.Normal(0.0, config.initializer_range)
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        from ..framework.lazy import lazy_enabled
        if not lazy_enabled():      # else the caller binds the weights
            self.embed_tokens.weight._data = init(
                [config.vocab_size, config.hidden_size], "float32")
        self.embed_tokens.weight._sharding_spec = P(MP_AXIS, None)
        self.layers = nn.LayerList(
            [self.layer_cls(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        cos, sin = config.rope_table(config.rope_positions)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def aux_loss(self):
        total = None
        for layer in self.layers:
            la = getattr(layer.mlp, "l_aux", None)
            if la is not None:
                total = la if total is None else total + la
        return total

    def enter(self, x):
        """The residual the layers carry, from the embedding's rows."""
        return x

    def exit(self, x):
        """... and back to [.., hidden] before the last norm."""
        return x

    def forward(self, input_ids, attn_mask=None):
        x = self.enter(self.embed_tokens(input_ids))
        cos, sin = self.rope_cos._data, self.rope_sin._data
        for layer in self.layers:
            if self.config.recompute and self.training:
                from ..distributed.recompute import recompute
                x = recompute(layer, x, cos, sin, attn_mask)
            else:
                x = layer(x, cos, sin, attn_mask)
        return self.norm(self.exit(x))


class DeepSeekV2ForCausalLM(nn.Layer):
    #: the decoder a member of the family builds (`models/xing.py`)
    model_cls = DeepSeekV2Model

    def __init__(self, config: DeepSeekV2Config):
        super().__init__()
        self.config = config
        self.model = self.model_cls(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)
        self.lm_head.weight._sharding_spec = P(None, MP_AXIS)

    def forward(self, input_ids, labels=None, attn_mask=None):
        h = self.model(input_ids, attn_mask)
        logits = self.lm_head(h)
        if labels is not None:
            tok_loss = ParallelCrossEntropy()(logits, labels)
            loss = tok_loss.mean()
            aux = self.model.aux_loss()
            if aux is not None and self.training:
                loss = loss + self.config.aux_loss_weight * aux
            return loss, logits
        return logits
