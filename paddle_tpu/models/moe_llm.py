"""MoE decoder LM family (Qwen2-MoE / DeepSeekMoE pattern).

Reference capability: PaddleNLP paddlenlp/transformers/{qwen2_moe,deepseek_v2}
(SURVEY §2.4 — MoE decoder layers with expert parallel via alltoall, shared
expert, aux load-balance loss). TPU-native: the routed experts are stacked
weights sharded on the `ep` mesh axis; dispatch/combine einsums lower to
GSPMD all-to-all (see paddle_tpu.incubate.moe).
"""

from __future__ import annotations

from typing import Optional

from jax.sharding import PartitionSpec as P

from .. import nn
from ..incubate.moe import MoELayer
from ..distributed.parallel_layers import MP_AXIS, ParallelCrossEntropy
from .llama import (LlamaAttention, LlamaConfig, LlamaMLP, precompute_rope)
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I

__all__ = ["MoEConfig", "MoEDecoderLayer", "MoEModel", "MoEForCausalLM",
           "qwen2_moe_tiny_config"]


class MoEConfig(LlamaConfig):
    """Llama backbone + MoE FFN knobs (moe_intermediate_size per expert,
    shared_expert_intermediate_size, num_experts, top_k, router aux weight;
    dense first-k layers DeepSeek-style via first_k_dense_replace)."""

    def __init__(self, num_experts=8, top_k=2, moe_intermediate_size=None,
                 shared_expert_intermediate_size=0, capacity_factor=1.25,
                 aux_loss_weight=0.01, router_z_loss_weight=0.0,
                 first_k_dense_replace=0, moe_dropless=False, **kw):
        super().__init__(**kw)
        self.num_experts = num_experts
        self.top_k = top_k
        self.moe_intermediate_size = (moe_intermediate_size
                                      or self.intermediate_size)
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        self.capacity_factor = capacity_factor
        self.aux_loss_weight = aux_loss_weight
        self.router_z_loss_weight = router_z_loss_weight
        self.first_k_dense_replace = first_k_dense_replace
        self.moe_dropless = moe_dropless


def qwen2_moe_tiny_config(**kw) -> MoEConfig:
    base = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=256,
                rope_theta=10000.0, num_experts=4, top_k=2,
                moe_intermediate_size=64,
                shared_expert_intermediate_size=64)
    base.update(kw)
    return MoEConfig(**base)


class MoEDecoderLayer(nn.Layer):
    def __init__(self, c: MoEConfig, layer_idx: int = 0):
        super().__init__()
        self.c = c
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = LlamaAttention(c)
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
                z_loss_weight=c.router_z_loss_weight)

    def forward(self, x, cos, sin, attn_mask=None):
        # the layout of `LlamaDecoderLayer.forward`: S/mp rows a chip
        # outside the two sublayers where the mesh and the length allow
        from ..distributed.parallel_layers import \
            annotate_sequence_parallel as held
        h = held(x + self.self_attn(held(self.input_layernorm(x)),
                                    cos, sin, attn_mask))
        return held(h + self.mlp(held(self.post_attention_layernorm(h))))


class MoEModel(nn.Layer):
    def __init__(self, config: MoEConfig):
        super().__init__()
        self.config = config
        init = I.Normal(0.0, config.initializer_range)
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.embed_tokens.weight._data = init(
            [config.vocab_size, config.hidden_size], "float32")
        self.embed_tokens.weight._sharding_spec = P(MP_AXIS, None)
        self.layers = nn.LayerList(
            [MoEDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        cos, sin = precompute_rope(config.head_dim,
                                   config.max_position_embeddings,
                                   config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def aux_loss(self):
        """Sum of router aux losses recorded during the last forward."""
        total = None
        for layer in self.layers:
            la = getattr(layer.mlp, "l_aux", None)
            if la is not None:
                total = la if total is None else total + la
        return total

    def forward(self, input_ids, attn_mask=None):
        x = self.embed_tokens(input_ids)
        cos, sin = self.rope_cos._data, self.rope_sin._data
        for layer in self.layers:
            if self.config.recompute and self.training:
                from ..distributed.recompute import recompute
                x = recompute(layer, x, cos, sin, attn_mask)
            else:
                x = layer(x, cos, sin, attn_mask)
        return self.norm(x)


class MoEForCausalLM(nn.Layer):
    def __init__(self, config: MoEConfig):
        super().__init__()
        self.config = config
        self.model = MoEModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)
        self.lm_head.weight._sharding_spec = P(None, MP_AXIS)

    def forward(self, input_ids, labels=None, attn_mask=None):
        h = self.model(input_ids, attn_mask)
        logits = self.lm_head(h)
        if labels is not None:
            loss_fn = ParallelCrossEntropy()
            tok_loss = loss_fn(logits, labels)
            loss = tok_loss.mean()
            aux = self.model.aux_loss()
            if aux is not None and self.config.aux_loss_weight:
                loss = loss + aux * self.config.aux_loss_weight
            return loss, logits
        return logits
