"""A.X-K1 (``skt/A.X-K1``, ``model_type`` ``axk1``): the DeepSeek-V3
layer under its own published keys, built over `models/deepseek.py`.

Source of the layout: the published ``config.json``.  With ``h =
RMSNorm(x)``:

1. ``c_q = RMSNorm(h W_qa)`` [q_lora_rank]; ``q = c_q W_qb`` -> heads of
   ``(q_nope [qk_nope_head_dim] | q_pe [qk_rope_head_dim])``.
2. ``kv_a = h W_kva`` [kv_lora_rank + qk_rope_head_dim]; ``c =
   RMSNorm(kv_a[:kv_lora_rank])``; ``k_pe = RoPE(kv_a[kv_lora_rank:])``,
   ONE head shared by all query heads.  ``(k_nope | v) = c W_kvb`` a head.
3. Causal softmax attention over ``(q_nope | RoPE(q_pe)) . (k_nope |
   k_pe)`` at scale ``qk_head_dim^-1/2 x m^2``, ``m = 0.1 x
   mscale_all_dim x ln(factor) + 1`` (yarn); rope in the rotate-half
   pairing with yarn's blended inverse frequencies; ``W_o``.
4. The first ``first_k_dense_replace`` layers: SwiGLU of
   ``intermediate_size``.  The others: ``sc = sigmoid(h2 W_g)`` over ALL
   ``n_routed_experts`` in float32; the experts are ``n_group`` groups
   of consecutive experts, a group's score the sum of its two largest
   ``sc``, the ``topk_group`` best groups stay; top ``num_experts_per_tok``
   of ``sc`` inside them; ``w_e = sc_e / sum sc x routed_scaling_factor``
   (``norm_topk_prob``); plus ``n_shared_experts`` shared SwiGLU experts
   as one of their summed width, ungated.

``topk_method`` ``none`` is read as "no per-expert correction bias";
the other conventions the config leaves open are under ``assumed`` in
``benchmarks/configs/a.x-k1-serve-ep16-d6.json``.

One chip's share of an expert-parallel deployment is an argument, not
a second model: ``experts_held = (first, count)`` stacks only those
experts (`incubate.moe.MoELayer`; the router keeps its published
width), and ``vocab_size`` is the rows of the vocabulary held here.
"""

from __future__ import annotations

from .deepseek import DeepSeekV2Config, DeepSeekV2ForCausalLM

__all__ = ["axk1_config", "axk1_tiny_config", "AXK1ForCausalLM"]

#: the same classes: what differs is configuration
AXK1ForCausalLM = DeepSeekV2ForCausalLM


def axk1_config(*, n_routed_experts=192, num_experts_per_tok=8,
                n_shared_experts=1, moe_intermediate_size=2048,
                topk_method="none", moe_layer_freq=1,
                experts_held=None, rope_positions=8192,
                **published) -> DeepSeekV2Config:
    """A `DeepSeekV2Config` from the published keys, under their
    published names (defaults: the published values)."""
    if topk_method != "none":
        raise NotImplementedError(
            f"topk_method {topk_method!r}: a per-expert correction "
            f"bias is not implemented")
    if moe_layer_freq != 1:
        raise NotImplementedError("moe_layer_freq must be 1")
    if published.pop("tie_word_embeddings", False):
        raise NotImplementedError("A.X-K1 has an untied head")
    base = dict(
        vocab_size=163840, hidden_size=7168, intermediate_size=18432,
        num_hidden_layers=61, num_attention_heads=64,
        num_key_value_heads=64, max_position_embeddings=131072,
        rms_norm_eps=1e-6, rope_theta=10000.0,
        rope_scaling={"type": "yarn", "factor": 32, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096},
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, first_k_dense_replace=1,
        n_group=8, topk_group=4, scoring_func="sigmoid",
        norm_topk_prob=True, routed_scaling_factor=2.5)
    base.update(published)
    return DeepSeekV2Config(
        num_experts=n_routed_experts, top_k=num_experts_per_tok,
        moe_intermediate_size=moe_intermediate_size,
        shared_expert_intermediate_size=(n_shared_experts
                                         * moe_intermediate_size),
        moe_dropless=True, experts_held=experts_held,
        rope_positions=rope_positions, **base)


def axk1_tiny_config(**kw) -> DeepSeekV2Config:
    """Toy widths with every mechanism on: q-lora, a latent of one
    whole 128-lane register (so the cache row is stored padded, as at
    the published 512 + 64), yarn with mscale^2 != 1, a leading dense
    layer, sigmoid scores, 4 groups of 4 experts of which 2 stay, a
    shared expert and the routed scale."""
    base = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=4, max_position_embeddings=4096,
        rope_scaling={"type": "yarn", "factor": 16, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 64},
        q_lora_rank=48, kv_lora_rank=128, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, n_routed_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=32, n_group=4,
        topk_group=2, rope_positions=512)
    base.update(kw)
    return axk1_config(**base)
