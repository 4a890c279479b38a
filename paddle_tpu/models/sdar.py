"""SDAR-MoE: a routed decoder that GENERATES BY DIFFUSION OVER BLOCKS
(JetLM SDAR-30B-A3B-Chat, ``model_type`` ``sdar_moe``).

The body is the MoE family's (`moe_llm.py`, `llama.py`) with two
additions — a per-head RMSNorm of q and k, and ``head_dim`` its own key
(32 heads of 128 are TWICE ``hidden_size`` 2048) — and no shared
expert. What is new is the MASK and the GENERATION RULE.

Layer l, rows x [T, hidden]:

    h  = RMSNorm(x; ln1, eps)
    q  = h Wq [T, heads, D],  k = h Wk [T, kv, D],  v = h Wv [T, kv, D]
    q  = RMSNorm_D(q; q_norm),  k = RMSNorm_D(k; k_norm)     one gain [D]
                                                            for all heads
    q, k = rotate-half RoPE at the row's absolute position (rope_theta)
    a  = softmax(q k^T / sqrt(D) + M) v          heads / kv query heads a
                                                  KV head
    x  = x + a Wo
    h2 = RMSNorm(x; ln2)
    p  = softmax_f32(h2 Wr) over num_experts;  (w, e) = top_k(p);
    w  = w / sum(w)                                        (norm_topk_prob)
    x  = x + sum_j w_j Wd[e_j] (silu(h2 Wg[e_j]) * h2 Wu[e_j])

then the final RMSNorm and an untied head.

**The mask M (block-causal, block length B):** the row at position i
sees key j iff ``j <= B * floor(i / B) + B - 1`` — everything up to the
end of its own block. B = 1 is the causal mask. The prompt is encoded
under the same mask.

**Generation** (``low_confidence_static``, greedy), prompt length P: the
first ``B * floor(P / B)`` prompt tokens are prefilled; the remaining ``P
mod B`` open the first block as GIVEN tokens and are never masked. A
block starts as its given tokens + ``mask_token_id``. Pass p = 1 .. S
(``denoising_steps``): the block's B rows run over the cache; ``x0 =
argmax(logits)``, ``c = max softmax_f32(logits)``; among the rows still
masked the ``B / S`` with the largest c (all that are left, if fewer)
take their ``x0``, ties to the lower position; a given or unmasked row
is never overwritten. When no mask is left ONE more pass with the final
tokens writes the block's K/V (the commit pass) and the block's tokens
are emitted: ``ceil((B - g) S / B) + 1`` launches for a block of g given
tokens. An EOS inside a committed block ends the request at it;
``max_new_tokens`` cuts the last block, which is still denoised whole.
`serving.ServingEngine` runs this rule; `generation.generate*` refuse
the family by name (they decode one causal token at a time).

``block_length``, ``denoising_steps`` and ``mask_token_id`` are not in
the published config (the catalog's ``not_given``): they are this
config's own keys, set by whoever builds it, and the engine reads them
from here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply
from ..incubate.moe import MoELayer
from .llama import apply_rope, precompute_rope
from .moe_llm import MoEConfig

__all__ = ["SDARMoeConfig", "SDARMoeModel", "SDARMoeForCausalLM",
           "block_causal_mask", "block_passes", "sdar_tiny_config"]


def block_causal_mask(S: int, block: int):
    """[S, S] bool: query i sees key j iff j <= the end of i's block."""
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    return j <= (i // block + 1) * block - 1


def block_passes(block: int, steps: int, given: int = 0) -> int:
    """Launches a block of `given` given tokens costs: its denoise
    passes, ``block / steps`` rows unmasked in each, and one commit."""
    return -(-(block - given) * steps // block) + 1


class SDARMoeConfig(MoEConfig):
    """The published keys under their published names (``num_experts_per_tok``,
    ``norm_topk_prob``, ``head_dim``) over `MoEConfig`, plus the three
    generation keys the published config does not give."""

    def __init__(self, num_experts_per_tok=8, norm_topk_prob=True,
                 block_length=4, denoising_steps=4, mask_token_id=151669,
                 **kw):
        kw.setdefault("rms_norm_eps", 1e-6)
        kw.setdefault("rope_theta", 1000000.0)
        super().__init__(top_k=num_experts_per_tok, moe_dropless=True,
                         shared_expert_intermediate_size=0, **kw)
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = bool(norm_topk_prob)
        self.block_length = int(block_length)
        self.denoising_steps = int(denoising_steps)
        self.mask_token_id = int(mask_token_id)
        if self.block_length < 1 or self.block_length % self.denoising_steps:
            raise ValueError(
                f"block_length {block_length} must be whole multiples of "
                f"denoising_steps {denoising_steps}: a pass unmasks "
                f"block_length / denoising_steps rows")


def sdar_tiny_config(**kw) -> SDARMoeConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32,
                max_position_embeddings=256, num_experts=8,
                num_experts_per_tok=2, moe_intermediate_size=32,
                mask_token_id=255)
    base.update(kw)
    return SDARMoeConfig(**base)


class SDARAttention(nn.Layer):
    def __init__(self, c: SDARMoeConfig):
        super().__init__()
        self.c = c
        H, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        lin = lambda i, o: nn.Linear(i, o, bias_attr=False)  # noqa: E731
        self.q_proj = lin(c.hidden_size, H * D)
        self.k_proj = lin(c.hidden_size, KV * D)
        self.v_proj = lin(c.hidden_size, KV * D)
        self.o_proj = lin(H * D, c.hidden_size)
        self.q_norm = nn.RMSNorm(D, c.rms_norm_eps)
        self.k_norm = nn.RMSNorm(D, c.rms_norm_eps)

    def forward(self, x, cos, sin):
        c = self.c
        B, S, _ = x.shape
        H, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        rep, eps = H // KV, c.rms_norm_eps
        seen = block_causal_mask(S, c.block_length)

        def head_norm(t, g):
            t32 = t.astype(jnp.float32)
            y = t32 * jax.lax.rsqrt(
                jnp.mean(t32 * t32, -1, keepdims=True) + eps)
            return y.astype(t.dtype) * g

        def impl(h, wq, wk, wv, wo, gq, gk):
            q = head_norm((h @ wq).reshape(B, S, H, D), gq)
            k = head_norm((h @ wk).reshape(B, S, KV, D), gk)
            v = (h @ wv).reshape(B, S, KV, D)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            qg = q.reshape(B, S, KV, rep, D)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).astype(jnp.float32)
            s = jnp.where(seen, s * D ** -0.5, -jnp.inf)
            p = jax.nn.softmax(s, -1).astype(v.dtype)
            o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v)
            return o.reshape(B, S, H * D) @ wo

        return apply("sdar_attention", impl,
                     [x, self.q_proj.weight, self.k_proj.weight,
                      self.v_proj.weight, self.o_proj.weight,
                      self.q_norm.weight, self.k_norm.weight])


class SDARDecoderLayer(nn.Layer):
    def __init__(self, c: SDARMoeConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = SDARAttention(c)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        self.mlp = MoELayer(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            top_k=c.num_experts_per_tok, activation="swiglu",
            dropless=True, renormalize=c.norm_topk_prob)

    def forward(self, x, cos, sin):
        h = x + self.self_attn(self.input_layernorm(x), cos, sin)
        return h + self.mlp(self.post_attention_layernorm(h))


class SDARMoeModel(nn.Layer):
    """The family's stack (`moe_llm.MoEModel`'s shape: embedding, layers,
    last norm) over `SDARDecoderLayer`s; the rope tables are made for
    the positions asked for, not held at the published maximum."""

    def __init__(self, config: SDARMoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [SDARDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def rope_tables(self, n: int):
        if n > self.config.max_position_embeddings:
            raise ValueError(f"{n} positions exceed max_position_embeddings "
                             f"{self.config.max_position_embeddings}")
        return precompute_rope(self.config.head_dim, n,
                               self.config.rope_theta)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        cos, sin = self.rope_tables(x.shape[1])
        for layer in self.layers:
            x = layer(x, cos, sin)
        return self.norm(x)


class SDARMoeForCausalLM(nn.Layer):
    """Logits of ids [B, S] under the block-causal mask: what one pass
    of the generation rule reads. It does not generate: `ServingEngine`
    does."""

    def __init__(self, config: SDARMoeConfig):
        super().__init__()
        self.config = config
        self.model = SDARMoeModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))
