"""LFM2-MoE: a hybrid decoder whose mixers are GATED SHORT CONVOLUTIONS
and a few grouped-query attention layers, over dense and routed FFNs
(LiquidAI LFM2-24B-A2B, ``model_type`` ``lfm2_moe``).

A published layer is two blocks on a norm each (pre-norm, RMSNorm in
float32, ``norm_eps``). Rows x [T, hidden]:

    h  = RMSNorm(x; operator_norm)

  ``conv`` layer (``C``):
    (B, C, z) = split3(h W_in)            W_in [hidden, 3 hidden], no bias,
                                          in THAT order
    u   = B * z
    c_t = sum_{j=0..K-1} w[:, j] * u_{t-(K-1)+j}     depthwise, causal,
                                          zeros left of the sequence, no
                                          bias, NO activation; K =
                                          ``conv_L_cache``
    x   = x + (C * c) W_out               W_out [hidden, hidden]

  ``full_attention`` layer (``*``):
    q = h Wq [T, heads, D],  k = h Wk [T, kv, D],  v = h Wv [T, kv, D]
    q = RMSNorm_D(q; q_layernorm),  k = RMSNorm_D(k; k_layernorm)   one
                                          gain [D] for all heads
    q, k = rotate-half RoPE over all D dims at the row's absolute
           position (``rope_theta``)
    a = causal softmax(q k^T / sqrt(D)) v     heads / kv query heads a KV
                                              head
    x = x + a Wo

    h2 = RMSNorm(x; ffn_norm)

  layers below ``num_dense_layers`` (``D``):
    x = x + W2 (silu(h2 W1) * h2 W3)      width ``intermediate_size``
  the others (``E``):
    s = sigmoid_f32(h2 Wr) [num_experts]
    e = top_k(s + b)                      b the ``expert_bias``: it PICKS,
                                          it does not weigh
    w = s[e] / sum s[e]  (``norm_topk_prob``),  w *= routed_scaling_factor
    x = x + sum_j w_j W2[e_j] (silu(h2 W1[e_j]) * h2 W3[e_j])

then ``RMSNorm(x; embedding_norm) E^T``: the head is the embedding.

What a ``C`` mixer remembers of a sequence is the last K - 1 rows of
``u``: TWO rows of [hidden] at the published K = 3, whatever the length.
It is a finite history, not a recurrent state: cut at a token, the rows
before it are all a continuation needs, which is why
`serving.ServingEngine` keeps the prefix cache for this family (a
snapshot of the tails a page, `engine._state_mixers`).

``layers_held`` names the PUBLISHED indices of the layers built (a
pipeline stage holds a run of them); ``pattern`` spells their blocks for
the hybrid step body.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply
from ..incubate.moe import MoELayer
from ..nn import initializer as I
from .llama import apply_rope, precompute_rope
from .nemotron_h import _apply_mixer, _lin, arrays, ssm_conv

__all__ = ["Lfm2MoeConfig", "Lfm2MoeModel", "Lfm2MoeForCausalLM",
           "lfm2_moe_config", "lfm2_tiny_config", "short_conv", "arrays"]

CONV, ATTN, DENSE, MOE = "C", "*", "D", "E"


class Lfm2MoeConfig:
    """The published keys under their published names (defaults: the
    published values of LFM2-24B-A2B)."""

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 intermediate_size=11776, num_hidden_layers=40,
                 layers_held=None, layer_types=None,
                 num_attention_heads=32, num_key_value_heads=8,
                 max_position_embeddings=128000, moe_intermediate_size=1536,
                 norm_eps=1e-5, norm_topk_prob=True, num_dense_layers=2,
                 num_experts=64, num_experts_per_tok=4,
                 rope_parameters=None, routed_scaling_factor=1.0,
                 use_expert_bias=True, conv_L_cache=3, conv_bias=False,
                 tie_embedding=True):
        if conv_bias or not tie_embedding:
            raise NotImplementedError("conv_bias / an untied head")
        rope = dict(rope_parameters or {"rope_theta": 1000000.0,
                                        "rope_type": "default"})
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError(f"rope_type {rope['rope_type']!r}")
        if layer_types is None:
            # the published rule: attention every fourth layer from 2
            layer_types = ["full_attention" if i % 4 == 2 else "conv"
                           for i in range(num_hidden_layers)]
        if len(layer_types) < num_hidden_layers or \
                set(layer_types) - {"conv", "full_attention"}:
            raise ValueError(f"layer_types {layer_types}")
        held = list(range(num_hidden_layers)) if layers_held is None \
            else [int(i) for i in layers_held]
        if not held or sorted(set(held)) != held or held[0] < 0 \
                or held[-1] >= len(layer_types):
            raise ValueError(f"layers_held {layers_held}: ascending "
                             f"published indices under {len(layer_types)}")
        if hidden_size % num_attention_heads or \
                num_attention_heads % num_key_value_heads:
            raise ValueError("heads do not divide")
        self.layers_held = tuple(held)
        self.layer_types = tuple(layer_types)
        self.published_layers = len(layer_types)
        self.num_hidden_layers = len(held)
        self.num_dense_layers = num_dense_layers
        self.pattern = "".join(
            (CONV if layer_types[i] == "conv" else ATTN)
            + (DENSE if i < num_dense_layers else MOE) for i in held)
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.intermediate_size = intermediate_size
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.moe_intermediate_size = moe_intermediate_size
        self.norm_eps = self.layer_norm_epsilon = float(norm_eps)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.rope_theta = float(rope["rope_theta"])
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.use_expert_bias = bool(use_expert_bias)
        self.conv_L_cache = self.conv_kernel = int(conv_L_cache)
        self.conv_dim = hidden_size

    def rope_table(self, n: int):
        if n > self.max_position_embeddings:
            raise ValueError(f"{n} positions exceed max_position_embeddings "
                             f"{self.max_position_embeddings}")
        return precompute_rope(self.head_dim, n, self.rope_theta)


def lfm2_moe_config(**published) -> Lfm2MoeConfig:
    """An `Lfm2MoeConfig` from the published keys; ``model_type`` says
    nothing of the shape and is dropped."""
    published.pop("model_type", None)
    return Lfm2MoeConfig(**published)


def lfm2_tiny_config(**kw) -> Lfm2MoeConfig:
    """Toy widths with every mechanism on: both dense layers and one
    whole period (published layers 0-5: conv conv attn conv conv conv),
    4 heads of 16 over 2 KV heads, 8 experts of which 2 are chosen."""
    base = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
                num_hidden_layers=6, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=1024,
                moe_intermediate_size=32, num_experts=8,
                num_experts_per_tok=2,
                rope_parameters={"rope_theta": 10000.0})
    base.update(kw)
    return Lfm2MoeConfig(**base)


# ---------------------------------------------------------------------------
# the mixers on one sequence, shared with the serving engine
# ---------------------------------------------------------------------------

def short_conv(u_ext, w):
    """The ``C`` mixer's convolution: depthwise, causal, no bias and NO
    activation. ``u_ext`` [L + K - 1, W]: the K - 1 rows before the L
    rows (zeros left of the sequence), then the rows; ``w`` [W, K]."""
    return ssm_conv(u_ext, w, None, act=None)


def _conv_forward(a, L, c: Lfm2MoeConfig):
    """The ``C`` mixer on one sequence a [S, hidden] from its start."""
    K = c.conv_kernel
    gate_b, gate_c, z = jnp.split(a @ L["w_in"], 3, axis=-1)
    u = gate_b * z
    conv = short_conv(jnp.concatenate(
        [jnp.zeros((K - 1, u.shape[1]), u.dtype), u]), L["conv_w"])
    return (gate_c * conv) @ L["w_out"]


def _head_norm(t, g, eps):
    t32 = t.astype(jnp.float32)
    y = t32 * jax.lax.rsqrt(jnp.mean(t32 * t32, -1, keepdims=True) + eps)
    return y.astype(t.dtype) * g


def _attention_forward(a, L, c: Lfm2MoeConfig):
    """The ``*`` mixer on one sequence a [S, hidden]."""
    S = a.shape[0]
    Hq, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    cos, sin = c.rope_table(S)
    q = _head_norm((a @ L["wq"]).reshape(S, Hq, D), L["q_norm"], c.norm_eps)
    k = _head_norm((a @ L["wk"]).reshape(S, KV, D), L["k_norm"], c.norm_eps)
    v = (a @ L["wv"]).reshape(S, KV, D)
    q, k = apply_rope(q[None], cos, sin)[0], apply_rope(k[None], cos, sin)[0]
    s = jnp.einsum("tgrd,sgd->grts", q.reshape(S, KV, Hq // KV, D),
                   k).astype(jnp.float32) * D ** -0.5
    t = jnp.arange(S)
    s = jnp.where(t[:, None] >= t[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, -1).astype(v.dtype)
    o = jnp.einsum("grts,sgd->tgrd", p, v)
    return o.reshape(S, Hq * D) @ L["wo"]


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

class Lfm2ShortConv(nn.Layer):
    def __init__(self, c: Lfm2MoeConfig):
        super().__init__()
        self.c = c
        self.in_proj = _lin(c.hidden_size, 3 * c.hidden_size)
        self.conv_weight = self.create_parameter(
            [c.hidden_size, c.conv_kernel],
            default_initializer=I.Normal(0.0, 0.5))
        self.out_proj = _lin(c.hidden_size, c.hidden_size)

    def weights(self) -> dict:
        return dict(w_in=self.in_proj.weight, conv_w=self.conv_weight,
                    w_out=self.out_proj.weight)

    def forward(self, a):
        return _apply_mixer("lfm2_short_conv", _conv_forward, a,
                            self.weights(), self.c)


class Lfm2Attention(nn.Layer):
    def __init__(self, c: Lfm2MoeConfig):
        super().__init__()
        self.c = c
        Hq, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        self.q_proj = _lin(c.hidden_size, Hq * D)
        self.k_proj = _lin(c.hidden_size, KV * D)
        self.v_proj = _lin(c.hidden_size, KV * D)
        self.out_proj = _lin(Hq * D, c.hidden_size)
        self.q_layernorm = nn.RMSNorm(D, c.norm_eps)
        self.k_layernorm = nn.RMSNorm(D, c.norm_eps)

    def weights(self) -> dict:
        return dict(wq=self.q_proj.weight, wk=self.k_proj.weight,
                    wv=self.v_proj.weight, wo=self.out_proj.weight,
                    q_norm=self.q_layernorm.weight,
                    k_norm=self.k_layernorm.weight)

    def forward(self, a):
        return _apply_mixer("lfm2_attention", _attention_forward, a,
                            self.weights(), self.c)


class Lfm2DenseFFN(nn.Layer):
    """``W2 (silu(h W1) * h W3)``, under `generation._mlp_params`'s
    names."""

    def __init__(self, c: Lfm2MoeConfig):
        super().__init__()
        self.gate_proj = _lin(c.hidden_size, c.intermediate_size)   # W1
        self.up_proj = _lin(c.hidden_size, c.intermediate_size)     # W3
        self.down_proj = _lin(c.intermediate_size, c.hidden_size)   # W2

    def forward(self, a):
        from ..nn import functional as F
        return self.down_proj(F.silu(self.gate_proj(a)) * self.up_proj(a))


def _routed(c: Lfm2MoeConfig) -> MoELayer:
    return MoELayer(
        c.hidden_size, c.moe_intermediate_size, c.num_experts,
        top_k=c.num_experts_per_tok, activation="swiglu", dropless=True,
        renormalize=c.norm_topk_prob, score="sigmoid",
        correction_bias=c.use_expert_bias,
        routed_scale=c.routed_scaling_factor)


MIXERS = {CONV: Lfm2ShortConv, ATTN: Lfm2Attention, DENSE: Lfm2DenseFFN,
          MOE: _routed}


class Lfm2Block(nn.Layer):
    """``x + mixer(RMSNorm(x))`` with ONE mixer, of the kind its letter
    of the pattern names; a published layer is two of these
    (``operator_norm`` then ``ffn_norm``)."""

    def __init__(self, c: Lfm2MoeConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(c.hidden_size, c.norm_eps)
        self.mixer = MIXERS[kind](c)

    @property
    def mlp(self):      # what `generation._mlp_params` reads of an FFN block
        return self.mixer

    def forward(self, x):
        return x + self.mixer(self.norm(x))


class Lfm2MoeModel(nn.Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [Lfm2Block(config, kind) for kind in config.pattern])
        self.embedding_norm = nn.RMSNorm(config.hidden_size,
                                         config.norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.embedding_norm(x)


class Lfm2MoeForCausalLM(nn.Layer):
    """The head is the embedding (``tie_embedding``): no ``lm_head``
    parameter."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.model = Lfm2MoeModel(config)

    def forward(self, input_ids):
        return apply("lfm2_head", lambda x, e: x @ e.T,
                     [self.model(input_ids),
                      self.model.embed_tokens.weight])
