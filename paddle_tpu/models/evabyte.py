"""EvaByte decoder family (``EvaByte/EvaByte``, ``model_type``
``evabyte``): a byte-level dense decoder whose every layer is
chunk-summary (EVA) attention, Zheng et al., arXiv:2302.04542, as the
EvaByte release uses it.

Source of the layout: the published ``config.json`` (``attention_class``
``eva``, ``chunk_size`` 16, ``window_size`` 2048, ``num_pred_heads`` 8,
``norm_add_unit_offset``, ``fp32_skip_add``, ``fp32_logits``).  The
layer, head ``h`` of width ``d``, ``s = d^-1/2``, window ``W``, chunk
``c``, positions from 0:

1. ``x`` is the residual stream in float32.  ``u = RMSNorm(x; gain
   1 + g)`` cast to the weights' type; ``q, k, v = u Wq, u Wk, u Wv``;
   RoPE (rotate-half) on ``q`` and ``k`` at absolute positions.
2. Chunk ``j`` holds tokens ``[c j, c j + c)``: ``a_m = softmax_m(s
   phi_h . k_m)``, ``k~_j = sum a_m k_m + mu_h``, ``v~_j = sum a_m
   v_m``; ``phi_h``, ``mu_h`` learned a head and layer.
3. Query ``t`` in window ``w = t // W`` sees the pooled rows of every
   chunk of windows ``< w`` and the exact rows ``W w <= m <= t``, under
   ONE softmax.  For ``t < W`` this is plain causal attention.
4. ``x = x + o Wo``; ``x = x + SwiGLU(RMSNorm(x))``: float32 adds.
5. Final RMSNorm; ``logits = float32(u W_head)`` [num_pred_heads x
   vocab_size], head 0 the next byte.

What the config leaves open is listed as ``assumed`` in
``benchmarks/configs/evabyte-6.5b-serve-pp4-d8.json``.  This forward is
the whole-sequence form (every chunk pooled, then masked); the serving
engine keeps the pooled rows in its page cache
(`serving.engine.ServingEngine._eva_unified_body`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import apply
from ..nn import initializer as I
from .llama import LlamaMLP

__all__ = ["EvaByteConfig", "EvaByteModel", "EvaByteForCausalLM",
           "evabyte_tiny_config"]


class EvaByteConfig:
    """The published keys (same names; defaults: the published values)
    plus ``rope_positions``, the rows of the rotary table a forward
    builds."""

    def __init__(self, vocab_size=320, hidden_size=4096,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=32, num_key_value_heads=32,
                 max_position_embeddings=32768, rms_norm_eps=1e-5,
                 rope_theta=100000.0, rope_scaling=None,
                 attention_class="eva", chunk_size=16, window_size=2048,
                 num_pred_heads=8, norm_add_unit_offset=True,
                 fp32_skip_add=True, fp32_logits=True, fp32_ln=False,
                 mixedp_attn=True, attention_bias=False,
                 hidden_act="silu", tie_word_embeddings=False,
                 rope_positions=None):
        if attention_class != "eva":
            raise NotImplementedError(f"attention_class {attention_class!r}")
        if num_key_value_heads != num_attention_heads:
            raise NotImplementedError("EVA pools a chunk per query head: "
                                      "one KV head a query head")
        if rope_scaling is not None or attention_bias or \
                tie_word_embeddings or hidden_act != "silu":
            raise NotImplementedError(
                "rope_scaling / attention_bias / tied head / activation")
        if not (norm_add_unit_offset and fp32_skip_add and fp32_logits):
            raise NotImplementedError(
                "the release's norm_add_unit_offset, fp32_skip_add and "
                "fp32_logits are what this family implements")
        if window_size % chunk_size:
            raise ValueError("window_size must be whole chunks")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.chunk_size = chunk_size
        self.window_size = window_size
        self.num_pred_heads = num_pred_heads
        self.fuse_attention_ffn = False     # LlamaMLP reads it
        self.rope_positions = min(int(rope_positions or 8192),
                                  max_position_embeddings)


def evabyte_tiny_config(**kw) -> EvaByteConfig:
    """Toy widths with every mechanism on: a window of 4 chunks of 4,
    so a few dozen tokens cross several closes, and 3 byte heads."""
    base = dict(vocab_size=64, hidden_size=64, intermediate_size=128,
                num_hidden_layers=3, num_attention_heads=2,
                num_key_value_heads=2, max_position_embeddings=1024,
                chunk_size=4, window_size=16, num_pred_heads=3,
                rope_positions=512)
    base.update(kw)
    return EvaByteConfig(**base)


def rope_table(theta: float, head_dim: int, n: int):
    """(cos, sin) float32 [n, head_dim / 2]."""
    inv = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                          / head_dim)
    f = np.outer(np.arange(n, dtype=np.float64), inv)
    return jnp.asarray(np.cos(f), jnp.float32), \
        jnp.asarray(np.sin(f), jnp.float32)


def _rope(x, cos, sin):
    """Rotate-half on x [B, S, h, D]."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[None, :x.shape[1], None, :].astype(x.dtype)
    s = sin[None, :x.shape[1], None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def eva_visible(S: int, window: int, chunk: int):
    """(exact [S, S], pooled [S, S // chunk]) bool: which exact keys and
    which chunks' pooled rows the query at each position sees."""
    t = jnp.arange(S)[:, None]
    m = jnp.arange(S)[None, :]
    j = jnp.arange(S // chunk)[None, :]
    w0 = t // window * window
    return (m <= t) & (m >= w0), (j + 1) * chunk <= w0


def _unit_offset_norm(x, g, eps):
    """RMSNorm of float32 x with gain 1 + g."""
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
    return y * (1.0 + g.astype(jnp.float32))


class EvaAttention(nn.Layer):
    def __init__(self, c: EvaByteConfig):
        super().__init__()
        self.c = c
        H, D = c.num_attention_heads, c.head_dim
        lin = lambda i, o: nn.Linear(i, o, bias_attr=False)  # noqa: E731
        self.q_proj = lin(c.hidden_size, H * D)
        self.k_proj = lin(c.hidden_size, H * D)
        self.v_proj = lin(c.hidden_size, H * D)
        self.o_proj = lin(H * D, c.hidden_size)
        self.adaptive_phi = self.create_parameter(
            [H, D], default_initializer=I.Normal(0.0, 1.0))
        self.adaptive_mu_k = self.create_parameter(
            [H, D], default_initializer=I.Normal(0.0, 1.0))

    def forward(self, u, cos, sin):
        """u [B, S, hidden] (normed, the weights' type) -> o Wo in
        float32."""
        c = self.c
        B, S, _ = u.shape
        H, D, W, ck = (c.num_attention_heads, c.head_dim, c.window_size,
                       c.chunk_size)
        scale = D ** -0.5

        def impl(h, wq, wk, wv, wo, phi, mu):
            f32 = jnp.float32
            q = _rope((h @ wq).reshape(B, S, H, D), cos, sin)
            k = _rope((h @ wk).reshape(B, S, H, D), cos, sin)
            v = (h @ wv).reshape(B, S, H, D)
            # pool every whole chunk (a trailing part is never visible)
            n = S // ck
            kc = k[:, :n * ck].reshape(B, n, ck, H, D)
            vc = v[:, :n * ck].reshape(B, n, ck, H, D)
            a = jax.nn.softmax(scale * jnp.einsum(
                "bnchd,hd->bnch", kc.astype(f32), phi.astype(f32)), 2)
            kt = (jnp.einsum("bnch,bnchd->bnhd", a, kc.astype(f32))
                  + mu.astype(f32)).astype(k.dtype)
            vt = jnp.einsum("bnch,bnchd->bnhd", a,
                            vc.astype(f32)).astype(v.dtype)
            exact, pooled = eva_visible(S, W, ck)
            s_x = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(f32) * scale
            s_p = jnp.einsum("bqhd,bnhd->bhqn", q, kt).astype(f32) * scale
            s = jnp.concatenate([jnp.where(pooled, s_p, -jnp.inf),
                                 jnp.where(exact, s_x, -jnp.inf)], -1)
            p = jax.nn.softmax(s, -1).astype(v.dtype)
            o = jnp.einsum("bhqk,bkhd->bqhd", p,
                           jnp.concatenate([vt, v], 1))
            return jnp.dot(o.reshape(B, S, H * D), wo,
                           preferred_element_type=f32)

        return apply("eva_attention", impl,
                     [u, self.q_proj.weight, self.k_proj.weight,
                      self.v_proj.weight, self.o_proj.weight,
                      self.adaptive_phi, self.adaptive_mu_k])


class _UnitOffsetNorm(nn.Layer):
    """RMSNorm with gain ``1 + weight`` (``norm_add_unit_offset``): the
    parameter is the offset ``g``, zero at initialisation."""

    def __init__(self, hidden_size, epsilon):
        super().__init__()
        self.epsilon = epsilon
        self.weight = self.create_parameter(
            [hidden_size], default_initializer=I.Constant(0.0))

    def forward(self, x, dtype):
        eps = self.epsilon
        return apply("unit_offset_rms_norm",
                     lambda x, g: _unit_offset_norm(x, g, eps).astype(dtype),
                     [x, self.weight])


class EvaByteDecoderLayer(nn.Layer):
    def __init__(self, c: EvaByteConfig):
        super().__init__()
        self.input_layernorm = _UnitOffsetNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = EvaAttention(c)
        self.post_attention_layernorm = _UnitOffsetNorm(c.hidden_size,
                                                        c.rms_norm_eps)
        self.mlp = LlamaMLP(c)

    def forward(self, x, cos, sin, dtype):
        """x float32 [B, S, hidden]: the residual adds stay float32."""
        x = x + self.self_attn(self.input_layernorm(x, dtype), cos, sin)
        return x + self.mlp(
            self.post_attention_layernorm(x, dtype)).astype("float32")


class EvaByteModel(nn.Layer):
    def __init__(self, config: EvaByteConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [EvaByteDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = _UnitOffsetNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        c = self.config
        e = self.embed_tokens(input_ids)
        dtype = e.dtype
        S = e.shape[1]
        if S > c.rope_positions:
            raise ValueError(f"{S} positions exceed rope_positions "
                             f"{c.rope_positions}")
        cos, sin = rope_table(c.rope_theta, c.head_dim, S)
        x = e.astype("float32")
        for layer in self.layers:
            x = layer(x, cos, sin, dtype)
        return self.norm(x, dtype)


class EvaByteForCausalLM(nn.Layer):
    """``lm_head`` holds the ``num_pred_heads`` byte heads side by side:
    columns [i * vocab_size, (i + 1) * vocab_size) are head i."""

    def __init__(self, config: EvaByteConfig):
        super().__init__()
        self.config = config
        self.model = EvaByteModel(config)
        self.lm_head = nn.Linear(config.hidden_size,
                                 config.num_pred_heads * config.vocab_size,
                                 bias_attr=False)

    def forward(self, input_ids):
        """float32 logits [B, S, num_pred_heads, vocab_size]."""
        c = self.config
        u = self.model(input_ids)
        return apply(
            "evabyte_heads",
            lambda u, w: jnp.dot(
                u, w, preferred_element_type=jnp.float32).reshape(
                    u.shape[:-1] + (c.num_pred_heads, c.vocab_size)),
            [u, self.lm_head.weight])
