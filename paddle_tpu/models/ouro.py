"""Ouro decoder family (``ByteDance/Ouro-2.6B``, ``model_type``
``ouro``): a LOOPED decoder — the whole layer list runs
``total_ut_steps`` times a token with ONE set of weights (Ouro / LoopLM,
"Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741).

Source of the layout: the published ``config.json`` (48 layers, hidden
2048, 16 heads x 128 over 16 KV heads, SwiGLU 5632, vocabulary 49,152
untied, ``rope_theta`` 1e6, ``rms_norm_eps`` 1e-6, ``total_ut_steps``
4, ``early_exit_threshold`` 1).  ``H`` hidden, head width ``d``, ``s =
d^-1/2``, ``U`` passes, positions from 0:

1. ``x_0 = E[tok]``: the embedding enters before the first pass only.
2. Pass ``u`` applies layers ``l`` in order, the SAME weights in every
   pass.  A layer has four RMSNorms, a sandwich: ``a = RMSNorm(x;
   g1)``; ``q, k, v = a Wq, a Wk, a Wv`` (no bias); RoPE (rotate-half)
   on ``q`` and ``k`` at the token's absolute position, the same in
   every pass; causal attention over the keys and values THIS pass of
   THIS layer made (a pass never reads another pass's rows); ``x = x +
   RMSNorm(o Wo; g2)``; ``m = RMSNorm(x; g3)``; ``x = x +
   RMSNorm(SwiGLU(m); g4)``.
3. Every pass ends with the model's last norm, ``h_u = RMSNorm(x;
   g_f)``, and ``h_u`` is what the next pass starts from.  ``lambda_u =
   sigmoid(h_u . w_e + b_e)`` (``early_exit_gate``); ``p(u) = lambda_u
   prod_{j<u} (1 - lambda_j)`` for ``u < U - 1`` and ``p(U - 1) =
   prod_{j<U-1} (1 - lambda_j)``.  A token leaves at the first pass
   whose cumulative ``p`` reaches ``early_exit_threshold``; at the
   published threshold 1 that is the last pass for every token:
   ``logits = h_{U-1} W_head``.

What the config leaves open is listed as ``assumed`` in
``benchmarks/configs/ouro-2.6b-serve-whole.json``.  This is the
whole-sequence forward; the serving engine keeps a cache slot for every
(pass, layer) (`serving.engine.ServingEngine._looped_unified_body`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply
from .evabyte import rope_table
from .llama import LlamaAttention, LlamaConfig, LlamaMLP

__all__ = ["OuroConfig", "OuroModel", "OuroForCausalLM", "ouro_tiny_config",
           "exit_distribution"]


class OuroConfig(LlamaConfig):
    """The published keys (same names; defaults: the published values)
    plus ``rope_positions``, the rows of the rotary table a forward
    builds."""

    def __init__(self, vocab_size=49152, hidden_size=2048,
                 intermediate_size=5632, num_hidden_layers=48,
                 num_attention_heads=16, num_key_value_heads=16,
                 head_dim=128, max_position_embeddings=65536,
                 rope_theta=1000000.0, rms_norm_eps=1e-6,
                 total_ut_steps=4, early_exit_threshold=1.0,
                 rope_scaling=None, attention_bias=False,
                 hidden_act="silu", tie_word_embeddings=False,
                 sliding_window=None, use_flash_attention=True,
                 rope_positions=None):
        if rope_scaling is not None or attention_bias or \
                tie_word_embeddings or hidden_act != "silu" or \
                sliding_window is not None:
            raise NotImplementedError(
                "rope_scaling / attention_bias / tied head / activation / "
                "sliding_window")
        if total_ut_steps < 1:
            raise ValueError("total_ut_steps must be >= 1")
        super().__init__(
            vocab_size=vocab_size, hidden_size=hidden_size,
            intermediate_size=intermediate_size,
            num_hidden_layers=num_hidden_layers,
            num_attention_heads=num_attention_heads,
            num_key_value_heads=num_key_value_heads, head_dim=head_dim,
            max_position_embeddings=max_position_embeddings,
            rope_theta=float(rope_theta), rms_norm_eps=rms_norm_eps,
            use_flash_attention=use_flash_attention,
            sequence_parallel=False)
        self.total_ut_steps = int(total_ut_steps)
        self.early_exit_threshold = float(early_exit_threshold)
        self.rope_positions = min(int(rope_positions or 8192),
                                  max_position_embeddings)


def ouro_tiny_config(**kw) -> OuroConfig:
    """Toy widths with every mechanism on: 3 layers run 3 times."""
    base = dict(vocab_size=96, hidden_size=64, intermediate_size=128,
                num_hidden_layers=3, num_attention_heads=2,
                num_key_value_heads=2, head_dim=32,
                max_position_embeddings=1024, total_ut_steps=3,
                rope_positions=512)
    base.update(kw)
    return OuroConfig(**base)


def exit_distribution(lam):
    """``lam`` [..., U] the gates ``lambda_u`` of the passes -> ``p``
    [..., U], equation 3's exit distribution (it sums to 1: the last
    pass takes what no earlier one took)."""
    lam = lam.astype(jnp.float32)
    stay = jnp.cumprod(1.0 - lam[..., :-1], -1)
    before = jnp.concatenate([jnp.ones_like(lam[..., :1]), stay], -1)
    take = jnp.concatenate([lam[..., :-1], jnp.ones_like(lam[..., :1])], -1)
    return take * before


class OuroDecoderLayer(nn.Layer):
    """Sandwich norms: one before each sublayer and one on its OUTPUT,
    before the add (the release's ``input_layernorm_2`` and
    ``post_attention_layernorm_2``)."""

    def __init__(self, c: OuroConfig):
        super().__init__()
        norm = lambda: nn.RMSNorm(c.hidden_size, c.rms_norm_eps)  # noqa: E731
        self.input_layernorm = norm()
        self.self_attn = LlamaAttention(c)
        self.input_layernorm_2 = norm()
        self.post_attention_layernorm = norm()
        self.mlp = LlamaMLP(c)
        self.post_attention_layernorm_2 = norm()

    def forward(self, x, cos, sin):
        x = x + self.input_layernorm_2(
            self.self_attn(self.input_layernorm(x), cos, sin))
        return x + self.post_attention_layernorm_2(
            self.mlp(self.post_attention_layernorm(x)))


class OuroModel(nn.Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [OuroDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.early_exit_gate = nn.Linear(config.hidden_size, 1)

    def forward(self, input_ids):
        """(``h`` a list of the ``U`` passes' normed states [B, S,
        hidden], ``p`` [B, S, U] the exit distribution)."""
        c = self.config
        x = self.embed_tokens(input_ids)
        S = x.shape[1]
        if S > c.rope_positions:
            raise ValueError(f"{S} positions exceed rope_positions "
                             f"{c.rope_positions}")
        cos, sin = rope_table(c.rope_theta, c.head_dim, S)
        hs, lams = [], []
        for _ in range(c.total_ut_steps):
            for layer in self.layers:
                x = layer(x, cos, sin)
            x = self.norm(x)
            hs.append(x)
            lams.append(self.early_exit_gate(x))
        p = apply("ouro_exit_distribution",
                  lambda *g: exit_distribution(jax.nn.sigmoid(
                      jnp.concatenate(g, -1))), lams)
        return hs, p


class OuroForCausalLM(nn.Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()
        self.config = config
        self.model = OuroModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    def forward(self, input_ids, return_passes: bool = False):
        """Logits [B, S, vocab] of the state each token LEAVES with: the
        first pass whose cumulative ``p`` reaches
        ``early_exit_threshold``, the last pass at the published 1.
        ``return_passes``: also the passes' states and ``p``."""
        c = self.config
        hs, p = self.model(input_ids)
        h = hs[-1]
        if c.early_exit_threshold < 1.0:
            def leave(p, *hs):
                reached = jnp.cumsum(p, -1) >= c.early_exit_threshold
                u = jnp.argmax(reached.at[..., -1].set(True), -1)
                return jnp.take_along_axis(
                    jnp.stack(hs, -2), u[..., None, None], -2)[..., 0, :]
            h = apply("ouro_early_exit", leave, [p, *hs])
        logits = self.lm_head(h)
        return (logits, hs, p) if return_passes else logits
