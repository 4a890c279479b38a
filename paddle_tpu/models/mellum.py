"""Mellum decoder family (JetBrains Mellum2-12B-A2.5B pattern): three
sliding-window attention layers to one full layer, each kind with its
own rotary table, and a routed FFN in EVERY layer (softmax router,
top-k over all experts, renormalised; no shared expert, no dense
layer).  Built over the Llama backbone's attention
(`llama.LlamaAttention`, given a static window) and the dropless routed
FFN (`incubate.moe.MoELayer`), so the trainer steps it through the same
builder as a dense Llama (`trainer/pretrain.py`: the decoder FAMILY is
the model's own, `MellumConfig.pretrain_family`).

Source of the layout: the published ``config.json`` of
``JetBrains/Mellum2-12B-A2.5B-Instruct`` (``model_type`` ``mellum``:
``layer_types``, ``rope_parameters`` by layer kind, ``sliding_window``,
the Qwen-MoE expert keys).  The equations, rows x [T, hidden], RMSNorm
in float32 with ``rms_norm_eps``:

1. ``h = RMSNorm(x; input_layernorm)``; ``q = h Wq`` [T, n_q, D],
   ``k = h Wk``, ``v = h Wv`` [T, n_kv, D], no bias; rotate-half RoPE
   over all D dims at the absolute position — ``sliding_attention``
   layers the default table, ``full_attention`` layers YaRN inverse
   frequencies (HF ``_compute_yarn_parameters``) with cos and sin times
   ``attention_factor``; ``a = softmax(q k^T / sqrt(D)) v``, GQA, key j
   visible to query i iff ``j <= i`` and, on sliding layers,
   ``i - j < sliding_window`` (the query itself counts, as in HF);
   ``x = x + a Wo``.
2. ``h2 = RMSNorm(x; post_attention_layernorm)``;
   ``g = softmax_f32(h2 Wr)`` over ALL ``num_experts``; ``e = top_k(g)``;
   ``w = g[e] / sum g[e]`` (``norm_topk_prob``);
   ``x = x + sum_j w_j Wd^{e_j} (silu(h2 Wg^{e_j}) * h2 Wu^{e_j})``.
   One chip's share of an expert-parallel layer is a constructor
   argument, not a second model: ``experts_held = (first, count)``
   stacks only those experts; the sum then runs over the chosen experts
   that lie in the share, with ``w`` normalised over all k chosen.
3. ``logits = RMSNorm(x; norm) W_head``, untied; ``vocab_size`` is the
   rows of the vocabulary held here.
4. ``loss = mean_t CE(logits_t, label_t) + router_aux_loss_coef *
   sum_layers L_aux``, ``L_aux = E * sum_e P_e F_e``, ``P_e = mean_t
   g_t[e]``, ``F_e = sum over the k choices of mean_t [e_tj = e]`` (the
   family's ``load_balancing_loss_func``: all k choices,
   `incubate.moe.load_balance_loss_all_choices`), over all E outputs.

What the config does not say is listed as ``assumed`` in
``benchmarks/configs/mellum2-12b-a2.5b-train-ep4-d4.json``: no q / k
normalisation, no MTP head (next-token loss only), the coefficient of
``L_aux``, a float32 router.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import nn
from ..distributed.parallel_layers import (
    MP_AXIS, ParallelCrossEntropy, annotate_sequence_parallel as _held)
from ..incubate.moe import MoELayer
from ..nn import initializer as I
from ..observability.attribution import scope as _scope
from .laguna import FULL, SLIDING, rope_table
from .llama import LlamaAttention, LlamaConfig

__all__ = ["MellumConfig", "MellumDecoderLayer", "MellumModel",
           "MellumForCausalLM", "mellum_tiny_config", "KINDS"]

#: the layer kinds, in the order of the stacked rotary tables' rows
KINDS = (SLIDING, FULL)


class MellumConfig(LlamaConfig):
    """The published keys (same names) over the Llama backbone's, plus
    ``experts_held`` and ``router_aux_loss_coef``.  The seeded draw is
    Xavier, residual-writing projections times ``1 / sqrt(2 L)``."""

    def __init__(self, vocab_size=98304, hidden_size=2304,
                 intermediate_size=7168, num_hidden_layers=28,
                 num_attention_heads=32, num_key_value_heads=4,
                 head_dim=128, max_position_embeddings=131072,
                 rms_norm_eps=1e-6, num_experts=64, num_experts_per_tok=8,
                 moe_intermediate_size=896, norm_topk_prob=True,
                 sliding_window=1024,
                 layer_types: Optional[Sequence[str]] = None,
                 rope_parameters=None, router_aux_loss_coef=0.001,
                 experts_held: Optional[Tuple[int, int]] = None,
                 tie_word_embeddings=False, **kw):
        if tie_word_embeddings:
            raise NotImplementedError("Mellum has an untied head")
        for fused in ("fuse_attention_qkv", "fuse_attention_ffn"):
            if kw.get(fused):
                raise NotImplementedError(f"{fused} with a Mellum layer")
        kw.setdefault("sequence_parallel", False)
        super().__init__(
            vocab_size=vocab_size, hidden_size=hidden_size,
            intermediate_size=intermediate_size,
            num_hidden_layers=num_hidden_layers,
            num_attention_heads=num_attention_heads,
            num_key_value_heads=num_key_value_heads, head_dim=head_dim,
            max_position_embeddings=max_position_embeddings,
            rms_norm_eps=rms_norm_eps, tie_word_embeddings=False, **kw)
        L = num_hidden_layers
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.norm_topk_prob = norm_topk_prob
        self.sliding_window = int(sliding_window)
        self.rope_parameters = rope_parameters or {
            FULL: {"rope_type": "default", "rope_theta": 500000.0},
            SLIDING: {"rope_type": "default", "rope_theta": 500000.0}}
        # a list longer than the depth is the published one, cut
        self.layer_types = tuple(
            layer_types[:L] if layer_types is not None
            else [FULL if i % 4 == 3 else SLIDING for i in range(L)])
        if len(self.layer_types) != L:
            raise ValueError("layer_types shorter than num_hidden_layers")
        for t in self.layer_types:
            if t not in KINDS:
                raise ValueError(f"unknown layer type {t!r}")
        self.router_aux_loss_coef = float(router_aux_loss_coef)
        self.experts_held = tuple(int(v) for v in experts_held) \
            if experts_held else None

    def window_of(self, kind: str) -> Optional[int]:
        return self.sliding_window if kind == SLIDING else None

    def rope_tables(self, n: int):
        """(cos, sin) float32 [len(KINDS), n, head_dim / 2]: a row of the
        leading axis a layer kind, the attention factor folded in."""
        tabs = [rope_table(self.rope_parameters[kind], self.head_dim, n)
                for kind in KINDS]
        return (jnp.stack([c for c, _ in tabs]),
                jnp.stack([s for _, s in tabs]))

    # ---- what `trainer.pretrain` reads of a decoder family
    def pretrain_family(self):
        from ..trainer.pretrain import DecoderFamily
        return DecoderFamily(
            build=MellumForCausalLM,
            # the scan's template is the built model's first layer: a
            # second draw of a layer's experts would be thrown away
            layer=lambda lm: lm.model.layers[0],
            layer_prefix="model.layers.",
            embed_key="model.embed_tokens.weight",
            norm_key="model.norm.weight", head_key="lm_head.weight",
            kinds=self.layer_types, rope=self.rope_tables,
            aux_coef=self.router_aux_loss_coef)


def mellum_tiny_config(**kw) -> MellumConfig:
    """One period at toy widths with every mechanism on: a window
    shorter than the positions, yarn on the full layer, routed experts."""
    base = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32, sliding_window=24,
        rope_parameters={
            FULL: {"rope_type": "yarn", "rope_theta": 500000.0,
                   "factor": 16.0, "original_max_position_embeddings": 32,
                   "beta_fast": 32.0, "beta_slow": 1.0,
                   "attention_factor": 1.2772588722239782},
            SLIDING: {"rope_type": "default", "rope_theta": 500000.0}})
    base.update(kw)
    return MellumConfig(**base)


class MellumDecoderLayer(nn.Layer):
    """One layer of EITHER kind: the parameters have one shape, the kind
    (its window, its row of the rotary tables) is an argument of the
    call, so that a scan over layers binds one template."""

    def __init__(self, c: MellumConfig):
        super().__init__()
        self.c = c
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = LlamaAttention(c)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        self.mlp = MoELayer(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            top_k=c.num_experts_per_tok, activation="swiglu",
            dropless=True, renormalize=c.norm_topk_prob,
            experts_held=c.experts_held, aux_choices="all")
        # the seeded draw: residual-writing projections at Xavier over
        # sqrt(2 L)
        res = 1.0 / math.sqrt(2.0 * c.num_hidden_layers)
        for w in (self.self_attn.o_proj.weight, self.mlp.w_down):
            w._data = w._data * jnp.asarray(res, w._data.dtype)

    def forward(self, x, cos, sin, kind: str = FULL):
        """`cos` / `sin` [len(KINDS), S, D/2] (`MellumConfig.rope_tables`).
        The routed FFN's load-balance term and `routing_stats` are left
        on `self.mlp` (`l_aux`, `l_stats`)."""
        row = KINDS.index(kind)
        with _scope("attn_norm"):
            hn = _held(self.input_layernorm(x))
        h = _held(x + self.self_attn(hn, cos[row], sin[row],
                                     window=self.c.window_of(kind)))
        with _scope("ffn_norm"):
            hn = _held(self.post_attention_layernorm(h))
        with _scope("routed_ffn"):
            out = _held(h + self.mlp(hn))
        return out


class MellumModel(nn.Layer):
    def __init__(self, config: MellumConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.embed_tokens.weight._data = I.Normal(
            0.0, config.initializer_range)(
                [config.vocab_size, config.hidden_size], "float32")
        self.embed_tokens.weight._sharding_spec = P(MP_AXIS, None)
        self.layers = nn.LayerList(
            [MellumDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def aux_loss(self):
        """Sum of the layers' load-balance terms of the last forward."""
        total = None
        for layer in self.layers:
            la = layer.mlp.l_aux
            total = la if total is None else total + la
        return total

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        cos, sin = self.config.rope_tables(x.shape[1])
        for layer, kind in zip(self.layers, self.config.layer_types):
            x = layer(x, cos, sin, kind)
        return self.norm(x)


class MellumForCausalLM(nn.Layer):
    def __init__(self, config: MellumConfig):
        super().__init__()
        self.config = config
        self.model = MellumModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)
        self.lm_head.weight._sharding_spec = P(None, MP_AXIS)

    def forward(self, input_ids, labels=None):
        logits = self.lm_head(self.model(input_ids))
        if labels is None:
            return logits
        loss = ParallelCrossEntropy()(logits, labels).mean()
        aux = self.model.aux_loss()
        if self.config.router_aux_loss_coef:
            loss = loss + aux * self.config.router_aux_loss_coef
        return loss, logits

