"""Laguna decoder family (poolside Laguna-S-2.1 pattern): full and
sliding-window attention layers mixed in one stack, each kind with its
own query-head count and its own rotary table, a per-head output gate,
a leading dense SwiGLU layer and routed experts with a shared one.

Source of the layout: the published ``config.json`` of
``poolside/Laguna-S-2.1`` (``layer_types``, ``num_attention_heads_per_layer``,
``rope_parameters``, ``gating``, the Qwen2-MoE expert keys).  The layer,
with ``h = RMSNorm(x)``:

1. ``q = h Wq`` [n_q, D], ``k = h Wk``, ``v = h Wv`` [n_kv, D]; n_q is the
   layer's own (48 on full layers, 72 on sliding ones at the published
   sizes).
2. Rope in the rotate-half pairing over the first ``r = D *
   partial_rotary_factor`` dims; full layers take YaRN inverse
   frequencies with cos and sin times ``attention_factor``, sliding
   layers the default table.
3. Causal softmax attention, GQA; a sliding layer's query at position i
   sees keys j with ``i - window < j <= i``.
4. ``g = sigmoid(h Wg)`` [n_q]; head a's output times ``g[a]``; o-proj.
5. Dense SwiGLU on ``mlp_only_layers``, else softmax router over ALL
   experts, top-k, renormalise, times ``moe_routed_scaling_factor``,
   plus the shared expert, ungated.

What the config does not say is listed as ``assumed`` in
``benchmarks/configs/laguna-s-2.1-serve-ep8-d8.json``.

One chip's share of an expert-parallel deployment is a constructor
argument, not a second model: ``experts_held = (first, count)`` stacks
only those experts (`incubate.moe.MoELayer`), and ``vocab_size`` is the
rows of the vocabulary held here.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import apply
from ..incubate.moe import MoELayer
from .llama import LlamaMLP

__all__ = ["LagunaConfig", "LagunaModel", "LagunaForCausalLM",
           "laguna_tiny_config", "rope_inv_freq", "rope_table"]

FULL, SLIDING = "full_attention", "sliding_attention"


class LagunaConfig:
    """The published keys (same names), plus ``experts_held`` and
    ``rope_positions`` (rows of the rotary tables a forward builds; the
    published 1,048,576 positions would be 0.5 GB a layer kind)."""

    def __init__(self, vocab_size=100352, hidden_size=3072,
                 intermediate_size=12288, num_hidden_layers=48,
                 num_attention_heads=48, num_key_value_heads=8,
                 head_dim=128, max_position_embeddings=1048576,
                 rms_norm_eps=1e-6, num_experts=256,
                 num_experts_per_tok=10, moe_intermediate_size=1024,
                 shared_expert_intermediate_size=1024,
                 norm_topk_prob=True, mlp_only_layers=(0,),
                 sliding_window=512, rope_parameters=None,
                 layer_types: Optional[Sequence[str]] = None,
                 num_attention_heads_per_layer: Optional[Sequence[int]]
                 = None, moe_routed_scaling_factor=2.5, gating="per-head",
                 tie_word_embeddings=False,
                 experts_held: Optional[Tuple[int, int]] = None,
                 rope_positions: int = 8192):
        if tie_word_embeddings:
            raise NotImplementedError("Laguna has an untied head")
        if gating != "per-head":
            raise NotImplementedError(f"gating {gating!r}")
        L = num_hidden_layers
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = L
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = \
            shared_expert_intermediate_size
        self.norm_topk_prob = norm_topk_prob
        self.mlp_only_layers = tuple(mlp_only_layers)
        self.sliding_window = sliding_window
        self.rope_parameters = rope_parameters or {
            FULL: {"rope_type": "default", "rope_theta": 10000.0},
            SLIDING: {"rope_type": "default", "rope_theta": 10000.0}}
        # a list longer than the depth is the published one, cut
        self.layer_types = tuple(
            layer_types[:L] if layer_types is not None
            else [FULL if i % 4 == 0 else SLIDING for i in range(L)])
        self.num_attention_heads_per_layer = tuple(
            num_attention_heads_per_layer[:L]
            if num_attention_heads_per_layer is not None
            else [num_attention_heads] * L)
        if len(self.layer_types) != L or \
                len(self.num_attention_heads_per_layer) != L:
            raise ValueError("layer_types / heads per layer shorter than "
                             "num_hidden_layers")
        for t in self.layer_types:
            if t not in (FULL, SLIDING):
                raise ValueError(f"unknown layer type {t!r}")
        for nq in self.num_attention_heads_per_layer:
            if nq % num_key_value_heads:
                raise ValueError(f"{nq} query heads over "
                                 f"{num_key_value_heads} KV heads")
        self.moe_routed_scaling_factor = moe_routed_scaling_factor
        self.gating = gating
        self.tie_word_embeddings = False
        self.experts_held = tuple(experts_held) if experts_held else None
        self.rope_positions = min(int(rope_positions),
                                  max_position_embeddings)

    def window_of(self, i: int) -> Optional[int]:
        return self.sliding_window if self.layer_types[i] == SLIDING \
            else None


def laguna_tiny_config(**kw) -> LagunaConfig:
    """Two periods at toy widths with every mechanism on: unequal head
    counts, a window shorter than the positions, partial rotary + yarn
    on full layers, the gate, a dense layer 0 and routed experts."""
    base = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=8, num_attention_heads=6,
        num_key_value_heads=2, head_dim=32, max_position_embeddings=4096,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, sliding_window=24,
        num_attention_heads_per_layer=[6, 8, 8, 8] * 2,
        rope_parameters={
            FULL: {"rope_type": "yarn", "rope_theta": 500000.0,
                   "factor": 16.0, "original_max_position_embeddings": 64,
                   "beta_fast": 32.0, "beta_slow": 1.0,
                   "attention_factor": 1.2772588722239781,
                   "partial_rotary_factor": 0.5},
            SLIDING: {"rope_type": "default", "rope_theta": 10000.0,
                      "partial_rotary_factor": 1.0}},
        rope_positions=512)
    base.update(kw)
    return LagunaConfig(**base)


# ---------------------------------------------------------------- rope
def rope_inv_freq(rp: dict, head_dim: int):
    """(inverse frequencies [r/2] float64, attention factor, r) of one
    layer kind's ``rope_parameters`` entry.  ``yarn`` follows HF's
    ``_compute_yarn_parameters``: interpolated and extrapolated
    frequencies blended by a linear ramp between the correction dims of
    ``beta_fast`` and ``beta_slow`` at the original length."""
    r = int(head_dim * float(rp.get("partial_rotary_factor", 1.0)))
    base = float(rp["rope_theta"])
    pos_freqs = base ** (np.arange(0, r, 2, dtype=np.float64) / r)
    kind = rp.get("rope_type", "default")
    if kind == "default":
        return 1.0 / pos_freqs, 1.0, r
    if kind != "yarn":
        raise NotImplementedError(f"rope_type {kind!r}")
    factor = float(rp["factor"])
    orig = float(rp["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (r * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(float(rp.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction_dim(float(rp.get("beta_slow", 1)))),
               r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    inv = (1.0 / (factor * pos_freqs)) * (1.0 - extrapolation) \
        + (1.0 / pos_freqs) * extrapolation
    af = rp.get("attention_factor")
    if af is None:
        af = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return inv, float(af), r


def rope_table(rp: dict, head_dim: int, n: int, kernel_layout=False):
    """(cos, sin) float32 [n, r/2], the attention factor folded in.
    ``kernel_layout``: [n, head_dim/2] for a kernel that pairs dims
    (i, i + head_dim/2): the rotary pairs first, then identity (cos 1,
    sin 0) for the dims that pass through — see
    `generation._laguna_decode_params` for the matching column order."""
    inv, af, r = rope_inv_freq(rp, head_dim)
    f = np.outer(np.arange(n, dtype=np.float64), inv)
    cos, sin = np.cos(f) * af, np.sin(f) * af
    if kernel_layout and r < head_dim:
        pad = (head_dim - r) // 2
        cos = np.concatenate([cos, np.ones((n, pad))], 1)
        sin = np.concatenate([sin, np.zeros((n, pad))], 1)
    return jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)


def _rope(x, cos, sin):
    """Rotate-half over the first 2 * cos.shape[-1] dims of x
    [B, S, h, D]; the rest pass through."""
    r2 = cos.shape[-1]
    x1, x2, rest = x[..., :r2], x[..., r2:2 * r2], x[..., 2 * r2:]
    c = cos[None, :x.shape[1], None, :].astype(x.dtype)
    s = sin[None, :x.shape[1], None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


# -------------------------------------------------------------- layers
class LagunaAttention(nn.Layer):
    def __init__(self, c: LagunaConfig, layer_idx: int):
        super().__init__()
        self.c = c
        self.heads = c.num_attention_heads_per_layer[layer_idx]
        self.window = c.window_of(layer_idx)
        self.kind = c.layer_types[layer_idx]
        H, KV, D = self.heads, c.num_key_value_heads, c.head_dim
        lin = lambda i, o: nn.Linear(i, o, bias_attr=False)  # noqa: E731
        self.q_proj = lin(c.hidden_size, H * D)
        self.k_proj = lin(c.hidden_size, KV * D)
        self.v_proj = lin(c.hidden_size, KV * D)
        self.g_proj = lin(c.hidden_size, H)     # one gate scalar a head
        self.o_proj = lin(H * D, c.hidden_size)

    def forward(self, x, cos, sin):
        c = self.c
        B, S, _ = x.shape
        H, KV, D = self.heads, c.num_key_value_heads, c.head_dim
        rep, window = H // KV, self.window

        def impl(h, wq, wk, wv, wg, wo):
            q = _rope((h @ wq).reshape(B, S, H, D), cos, sin)
            k = _rope((h @ wk).reshape(B, S, KV, D), cos, sin)
            v = (h @ wv).reshape(B, S, KV, D)
            qg = q.reshape(B, S, KV, rep, D)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).astype(jnp.float32)
            i = jnp.arange(S)[:, None]
            j = jnp.arange(S)[None, :]
            seen = j <= i
            if window is not None:
                seen &= i - j < window
            s = jnp.where(seen, s * D ** -0.5, -jnp.inf)
            p = jax.nn.softmax(s, -1).astype(v.dtype)
            o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v).reshape(B, S, H, D)
            gate = jax.nn.sigmoid(h @ wg)                   # [B, S, H]
            return (o * gate[..., None]).reshape(B, S, H * D) @ wo

        return apply("laguna_attention", impl,
                     [x, self.q_proj.weight, self.k_proj.weight,
                      self.v_proj.weight, self.g_proj.weight,
                      self.o_proj.weight])


class _DenseWidth:
    """The two sizes LlamaMLP reads, for the dense layers."""

    def __init__(self, c: LagunaConfig):
        self.hidden_size = c.hidden_size
        self.intermediate_size = c.intermediate_size
        self.fuse_attention_ffn = False


class LagunaDecoderLayer(nn.Layer):
    def __init__(self, c: LagunaConfig, layer_idx: int):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = LagunaAttention(c, layer_idx)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        if layer_idx in c.mlp_only_layers:
            self.mlp = LlamaMLP(_DenseWidth(c))
        else:
            self.mlp = MoELayer(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                top_k=c.num_experts_per_tok, activation="swiglu",
                dropless=True, renormalize=c.norm_topk_prob,
                shared_expert_hidden=c.shared_expert_intermediate_size,
                experts_held=c.experts_held,
                routed_scale=c.moe_routed_scaling_factor)

    def forward(self, x, cos, sin):
        h = x + self.self_attn(self.input_layernorm(x), cos, sin)
        return h + self.mlp(self.post_attention_layernorm(h))


class LagunaModel(nn.Layer):
    def __init__(self, config: LagunaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [LagunaDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def rope_tables(self, n: int, kernel_layout: bool = False):
        """{layer kind: (cos, sin)} over ``n`` positions."""
        c = self.config
        return {kind: rope_table(c.rope_parameters[kind], c.head_dim, n,
                                 kernel_layout)
                for kind in sorted(set(c.layer_types))}

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        S = x.shape[1]
        if S > self.config.rope_positions:
            raise ValueError(f"{S} positions exceed rope_positions "
                             f"{self.config.rope_positions}")
        tables = self.rope_tables(S)
        for layer in self.layers:
            x = layer(x, *tables[layer.self_attn.kind])
        return self.norm(x)


class LagunaForCausalLM(nn.Layer):
    def __init__(self, config: LagunaConfig):
        super().__init__()
        self.config = config
        self.model = LagunaModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)

    def forward(self, input_ids, labels=None):
        logits = self.lm_head(self.model(input_ids))
        if labels is not None:
            from ..distributed.parallel_layers import ParallelCrossEntropy
            return ParallelCrossEntropy()(logits, labels).mean(), logits
        return logits
