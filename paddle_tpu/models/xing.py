"""Xing 4.0 (``XingChen-AGI/Xing4.0-29B-A4B``, ``model_type``
``xing4_0``): the DeepSeek-V3 layer — latent attention with q-lora and
yarn, a sigmoid router with a correction bias, a shared expert — under
a residual of ``hc_mult`` STREAMS mixed by manifold-constrained
hyper-connections (mHC, arXiv:2512.24880, over hyper-connections,
arXiv:2409.19606), built over `models/deepseek.py`.

Source of the layout: the published ``config.json`` and the two papers.
A token's state is ``X`` [n, C] (``n = hc_mult``, ``C = hidden_size``),
carried as [.., n C] with stream j in columns [j C, (j + 1) C).  Entry:
every stream the embedding.  Each SUBLAYER ``F`` (a layer has two: the
mixer, the FFN) owns ``phi`` [n C, n^2 + 2 n], ``b`` [n^2 + 2 n] and
``a`` = (a_pre, a_post, a_res); in float32::

    r     = (mean(vec(X)^2) + rms_norm_eps)^-1/2     ONE scalar a token
    u     = r (vec(X) phi)                           = RMSNorm(vec X) phi
    Hpre  = sigmoid(a_pre u[0:n] + b[0:n])
    Hpost = 2 sigmoid(a_post u[n:2n] + b[n:2n])
    Z     = clamp(a_res u[2n:] + b[2n:], mhc_h_res_clamp)   [n, n] row-major
    Hres  = exp(Z), hc_sinkhorn_iters times: columns / (their sum +
            hc_eps), then rows / (their sum + hc_eps)
    x_in  = sum_j Hpre_j X_j
    y     = F(RMSNorm_g(x_in))                       the sublayer's own norm
    X'_i  = sum_j Hres[i, j] X_j + Hpost_i y

Exit: the SUM of the streams, then the model's last RMSNorm and the
head.  The mixer is `deepseek.MLAttention`, the FFN `LlamaMLP` in the
first ``first_k_dense_replace`` layers and `incubate.moe.MoELayer`
after: ``s = sigmoid(h W_r)`` over ALL ``n_routed_experts`` in float32;
the ``num_experts_per_tok`` experts are chosen on ``s + bias``
(``topk_method`` ``noaux_tc``: the bias picks and does not weigh),
weights the chosen ``s`` over their sum (``norm_topk_prob``) times
``routed_scaling_factor``; ``n_group`` = ``topk_group`` = 1 is no group
limit; ``n_shared_experts`` shared SwiGLU experts as one of their
summed width, ungated.

What the config leaves open is listed as ``assumed`` in
``benchmarks/configs/xing4.0-29b-a4b-serve-ep4-d20.json``.  One chip's
share of an expert-parallel deployment is an argument, as in
`models/axk1.py`: ``experts_held = (first, count)``, ``vocab_size`` the
rows held.  The prediction block (``num_nextn_predict_layers``) feeds
no logit of the main pass and is not built.  The serving engine runs
the same mixing through `ops.pallas_mhc`
(`serving.engine.ServingEngine._chain_unified_body`: the latent
family's blocks under `_HyperResidual`).
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from .. import nn
from ..core.dispatch import apply
from ..nn import initializer as I
from ..ops.pallas_mhc import mhc_enter, mhc_exit
from ..ops.references import (mhc_pack, mhc_post_reference,
                              mhc_pre_reference)
from .deepseek import (DeepSeekV2Config, DeepSeekV2DecoderLayer,
                       DeepSeekV2ForCausalLM, DeepSeekV2Model)

__all__ = ["xing_config", "xing_tiny_config", "XingForCausalLM",
           "XingModel", "XingDecoderLayer", "HyperConnection"]


@functools.partial(jax.jit, static_argnames=("n", "eps", "hc_eps", "iters",
                                              "clamp"))
def _pre(xa, phi, b, a, **knobs):
    """x [.., n C] -> (x_in [.., C], coef [.., 128]): ONE compiled call
    (eagerly the twenty iterations are hundreds of dispatches)."""
    lead = xa.shape[:-1]
    phi_t, ab = mhc_pack(phi, b, a, knobs["n"], xa.dtype)
    x_in, coef = mhc_pre_reference(xa.reshape(-1, xa.shape[-1]), phi_t, ab,
                                   **knobs)
    return x_in.reshape(lead + (-1,)), coef.reshape(lead + (-1,))


@functools.partial(jax.jit, static_argnames=("n",))
def _post(xa, ya, ca, *, n):
    out = mhc_post_reference(
        xa.reshape(-1, xa.shape[-1]), ya.reshape(-1, ya.shape[-1]),
        ca.reshape(-1, ca.shape[-1]), n=n)
    return out.reshape(xa.shape)


class _ResidualBias(I.Initializer):
    """``b``: zeros, but ``diag`` on the residual matrix's diagonal, so
    that Hres starts near the identity."""

    def __init__(self, n: int, diag: float):
        self.n, self.diag = n, diag

    def __call__(self, shape, dtype):
        import jax.numpy as jnp
        n = self.n
        b = np.zeros(shape, np.float32)
        b[2 * n:] = (self.diag * np.eye(n)).reshape(-1)
        return jnp.asarray(b, dtype)


class HyperConnection(nn.Layer):
    """One sublayer's mixing: its parameters, and the stream's two
    passes around ``F`` (the plain `jnp` forms; float32 inside)."""

    def __init__(self, c: DeepSeekV2Config):
        super().__init__()
        self.c = c
        n, C = c.hc_mult, c.hidden_size
        k = n * n + 2 * n
        self.phi = self.create_parameter(
            [n * C, k],
            default_initializer=I.Normal(0.0, float((n * C) ** -0.5)))
        self.b = self.create_parameter(
            [k], default_initializer=_ResidualBias(n, 4.0))
        self.a = self.create_parameter(
            [3], default_initializer=I.Constant(1.0))

    def _knobs(self) -> dict:
        c = self.c
        return dict(n=c.hc_mult, eps=c.rms_norm_eps, hc_eps=c.hc_eps,
                    iters=c.hc_sinkhorn_iters, clamp=c.mhc_h_res_clamp)

    def pre(self, x):
        """x [B, S, n C] -> (x_in [B, S, C], coef [B, S, 128])."""
        return apply("mhc_pre", functools.partial(_pre, **self._knobs()),
                     [x, self.phi, self.b, self.a])

    def post(self, x, y, coef):
        return apply("mhc_post", functools.partial(_post, n=self.c.hc_mult),
                     [x, y, coef])


class XingDecoderLayer(DeepSeekV2DecoderLayer):
    def __init__(self, c: DeepSeekV2Config, layer_idx: int = 0):
        super().__init__(c, layer_idx)
        self.hc_attn = HyperConnection(c)
        self.hc_ffn = HyperConnection(c)

    def forward(self, x, cos, sin, attn_mask=None):
        a, coef = self.hc_attn.pre(x)
        x = self.hc_attn.post(
            x, self.self_attn(self.input_layernorm(a), cos, sin, attn_mask),
            coef)
        a, coef = self.hc_ffn.pre(x)
        return self.hc_ffn.post(
            x, self.mlp(self.post_attention_layernorm(a)), coef)


class XingModel(DeepSeekV2Model):
    layer_cls = XingDecoderLayer

    def enter(self, x):
        n = self.config.hc_mult
        return apply("mhc_enter", lambda xa: mhc_enter(
            xa.reshape(-1, xa.shape[-1]), n).reshape(
                xa.shape[:-1] + (-1,)), [x])

    def exit(self, x):
        n = self.config.hc_mult
        return apply("mhc_exit", lambda xa: mhc_exit(
            xa.reshape(-1, xa.shape[-1]), n).reshape(
                xa.shape[:-1] + (-1,)), [x])


class XingForCausalLM(DeepSeekV2ForCausalLM):
    model_cls = XingModel


def xing_config(*, n_routed_experts=64, num_experts_per_tok=4,
                n_shared_experts=1, moe_intermediate_size=1024,
                topk_method="noaux_tc", moe_layer_freq=1,
                mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
                num_nextn_predict_layers=1, experts_held=None,
                rope_positions=8192, **published) -> DeepSeekV2Config:
    """A `DeepSeekV2Config` from the published keys, under their
    published names (defaults: the published values)."""
    if topk_method != "noaux_tc":
        raise NotImplementedError(
            f"topk_method {topk_method!r}: this family's router carries "
            f"a correction bias (noaux_tc)")
    if moe_layer_freq != 1:
        raise NotImplementedError("moe_layer_freq must be 1")
    if published.pop("tie_word_embeddings", False):
        raise NotImplementedError("Xing 4.0 has an untied head")
    if published.pop("attention_bias", False):
        raise NotImplementedError("attention_bias")
    if published.pop("hidden_act", "silu") != "silu":
        raise NotImplementedError("hidden_act must be silu")
    del num_nextn_predict_layers    # the prediction block is not built
    base = dict(
        vocab_size=131072, hidden_size=3584, intermediate_size=9216,
        num_hidden_layers=40, num_attention_heads=32,
        num_key_value_heads=32, max_position_embeddings=262144,
        rms_norm_eps=1e-6, rope_theta=10000.0,
        rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096},
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, first_k_dense_replace=2,
        n_group=1, topk_group=1, scoring_func="sigmoid",
        norm_topk_prob=True, routed_scaling_factor=2.0, hc_mult=4,
        hc_sinkhorn_iters=20, hc_eps=1e-6)
    base.update(published)
    if base["hc_mult"] < 2:
        raise NotImplementedError(
            "hc_mult < 2 is the plain residual: `models.axk1`'s family")
    return DeepSeekV2Config(
        num_experts=n_routed_experts, top_k=num_experts_per_tok,
        moe_intermediate_size=moe_intermediate_size,
        shared_expert_intermediate_size=(n_shared_experts
                                         * moe_intermediate_size),
        moe_dropless=True, experts_held=experts_held,
        rope_positions=rope_positions, correction_bias=True,
        mhc_h_res_clamp=(mhc_h_res_clamp_min, mhc_h_res_clamp_max), **base)


def xing_tiny_config(**kw) -> DeepSeekV2Config:
    """Toy widths with every mechanism on: four streams and twenty
    iterations kept, hidden 64, 2 dense + 2 routed layers, 8 experts of
    which 4 a token, q-lora, a latent of one whole 128-lane register,
    yarn with mscale^2 != 1, a shared expert, the routed scale."""
    base = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=4, max_position_embeddings=4096,
        rope_scaling={"type": "yarn", "factor": 16, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 64},
        q_lora_rank=48, kv_lora_rank=128, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, n_routed_experts=8,
        num_experts_per_tok=4, moe_intermediate_size=32,
        rope_positions=512)
    base.update(kw)
    return xing_config(**base)
