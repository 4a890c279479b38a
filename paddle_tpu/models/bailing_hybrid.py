"""Ling 3.0 family (``inclusionAI/Ling-3.0-flash``, ``model_type``
``bailing_hybrid``): a HYBRID decoder whose layers mix with a Kimi
delta attention (KDA) linear-attention layer or, every
``layer_group_size``-th, a gated latent-attention layer, and whose FFNs
are dense in the first ``first_k_dense_replace`` layers and routed in
the others.

Source of the layout: the published ``config.json``; the forms are the
families' (KDA, arXiv:2510.26692: a gated delta rule with a decay for
every key channel; DeepSeek's latent attention; a sigmoid router with a
per-expert bias of the choice, limited to the best groups).  ``H``
hidden, RMSNorm with plain gain, no bias anywhere, positions from 0.
Layer ``i`` is ``x += mixer_i(RMSNorm(x))``, ``x += ffn_i(RMSNorm(x))``
— TWO blocks of one mixer each, in the letters of ``pattern``:

``K`` (KDA, where ``(i + 1) % layer_group_size != 0``): ``q~, k~, v~ =
  a W_q, a W_k, a W_v`` (heads x head_dim each); each through a depthwise
  causal convolution of ``short_conv_kernel_size`` (zeros before position
  0) and SiLU; per head ``q, k`` L2-normalised, ``q`` scaled by
  ``head_dim^-1/2``.  ``g_t = kda_lower_bound x sigmoid(exp(A_log[h])
  x (a W_f + dt_bias))`` per key channel, in (kda_lower_bound, 0);
  ``beta_t = sigmoid(a W_beta)`` per head.  State ``S_h`` [head_dim,
  head_dim] float32: ``S' = Diag(exp(g_t)) S_{t-1}``; ``S_t = S' +
  beta_t k_t (v_t - S'^T k_t)^T``; ``o_t = S_t^T q_t``.  Then per head
  ``RMSNorm(o; gain [head_dim]) x sigmoid(a W_g)[h]``, then ``W_o``.
``L`` (latent attention, no query rank): ``q = a W_q`` -> heads of
  ``(q_nope | q_pe)``; ``a W_kva = (latent | k_pe)``; the latent
  RMS-normed; RoPE on INTERLEAVED pairs (2j, 2j + 1) of ``q_pe``,
  ``k_pe``; ``(k_nope | v) = latent W_kvb`` a head; causal softmax at
  ``qk_head_dim^-1/2``; each head's output times ``sigmoid(a W_g)[h]``;
  ``W_o``.
``D``: SwiGLU of ``intermediate_size``.
``E``: ``s = sigmoid(a_f32 W_r)`` over ALL ``num_experts``; the choice on
  ``s + b``, limited to the ``topk_group`` best of ``n_group`` groups of
  consecutive experts (a group's mark the sum of its two best), top
  ``num_experts_per_tok``; ``w = routed_scaling_factor x s / sum s``;
  SwiGLU experts of ``moe_intermediate_size``; one shared SwiGLU expert
  added ungated.

What the config leaves open is listed as ``assumed`` in
``benchmarks/configs/ling-3.0-flash-serve-ep8-d7.json``.  One chip's
share of an expert-parallel deployment is an argument: ``experts_held =
(first, count)`` stacks only those experts (the router keeps its
published width), ``vocab_size`` is the rows of the vocabulary held
here, ``layers_held`` the PUBLISHED indices of the layers built (the
pattern and the SwiGLU limit lists read them).  A nonzero entry of
``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list`` for a
layer that is built is refused: the clamp's form is not in the config.
The prediction block (``num_nextn_predict_layers``) feeds no logit of
the main pass and is not built.  This is the whole-sequence forward
from zero state; the serving engine keeps a slot of state a sequence
(`serving.engine.ServingEngine._chain_unified_body`, whose blocks
`serving.engine._chain_of` reads off the pattern).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn import initializer as I
from ..ops.pallas_kda import kda_chunk_scan
from .nemotron_h import (_apply_mixer, _apply_routed, _lin, arrays,
                         ssm_conv)

__all__ = ["arrays", "BailingHybridConfig", "BailingHybridModel",
           "BailingHybridForCausalLM", "bailing_hybrid_config",
           "bailing_hybrid_tiny_config", "kda_operands", "kda_gated_norm",
           "interleaved_to_halves", "KDA_SUB_CHUNK"]

KDA, LATENT, DENSE, MOE = "K", "L", "D", "E"
#: rows of a sub-chunk of the KDA scan (`ops.pallas_kda.kda_chunk_scan`)
KDA_SUB_CHUNK = 64


class BailingHybridConfig:
    """The published keys under their published names (defaults: the
    published values of Ling-3.0-flash)."""

    def __init__(self, vocab_size=157184, hidden_size=2560,
                 intermediate_size=6144, num_hidden_layers=42,
                 layers_held=None, num_attention_heads=32,
                 num_key_value_heads=32, num_kv_heads_for_linear_attn=0,
                 head_dim=128, layer_group_size=6, first_k_dense_replace=2,
                 short_conv_kernel_size=4, kda_lower_bound=-5.0,
                 kda_safe_gate=True, no_kda_lora=True, use_kda_lora=False,
                 linear_silu=True, group_norm_size=1, use_qk_norm=True,
                 gated_attention_proj_granularity_type="head_wise",
                 q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rope_theta=6000000.0,
                 rope_interleave=True, rope_scaling=None, num_experts=512,
                 num_experts_per_tok=8, num_shared_experts=1,
                 moe_intermediate_size=768,
                 moe_shared_expert_intermediate_size=768, n_group=8,
                 topk_group=4, norm_topk_prob=True,
                 routed_scaling_factor=2.5, score_function="sigmoid",
                 moe_router_enable_expert_bias=True,
                 expert_swiglu_limit_list=None,
                 share_expert_swiglu_limit_list=None, rms_norm_eps=1e-6,
                 max_position_embeddings=262144, hidden_act="silu",
                 use_bias=False, use_qkv_bias=False,
                 tie_word_embeddings=False, experts_held=None,
                 kda_sub_chunk=KDA_SUB_CHUNK):
        if hidden_act != "silu" or not linear_silu:
            raise NotImplementedError("hidden_act / linear_silu")
        if use_bias or use_qkv_bias or tie_word_embeddings:
            raise NotImplementedError("a bias / a tied head")
        if not kda_safe_gate or not no_kda_lora or use_kda_lora:
            raise NotImplementedError(
                "the KDA gate is kda_safe_gate with a full-rank W_f")
        if num_kv_heads_for_linear_attn not in (0, num_attention_heads):
            raise NotImplementedError("num_kv_heads_for_linear_attn")
        if group_norm_size != 1 or not use_qk_norm or \
                gated_attention_proj_granularity_type != "head_wise":
            raise NotImplementedError(
                "group_norm_size / use_qk_norm / the gate's granularity")
        if q_lora_rank or rope_scaling is not None or not rope_interleave:
            raise NotImplementedError(
                "q_lora_rank / rope_scaling / rope_interleave")
        if score_function != "sigmoid" or num_shared_experts != 1 or \
                not moe_router_enable_expert_bias:
            raise NotImplementedError(
                "score_function / num_shared_experts / the expert bias")
        if num_key_value_heads != num_attention_heads:
            raise NotImplementedError("num_key_value_heads")
        held = list(range(num_hidden_layers)) if layers_held is None \
            else [int(i) for i in layers_held]
        if not held or sorted(set(held)) != held or held[0] < 0 \
                or held[-1] >= num_hidden_layers:
            raise ValueError(f"layers_held {layers_held}: ascending "
                             f"published indices under {num_hidden_layers}")
        for name, limits in (
                ("expert_swiglu_limit_list", expert_swiglu_limit_list),
                ("share_expert_swiglu_limit_list",
                 share_expert_swiglu_limit_list)):
            on = [i for i in held if limits and limits[i]]
            if on:
                raise NotImplementedError(
                    f"{name} is nonzero for the layers {on}: the form of "
                    f"the SwiGLU clamp is not in the published config and "
                    f"is not guessed; hold layers whose limit is 0")
        self.layers_held = tuple(held)
        self.published_layers = num_hidden_layers
        self.num_hidden_layers = len(held)
        self.pattern = "".join(
            (LATENT if (i + 1) % layer_group_size == 0 else KDA)
            + (DENSE if i < first_k_dense_replace else MOE) for i in held)
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.intermediate_size = intermediate_size
        self.num_attention_heads = num_attention_heads
        self.head_dim = head_dim
        self.layer_group_size = layer_group_size
        self.first_k_dense_replace = first_k_dense_replace
        self.conv_kernel = short_conv_kernel_size
        self.kda_lower_bound = float(kda_lower_bound)
        self.kda_sub_chunk = int(kda_sub_chunk)
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim, self.qk_rope_head_dim = (qk_nope_head_dim,
                                                        qk_rope_head_dim)
        self.v_head_dim = v_head_dim
        self.rope_theta = float(rope_theta)
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.n_group, self.topk_group = n_group, topk_group
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rms_norm_eps = self.layer_norm_epsilon = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        if experts_held is not None:
            first, count = (int(v) for v in experts_held)
            if not (0 <= first and count >= 1
                    and first + count <= num_experts):
                raise ValueError(f"experts_held {experts_held} outside "
                                 f"0..{num_experts}")
            experts_held = (first, count)
        self.experts_held = experts_held

    @property
    def conv_dim(self) -> int:
        """The three convolved streams side by side: q | k | v."""
        return 3 * self.num_attention_heads * self.head_dim

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def rope_table(self, n: int):
        from .evabyte import rope_table
        return rope_table(self.rope_theta, self.qk_rope_head_dim, n)


def bailing_hybrid_config(**published) -> BailingHybridConfig:
    """A `BailingHybridConfig` from the published keys; keys that say
    nothing of the main pass's shape are taken and dropped."""
    for k in ("model_type", "num_nextn_predict_layers", "mtp_use_kda",
              "mtp_loss_scaling_factor", "max_window_layers",
              "partial_rotary_factor", "rotary_dim", "qk_head_dim",
              "scale_router_input", "scoring_func", "seq_aux",
              "topk_method", "up_proj_norm", "use_mla_nope", "use_nGPT",
              "value_norm"):
        published.pop(k, None)
    return BailingHybridConfig(**published)


def bailing_hybrid_tiny_config(**kw) -> BailingHybridConfig:
    """Toy widths with every mechanism on: one whole period after the
    dense layer (published layers 0, 2..7 of 8), 4 heads of 16, sub-chunks
    of 8, a latent of 32 + 8, 16 experts in 4 groups of which 2 stay
    (top 4), a shared expert."""
    base = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                num_hidden_layers=8, layers_held=(0, 2, 3, 4, 5, 6, 7),
                num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, rope_theta=10000.0, num_experts=16,
                num_experts_per_tok=4, moe_intermediate_size=24,
                moe_shared_expert_intermediate_size=24, n_group=4,
                topk_group=2, max_position_embeddings=1024,
                kda_sub_chunk=8)
    base.update(kw)
    return BailingHybridConfig(**base)


# ---------------------------------------------------------------------------
# the mixers' parts, shared with the serving engine
# ---------------------------------------------------------------------------

def kda_operands(u, f, b, L, c: BailingHybridConfig):
    """The recurrence's operands, float32, from the convolved ``u`` [T,
    3 x heads x head_dim] (q | k | v), the raw gate ``f`` [T, heads x
    head_dim] and the raw ``b`` [T, heads]: (q, k [T, H, D] L2-normalised,
    q scaled; v [T, H, D]; g [T, H, D] the log decay in
    (kda_lower_bound, 0); beta [T, H])."""
    H, D = c.num_attention_heads, c.head_dim
    f32 = jnp.float32
    T = u.shape[0]
    q, k, v = (u[:, j * H * D:(j + 1) * H * D].reshape(T, H, D).astype(f32)
               for j in range(3))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    a = jnp.exp(L["A_log"].astype(f32))[None, :, None]
    g = c.kda_lower_bound * jax.nn.sigmoid(
        a * (f.astype(f32) + L["dt_bias"].astype(f32)).reshape(T, H, D))
    return (unit(q) * D ** -0.5, unit(k), v, g,
            jax.nn.sigmoid(b.astype(f32)))


def kda_gated_norm(o, gate, gain, eps: float):
    """``RMSNorm(o; gain)`` over each head's width, times the head's
    ``sigmoid(gate)``: o [T, H, D] float32, gate [T, H], gain [D] ->
    [T, H x D] float32 (the norm first, then the gate)."""
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps)
    o = o * gain.astype(jnp.float32) \
        * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]
    return o.reshape(o.shape[0], -1)


def interleaved_to_halves(d: int):
    """The column order that turns a rope over INTERLEAVED pairs (2j,
    2j + 1) into one over halves (j, j + d/2): even columns, then odd."""
    return np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])


def _rope_interleaved(t, cos, sin):
    """t [S, ..., d]: pairs (2j, 2j + 1) turned by the row's angle j."""
    shape = t.shape
    t = t.reshape(shape[:-1] + (shape[-1] // 2, 2)).astype(jnp.float32)
    ex = (slice(None),) + (None,) * (t.ndim - 3)
    c, s = cos[ex], sin[ex]
    out = jnp.stack([t[..., 0] * c - t[..., 1] * s,
                     t[..., 1] * c + t[..., 0] * s], -1)
    return out.reshape(shape)


def _kda_forward(a, L, c: BailingHybridConfig):
    """The ``K`` mixer on one sequence a [S, hidden] from zero state."""
    H, D = c.num_attention_heads, c.head_dim
    u = jnp.concatenate([a @ L["wq"], a @ L["wk"], a @ L["wv"]], -1)
    K = c.conv_kernel
    u = ssm_conv(jnp.concatenate([jnp.zeros((K - 1,) + u.shape[1:],
                                            u.dtype), u]),
                 jnp.concatenate([L["q_conv"], L["k_conv"], L["v_conv"]]),
                 None)
    q, k, v, g, beta = kda_operands(u, a @ L["wf"], a @ L["wb"], L, c)
    o, _ = kda_chunk_scan(q, k, v, g, beta,
                          jnp.zeros((H, D, D), jnp.float32),
                          chunk=c.kda_sub_chunk)
    y = kda_gated_norm(o, a @ L["wgate"], L["norm_g"], c.rms_norm_eps)
    return y.astype(a.dtype) @ L["wo"]


def _latent_forward(a, L, c: BailingHybridConfig):
    """The ``L`` mixer on one sequence a [S, hidden], in the plain
    (not absorbed) form."""
    S = a.shape[0]
    nh, dn, dr, dv, r = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank)
    f32 = jnp.float32
    cos, sin = c.rope_table(S)
    q = (a @ L["wq"]).reshape(S, nh, dn + dr)
    kva = a @ L["wkva"]
    lat = kva[:, :r].astype(f32)
    lat = (lat * jax.lax.rsqrt((lat * lat).mean(-1, keepdims=True)
                               + c.rms_norm_eps)
           * L["gkv"].astype(f32)).astype(a.dtype)
    q_pe = _rope_interleaved(q[..., dn:], cos, sin)
    k_pe = _rope_interleaved(kva[:, r:], cos, sin)
    kv = (lat @ L["wkvb"]).reshape(S, nh, dn + dv)
    s = (jnp.einsum("tnd,snd->nts", q[..., :dn], kv[..., :dn])
         + jnp.einsum("tnd,sd->nts", q_pe.astype(a.dtype),
                      k_pe.astype(a.dtype))).astype(f32) * c.softmax_scale
    t = jnp.arange(S)
    s = jnp.where(t[:, None] >= t[None, :], s, -jnp.inf)
    o = jnp.einsum("nts,snd->tnd", jax.nn.softmax(s, -1).astype(a.dtype),
                   kv[..., dn:])
    o = o * jax.nn.sigmoid((a @ L["wgate"]).astype(f32))[..., None] \
        .astype(a.dtype)
    return o.reshape(S, nh * dv) @ L["wo"]


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

class BailingKDA(nn.Layer):
    def __init__(self, c: BailingHybridConfig):
        super().__init__()
        self.c = c
        Hd, H, D = c.hidden_size, c.num_attention_heads, c.head_dim
        self.q_proj, self.k_proj, self.v_proj = (_lin(Hd, H * D)
                                                 for _ in range(3))
        for n in ("q_conv", "k_conv", "v_conv"):
            setattr(self, n, self.create_parameter(
                [H * D, c.conv_kernel],
                default_initializer=I.Uniform(-0.5, 0.5)))
        self.f_proj = _lin(Hd, H * D)
        self.dt_bias = self.create_parameter(
            [H * D], default_initializer=I.Uniform(-1.0, 1.0))
        self.A_log = self.create_parameter(
            [H], default_initializer=I.Assign(
                np.log(np.linspace(1.0, 4.0, H)).astype(np.float32)))
        self.b_proj = _lin(Hd, H)
        self.g_proj = _lin(Hd, H)
        self.o_norm = nn.RMSNorm(D, c.rms_norm_eps)
        self.o_proj = _lin(H * D, Hd)

    def weights(self) -> dict:
        return dict(wq=self.q_proj.weight, wk=self.k_proj.weight,
                    wv=self.v_proj.weight, q_conv=self.q_conv,
                    k_conv=self.k_conv, v_conv=self.v_conv,
                    wf=self.f_proj.weight, dt_bias=self.dt_bias,
                    A_log=self.A_log, wb=self.b_proj.weight,
                    wgate=self.g_proj.weight, norm_g=self.o_norm.weight,
                    wo=self.o_proj.weight)

    def forward(self, a):
        return _apply_mixer("bailing_kda", _kda_forward, a, self.weights(),
                            self.c)


class BailingLatentAttention(nn.Layer):
    def __init__(self, c: BailingHybridConfig):
        super().__init__()
        self.c = c
        Hd, nh = c.hidden_size, c.num_attention_heads
        self.q_proj = _lin(Hd, nh * (c.qk_nope_head_dim
                                     + c.qk_rope_head_dim))
        self.kv_a_proj_with_mqa = _lin(Hd, c.kv_lora_rank
                                       + c.qk_rope_head_dim)
        self.kv_a_layernorm = nn.RMSNorm(c.kv_lora_rank, c.rms_norm_eps)
        self.kv_b_proj = _lin(c.kv_lora_rank,
                              nh * (c.qk_nope_head_dim + c.v_head_dim))
        self.g_proj = _lin(Hd, nh)
        self.o_proj = _lin(nh * c.v_head_dim, Hd)

    def weights(self) -> dict:
        return dict(wq=self.q_proj.weight,
                    wkva=self.kv_a_proj_with_mqa.weight,
                    gkv=self.kv_a_layernorm.weight,
                    wkvb=self.kv_b_proj.weight, wgate=self.g_proj.weight,
                    wo=self.o_proj.weight)

    def forward(self, a):
        return _apply_mixer("bailing_latent_attention", _latent_forward, a,
                            self.weights(), self.c)


class BailingDenseFFN(nn.Layer):
    def __init__(self, c: BailingHybridConfig):
        super().__init__()
        self.gate_proj = _lin(c.hidden_size, c.intermediate_size)
        self.up_proj = _lin(c.hidden_size, c.intermediate_size)
        self.down_proj = _lin(c.intermediate_size, c.hidden_size)

    def weights(self) -> dict:
        return dict(wg=self.gate_proj.weight, wu=self.up_proj.weight,
                    wd=self.down_proj.weight)

    def forward(self, a):
        return self.down_proj(nn.functional.silu(self.gate_proj(a))
                              * self.up_proj(a))


class BailingMoE(nn.Layer):
    """The router covers all ``num_experts``; the stacks hold
    ``experts_held = (first, count)`` of them (all, if None)."""

    def __init__(self, c: BailingHybridConfig):
        super().__init__()
        self.c = c
        Hd, Iw = c.hidden_size, c.moe_intermediate_size
        E = c.experts_held[1] if c.experts_held else c.num_experts
        Sw = c.moe_shared_expert_intermediate_size
        self.gate_weight = self.create_parameter(
            [Hd, c.num_experts], default_initializer=I.Normal(0.0, 0.02))
        self.expert_bias = self.create_parameter(
            [c.num_experts], default_initializer=I.Constant(0.0))
        self.w_gate = self.create_parameter([E, Hd, Iw])
        self.w_up = self.create_parameter([E, Hd, Iw])
        self.w_down = self.create_parameter([E, Iw, Hd])
        self.shared_gate = _lin(Hd, Sw)
        self.shared_up = _lin(Hd, Sw)
        self.shared_down = _lin(Sw, Hd)

    def weights(self) -> dict:
        """The tree `generation._ffn_apply` reads."""
        return dict(gate=self.gate_weight, bias=self.expert_bias,
                    wge=self.w_gate, wup=self.w_up, wdn=self.w_down,
                    shared=dict(sg=self.shared_gate.weight,
                                su=self.shared_up.weight,
                                sd=self.shared_down.weight))

    def static(self) -> dict:
        c = self.c
        return dict(top_k=c.num_experts_per_tok, renorm=c.norm_topk_prob,
                    score="sigmoid", scale=c.routed_scaling_factor,
                    held=c.experts_held, group=(c.n_group, c.topk_group))

    def forward(self, a):
        return _apply_routed("bailing_moe", a, self.weights(), self.static())


MIXERS = {KDA: BailingKDA, LATENT: BailingLatentAttention,
          DENSE: BailingDenseFFN, MOE: BailingMoE}


class BailingBlock(nn.Layer):
    """``x + mixer(RMSNorm(x))`` with ONE mixer, of the kind its letter
    of the pattern names; a published layer is two of these."""

    def __init__(self, c: BailingHybridConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.mixer = MIXERS[kind](c)

    def forward(self, x):
        return x + self.mixer(self.norm(x))


class BailingHybridModel(nn.Layer):
    def __init__(self, config: BailingHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [BailingBlock(config, kind) for kind in config.pattern])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class BailingHybridForCausalLM(nn.Layer):
    def __init__(self, config: BailingHybridConfig):
        super().__init__()
        self.config = config
        self.model = BailingHybridModel(config)
        self.lm_head = _lin(config.hidden_size, config.vocab_size)

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))

