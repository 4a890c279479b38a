"""Nemotron-H family (``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``,
``model_type`` ``nemotron_h``): a HYBRID decoder whose blocks hold ONE
mixer each — a Mamba-2 state-space layer (``M``), an attention layer
(``*``) or a latent routed FFN (``E``) — in the order
``hybrid_override_pattern`` spells.

Source of the layout: the published ``config.json``; the forms are the
family's (Mamba-2 / SSD, arXiv:2405.21060; a sigmoid router with a
per-expert correction bias; experts in a latent).  ``H`` hidden,
RMSNorm with plain gain, no bias but the convolution's, positions from
0.  Block ``l`` is ``x <- x + mixer_l(RMSNorm(x; g_l))``:

1. ``x_0 = Emb[tok]``; after the last block ``logits = RMSNorm(x; g_f)
   W_head`` (untied).
2. ``M``: ``[z | u | dt] = a W_in`` (widths ``d_inner = mamba_num_heads
   x mamba_head_dim``, ``d_inner + 2 x n_groups x ssm_state_size``,
   ``mamba_num_heads``); ``u = [x' | B | C]``.  ``u_t <- silu(b_c +
   sum_j w_c[:, j] u_{t-K+1+j})``: a depthwise causal convolution of
   ``conv_kernel`` K over the sequence, zeros before position 0.
   ``dt_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``, float32.
   For head ``h`` of group ``g``: ``S_t = exp(dt_t[h] A[h]) S_{t-1} +
   dt_t[h] x'_t[h] (outer) B_t[g]``, ``y_t[h] = S_t C_t[g] + D[h]
   x'_t[h]``.  ``y <- GroupRMSNorm(y silu(z); g_n)`` over ``n_groups``
   (the gate FIRST, then the norm); out ``y W_out``.
3. ``*``: ``q, k, v = a Wq, a Wk, a Wv``, causal softmax attention at
   scale ``head_dim^-1/2`` with NO rotary embedding, out ``o Wo``.
4. ``E``: ``s = sigmoid(a_f32 W_r)`` over ALL ``n_routed_experts``; the
   ``num_experts_per_tok`` experts with the largest ``s + b`` (``b`` the
   correction bias: it picks, it does not weigh); ``w_e = s_e / sum s x
   routed_scaling_factor``; ``c = a W_dn`` (``moe_latent_size``);
   expert ``e``: ``relu(c U_e)^2 V_e``; ``y = (sum_e w_e f_e(c)) W_up +
   relu(a U_s)^2 V_s`` (the shared expert, on the full width).

What the config leaves open is listed as ``assumed`` in
``benchmarks/configs/nemotron-3-super-serve-ep4-d11.json``.  One chip's
share of an expert-parallel deployment is an argument: ``experts_held =
(first, count)`` stacks only those experts (the router keeps its
published width), ``vocab_size`` is the rows of the vocabulary held
here.  The prediction block (``num_nextn_predict_layers``) feeds no
logit of the main pass and is not built.  This is the whole-sequence
forward from zero state; the serving engine keeps a slot of state a
sequence (`serving.engine.ServingEngine._chain_unified_body`, whose
blocks `serving.engine._chain_of` reads off the pattern).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import apply
from ..nn import initializer as I
from ..ops.pallas_ssm import ssm_chunk_scan

__all__ = ["arrays", "NemotronHConfig", "NemotronHModel", "NemotronHForCausalLM",
           "nemotron_h_config", "nemotron_h_tiny_config", "ssm_split",
           "ssm_conv", "ssm_gated_norm", "ssm_operands"]

MAMBA, ATTENTION, MOE = "M", "*", "E"


class NemotronHConfig:
    """The published keys under their published names (defaults: the
    published values of Nemotron-3-Super)."""

    def __init__(self, vocab_size=131072, hidden_size=4096,
                 hybrid_override_pattern=(
                     "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
                 num_hidden_layers=None, num_attention_heads=32,
                 num_key_value_heads=2, head_dim=128,
                 mamba_num_heads=128, mamba_head_dim=64, n_groups=8,
                 ssm_state_size=128, conv_kernel=4, chunk_size=128,
                 expand=2, n_routed_experts=512, num_experts_per_tok=22,
                 moe_intermediate_size=2688, moe_latent_size=1024,
                 moe_shared_expert_intermediate_size=5376,
                 n_shared_experts=1, routed_scaling_factor=5.0,
                 norm_topk_prob=True, n_group=1, topk_group=1,
                 layer_norm_epsilon=1e-5, max_position_embeddings=262144,
                 time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4, mlp_hidden_act="relu2",
                 mamba_hidden_act="silu", use_conv_bias=True,
                 use_bias=False, mamba_proj_bias=False, mlp_bias=False,
                 attention_bias=False, tie_word_embeddings=False,
                 sliding_window=None, experts_held=None):
        if mlp_hidden_act != "relu2" or mamba_hidden_act != "silu":
            raise NotImplementedError("mlp_hidden_act / mamba_hidden_act")
        if use_bias or mamba_proj_bias or mlp_bias or attention_bias \
                or not use_conv_bias:
            raise NotImplementedError(
                "no bias but the convolution's is implemented")
        if tie_word_embeddings or sliding_window is not None:
            raise NotImplementedError("tied head / sliding_window")
        if n_group != 1 or topk_group != 1:
            raise NotImplementedError("group-limited routing")
        if n_shared_experts != 1:
            raise NotImplementedError("n_shared_experts must be 1")
        if expand * hidden_size != mamba_num_heads * mamba_head_dim:
            raise ValueError("expand x hidden_size must be mamba_num_heads "
                             "x mamba_head_dim")
        pattern = str(hybrid_override_pattern)
        if num_hidden_layers is not None:
            pattern = pattern[:int(num_hidden_layers)]
        if not pattern or set(pattern) - {MAMBA, ATTENTION, MOE}:
            raise ValueError(f"pattern {pattern!r}: letters M, * and E")
        if mamba_num_heads % n_groups:
            raise ValueError("mamba_num_heads must be whole groups")
        self.hybrid_override_pattern = pattern
        self.num_hidden_layers = len(pattern)
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.mamba_num_heads, self.mamba_head_dim = (mamba_num_heads,
                                                     mamba_head_dim)
        self.n_groups, self.ssm_state_size = n_groups, ssm_state_size
        self.conv_kernel, self.chunk_size = conv_kernel, chunk_size
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_latent_size = moe_latent_size
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.layer_norm_epsilon = self.rms_norm_eps = layer_norm_epsilon
        self.max_position_embeddings = max_position_embeddings
        self.time_step_min, self.time_step_max = time_step_min, time_step_max
        self.time_step_floor = time_step_floor
        if experts_held is not None:
            first, count = (int(v) for v in experts_held)
            if not (0 <= first and count >= 1
                    and first + count <= n_routed_experts):
                raise ValueError(f"experts_held {experts_held} outside "
                                 f"0..{n_routed_experts}")
            experts_held = (first, count)
        self.experts_held = experts_held

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size


def nemotron_h_config(**published) -> NemotronHConfig:
    """A `NemotronHConfig` from the published keys; keys that say
    nothing of the main pass's shape are taken and dropped."""
    for k in ("model_type", "num_nextn_predict_layers",
              "mtp_hybrid_override_pattern", "num_logits_to_keep",
              "partial_rotary_factor", "rope_theta", "norm_eps",
              "rescale_prenorm_residual", "residual_in_fp32",
              "use_mamba_kernels", "moe_shared_expert_overlap",
              "intermediate_size"):
        published.pop(k, None)
    return NemotronHConfig(**published)


def nemotron_h_tiny_config(**kw) -> NemotronHConfig:
    """Toy widths with every mechanism on: the published stage's
    pattern, 8 heads of 8 in 2 groups over a state of 16, scan chunks
    of 8, 16 experts (top 4) in a latent of 32, a shared expert."""
    base = dict(vocab_size=96, hidden_size=32,
                hybrid_override_pattern="MEMEMEM*EME",
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
                ssm_state_size=16, conv_kernel=4, chunk_size=8,
                n_routed_experts=16, num_experts_per_tok=4,
                moe_intermediate_size=24, moe_latent_size=16,
                moe_shared_expert_intermediate_size=40,
                routed_scaling_factor=2.5, max_position_embeddings=1024)
    base.update(kw)
    return NemotronHConfig(**base)


# ---------------------------------------------------------------------------
# the state-space mixer's parts, shared with the serving engine
# ---------------------------------------------------------------------------

def ssm_split(zxbcdt, c: NemotronHConfig):
    """``a W_in`` [..., d_inner + conv_dim + heads] -> (z, u, dt)."""
    d, w = c.d_inner, c.conv_dim
    return zxbcdt[..., :d], zxbcdt[..., d:d + w], zxbcdt[..., d + w:]


def ssm_conv(u_ext, w, b, act=jax.nn.silu):
    """The depthwise causal convolution and its activation (silu; None:
    none, LFM2's short convolution): ``u_ext`` [L + K - 1, W] is the K -
    1 rows before the L rows, then the rows; ``w`` [W, K], ``b`` [W] or
    None (no bias).  Float32 inside; returns [L, W] in ``u_ext``'s
    type."""
    K = w.shape[-1]
    L = u_ext.shape[0] - (K - 1)
    f32 = jnp.float32
    acc = 0.0 if b is None else b.astype(f32)[None]
    for j in range(K):
        acc = acc + w[:, j].astype(f32)[None] * u_ext[j:j + L].astype(f32)
    return (acc if act is None else act(acc)).astype(u_ext.dtype)


def ssm_operands(u, dt, L, c: NemotronHConfig):
    """The recurrence's operands from the convolved ``u`` [T, conv_dim]
    and the raw ``dt`` [T, heads]: (x [T, H, P], dt [T, H] float32 after
    the softplus, dA [T, H] = dt A, B, C [T, G, N])."""
    H, P, G, N = (c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                  c.ssm_state_size)
    f32 = jnp.float32
    T = u.shape[0]
    x = u[:, :H * P].reshape(T, H, P)
    bm = u[:, H * P:H * P + G * N].reshape(T, G, N)
    cm = u[:, H * P + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt.astype(f32) + L["dt_bias"].astype(f32))
    dA = dt * -jnp.exp(L["A_log"].astype(f32))
    return x, dt, dA, bm, cm


def ssm_gated_norm(y, z, g, groups: int, eps: float):
    """``GroupRMSNorm(y silu(z); g)``: the gate first, then the norm
    over ``groups`` equal parts of the last axis.  Float32 inside."""
    f32 = jnp.float32
    v = y.astype(f32) * jax.nn.silu(z.astype(f32))
    shape = v.shape
    v = v.reshape(shape[:-1] + (groups, shape[-1] // groups))
    v = v * jax.lax.rsqrt((v * v).mean(-1, keepdims=True) + eps)
    return v.reshape(shape) * g.astype(f32)


def arrays(w):
    """A mixer's ``weights()`` as plain arrays (dicts walked by hand: a
    parameter is a pytree of its own)."""
    return {k: arrays(v) if isinstance(v, dict) else v._data
            for k, v in w.items()}


def _mamba_forward(a, L, c: NemotronHConfig):
    """The ``M`` mixer on one sequence a [S, hidden] from zero state."""
    H, P, N = c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size
    f32 = jnp.float32
    z, u, dt = ssm_split(a @ L["w_in"], c)
    K = c.conv_kernel
    u = ssm_conv(jnp.concatenate([jnp.zeros((K - 1,) + u.shape[1:],
                                            u.dtype), u]),
                 L["conv_w"], L["conv_b"])
    x, dt, dA, bm, cm = ssm_operands(u, dt, L, c)
    xf = x.astype(f32)
    y, _ = ssm_chunk_scan(xf * dt[..., None], dA, bm, cm,
                          jnp.zeros((P, N, H), f32), chunk=c.chunk_size)
    y = y + L["D"].astype(f32)[None, :, None] * xf
    y = ssm_gated_norm(y.reshape(-1, H * P), z, L["norm_g"], c.n_groups,
                       c.layer_norm_epsilon)
    return y.astype(a.dtype) @ L["w_out"]


def _attention_forward(a, L, c: NemotronHConfig):
    """The ``*`` mixer on one sequence a [S, hidden]: no rotary."""
    S = a.shape[0]
    Hq, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    q = (a @ L["wq"]).reshape(S, KV, Hq // KV, D)
    k = (a @ L["wk"]).reshape(S, KV, D)
    v = (a @ L["wv"]).reshape(S, KV, D)
    s = jnp.einsum("tgrd,sgd->grts", q, k).astype(jnp.float32) * D ** -0.5
    t = jnp.arange(S)
    s = jnp.where(t[:, None] >= t[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, -1).astype(v.dtype)
    o = jnp.einsum("grts,sgd->tgrd", p, v)
    return o.reshape(S, Hq * D) @ L["wo"]


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def _lin(i, o):
    return nn.Linear(i, o, bias_attr=False)


def _apply_mixer(name, fn, a, w, c):
    """``fn(sequence [S, hidden], weights, config)`` over the batch of
    ``a`` as one dispatched op."""
    names = sorted(w)

    def impl(a, *vals):
        L = dict(zip(names, vals))
        return jax.vmap(lambda s: fn(s, L, c))(a)

    return apply(name, impl, [a] + [w[k] for k in names])


def _apply_routed(name, a, weights, st):
    """`generation._ffn_apply` over a routed layer's ``weights()`` tree
    and its static knobs ``st``, as one dispatched op."""
    from ..generation import _ffn_apply
    flat, tree = jax.tree_util.tree_flatten(weights)

    def impl(a, *vals):
        mo = jax.tree_util.tree_unflatten(tree, vals)
        return _ffn_apply(dict(moe=mo), a, st)

    return apply(name, impl, [a] + flat)


class _DtBias(I.Initializer):
    """``dt_bias`` such that softplus(dt_bias) is log-uniform in
    [time_step_min, time_step_max], not under time_step_floor."""

    def __init__(self, c: NemotronHConfig):
        self.c = c

    def __call__(self, shape, dtype):
        c = self.c
        rng = np.random.default_rng(int(np.prod(shape)))
        dt = np.exp(rng.uniform(math.log(c.time_step_min),
                                math.log(c.time_step_max), shape))
        dt = np.maximum(dt, c.time_step_floor)
        return jnp.asarray(dt + np.log(-np.expm1(-dt)), dtype)


class NemotronHMamba(nn.Layer):
    def __init__(self, c: NemotronHConfig):
        super().__init__()
        self.c = c
        H = c.mamba_num_heads
        self.in_proj = _lin(c.hidden_size, c.d_inner + c.conv_dim + H)
        self.conv_weight = self.create_parameter(
            [c.conv_dim, c.conv_kernel],
            default_initializer=I.Uniform(-0.5, 0.5))
        self.conv_bias = self.create_parameter(
            [c.conv_dim], default_initializer=I.Constant(0.0))
        self.dt_bias = self.create_parameter(
            [H], default_initializer=_DtBias(c))
        self.A_log = self.create_parameter(
            [H], default_initializer=I.Assign(
                np.log(np.linspace(1.0, 16.0, H)).astype(np.float32)))
        self.D = self.create_parameter(
            [H], default_initializer=I.Constant(1.0))
        self.norm = nn.RMSNorm(c.d_inner, c.layer_norm_epsilon)
        self.out_proj = _lin(c.d_inner, c.hidden_size)

    def weights(self) -> dict:
        return dict(w_in=self.in_proj.weight, conv_w=self.conv_weight,
                    conv_b=self.conv_bias, dt_bias=self.dt_bias,
                    A_log=self.A_log, D=self.D, norm_g=self.norm.weight,
                    w_out=self.out_proj.weight)

    def forward(self, a):
        return _apply_mixer("nemotron_h_mamba", _mamba_forward, a,
                            self.weights(), self.c)


class NemotronHAttention(nn.Layer):
    def __init__(self, c: NemotronHConfig):
        super().__init__()
        self.c = c
        Hq, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        self.q_proj = _lin(c.hidden_size, Hq * D)
        self.k_proj = _lin(c.hidden_size, KV * D)
        self.v_proj = _lin(c.hidden_size, KV * D)
        self.o_proj = _lin(Hq * D, c.hidden_size)

    def weights(self) -> dict:
        return dict(wq=self.q_proj.weight, wk=self.k_proj.weight,
                    wv=self.v_proj.weight, wo=self.o_proj.weight)

    def forward(self, a):
        return _apply_mixer("nemotron_h_attention", _attention_forward, a,
                            self.weights(), self.c)


class NemotronHMoE(nn.Layer):
    """The router covers all ``n_routed_experts``; the stacks hold
    ``experts_held = (first, count)`` of them (all, if None)."""

    def __init__(self, c: NemotronHConfig):
        super().__init__()
        self.c = c
        Hd, Z, Iw = c.hidden_size, c.moe_latent_size, c.moe_intermediate_size
        E = c.experts_held[1] if c.experts_held else c.n_routed_experts
        Sw = c.moe_shared_expert_intermediate_size
        self.gate_weight = self.create_parameter(
            [Hd, c.n_routed_experts],
            default_initializer=I.Normal(0.0, 0.02))
        self.e_score_correction_bias = self.create_parameter(
            [c.n_routed_experts], default_initializer=I.Constant(0.0))
        self.fc1_latent_proj = _lin(Hd, Z)
        self.fc2_latent_proj = _lin(Z, Hd)
        self.w_up = self.create_parameter([E, Z, Iw])
        self.w_down = self.create_parameter([E, Iw, Z])
        self.shared_up = _lin(Hd, Sw)
        self.shared_down = _lin(Sw, Hd)

    def weights(self) -> dict:
        """The tree `generation._ffn_apply` reads."""
        return dict(gate=self.gate_weight,
                    bias=self.e_score_correction_bias,
                    lat_dn=self.fc1_latent_proj.weight,
                    lat_up=self.fc2_latent_proj.weight,
                    wup=self.w_up, wdn=self.w_down,
                    shared=dict(su=self.shared_up.weight,
                                sd=self.shared_down.weight))

    def static(self) -> dict:
        c = self.c
        return dict(top_k=c.num_experts_per_tok, renorm=c.norm_topk_prob,
                    score="sigmoid", act="relu2",
                    scale=c.routed_scaling_factor, held=c.experts_held)

    def forward(self, a):
        return _apply_routed("nemotron_h_moe", a, self.weights(),
                             self.static())


MIXERS = {MAMBA: NemotronHMamba, ATTENTION: NemotronHAttention,
          MOE: NemotronHMoE}


class NemotronHBlock(nn.Layer):
    """``x + mixer(RMSNorm(x))`` with ONE mixer, of the kind its letter
    of the pattern names."""

    def __init__(self, c: NemotronHConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(c.hidden_size, c.layer_norm_epsilon)
        self.mixer = MIXERS[kind](c)

    def forward(self, x):
        return x + self.mixer(self.norm(x))


class NemotronHModel(nn.Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [NemotronHBlock(config, kind)
             for kind in config.hybrid_override_pattern])
        self.norm_f = nn.RMSNorm(config.hidden_size,
                                 config.layer_norm_epsilon)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm_f(x)


class NemotronHForCausalLM(nn.Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.model = NemotronHModel(config)
        self.lm_head = _lin(config.hidden_size, config.vocab_size)

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))
