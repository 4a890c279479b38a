"""Falcon-H1 family (``tiiuae/Falcon-H1-34B-Instruct``, ``model_type``
``falcon_h1``; arXiv:2507.22448): a decoder whose every block feeds ONE
norm to a Mamba-2 state-space mixer AND a rotary GQA mixer side by side,
adds both to the residual, and follows them with a dense SwiGLU FFN —
so every layer holds a slot of recurrent state and pages — with
fourteen muP multipliers (nine keys of the config) on the path.

Source of the layout: the published ``config.json`` and the family's
public description.  ``d`` hidden, RMSNorm with plain gain (eps
``rms_norm_eps``), no bias but the convolution's, positions from 0:

1. ``x_0 = Emb[tok] * embedding_multiplier``; after the last layer
   ``logits = (RMSNorm(x; g_f) W_head) * lm_head_multiplier`` (untied).
2. ``a = RMSNorm(x; g_1)``.
3. State branch (Mamba-2 / SSD, arXiv:2405.21060): ``[z | x' | B | C |
   dt] = ((a * ssm_in_multiplier) W_in) * m`` (widths ``mamba_d_ssm``,
   ``mamba_d_ssm``, ``G N``, ``G N``, ``mamba_n_heads``; ``m`` constant
   over each segment, ``ssm_multipliers[0..4]`` in that order); ``[x' |
   B | C] <- silu(conv(.) + b)`` (depthwise, causal, ``mamba_d_conv``);
   ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; head ``h`` of
   group ``h // (H / G)``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x'_t
   (outer) B_t``, ``y_t = S_t C_t + D x'_t``; ``y <- GroupRMSNorm(y
   silu(z); g_n)`` over G groups (``mamba_norm_before_gate`` false: the
   gate first); ``out_s = (y W_out) * ssm_out_multiplier``.
4. Attention branch: ``a' = a * attention_in_multiplier``; ``q = a'
   W_q``, ``k = (a' W_k) * key_multiplier``, ``v = a' W_v``; rotary on
   all ``head_dim`` dims of q and k, half-split pairs, base
   ``rope_theta``; causal softmax at ``head_dim^-1/2``; ``out_a = (o
   W_o) * attention_out_multiplier``.
5. ``x <- x + out_s + out_a``; ``b = RMSNorm(x; g_2)``; ``x <- x +
   ((silu((b W_gate) * mlp_multipliers[0]) (b W_up)) W_down) *
   mlp_multipliers[1]``.

The multipliers are static scalars of the configuration, applied in the
program where the equations put them and never folded into a stored
weight.  What the config leaves open is listed as ``assumed`` in
``benchmarks/configs/falcon-h1-34b-serve-pp8-d9.json``.  This is the
whole-sequence forward from zero state; the serving engine keeps a slot
of state and pages a sequence (`serving.engine.ServingEngine.
_chain_unified_body`, whose blocks `serving.engine._chain_of` reads off
the pattern, ``[M*]D`` a layer).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn import initializer as I
from ..ops.pallas_ssm import ssm_chunk_scan
from .evabyte import _rope, rope_table
from .nemotron_h import (_apply_mixer, _DtBias, _lin, arrays, ssm_conv,
                         ssm_gated_norm, ssm_operands, ssm_split)

__all__ = ["arrays", "FalconH1Config", "FalconH1Model",
           "FalconH1ForCausalLM", "falcon_h1_config",
           "falcon_h1_tiny_config", "mup_vector", "MULTIPLIERS"]

#: the fourteen multipliers, by the name the programs read them under
MULTIPLIERS = ("embedding", "lm_head", "attention_in", "attention_out",
               "key", "ssm_in", "ssm_out", "ssm_z", "ssm_x", "ssm_B",
               "ssm_C", "ssm_dt", "mlp_gate", "mlp_down")


class FalconH1Config:
    """The published keys under their published names (defaults: the
    published values of Falcon-H1-34B-Instruct), and the names the
    state-space parts shared with Nemotron-H read."""

    def __init__(self, vocab_size=261120, hidden_size=5120,
                 num_hidden_layers=72, num_attention_heads=20,
                 num_key_value_heads=4, head_dim=128,
                 intermediate_size=21504, mamba_d_ssm=4096,
                 mamba_n_heads=32, mamba_d_head=128, mamba_d_state=256,
                 mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=128,
                 mamba_expand=2, mamba_conv_bias=True,
                 mamba_proj_bias=False, mamba_rms_norm=True,
                 mamba_norm_before_gate=False, mamba_use_mlp=True,
                 attn_layer_indices=None, attention_bias=False,
                 mlp_bias=False, projectors_bias=False, hidden_act="silu",
                 rms_norm_eps=1e-5, rope_theta=1e11, rope_scaling=None,
                 max_position_embeddings=262144,
                 tie_word_embeddings=False,
                 embedding_multiplier=5.656854249492381,
                 lm_head_multiplier=0.0078125,
                 attention_in_multiplier=1.0,
                 attention_out_multiplier=0.0375,
                 key_multiplier=0.011048543456039804,
                 ssm_in_multiplier=0.25,
                 ssm_out_multiplier=0.08838834764831845,
                 ssm_multipliers=(0.3535533905932738, 0.25,
                                  0.1767766952966369, 0.5,
                                  0.3535533905932738),
                 mlp_multipliers=(0.1767766952966369,
                                  0.011160714285714284),
                 time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4):
        if hidden_act != "silu":
            raise NotImplementedError("hidden_act")
        if mamba_proj_bias or mlp_bias or attention_bias or projectors_bias \
                or not mamba_conv_bias:
            raise NotImplementedError(
                "no bias but the convolution's is implemented")
        if tie_word_embeddings or rope_scaling is not None:
            raise NotImplementedError("tied head / rope_scaling")
        if not mamba_rms_norm or mamba_norm_before_gate:
            raise NotImplementedError(
                "the gated group norm, the gate first, is what is built")
        if attn_layer_indices is not None or not mamba_use_mlp:
            raise NotImplementedError(
                "attention in every layer and an FFN in every block")
        if mamba_d_ssm != mamba_n_heads * mamba_d_head:
            raise ValueError("mamba_d_ssm must be mamba_n_heads x "
                             "mamba_d_head")
        if mamba_n_heads % mamba_n_groups or \
                num_attention_heads % num_key_value_heads:
            raise ValueError("heads must be whole groups")
        if len(ssm_multipliers) != 5 or len(mlp_multipliers) != 2:
            raise ValueError("five ssm_multipliers, two mlp_multipliers")
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.mamba_d_ssm, self.mamba_n_heads = mamba_d_ssm, mamba_n_heads
        self.mamba_d_head, self.mamba_d_state = mamba_d_head, mamba_d_state
        self.mamba_n_groups, self.mamba_d_conv = mamba_n_groups, mamba_d_conv
        self.mamba_chunk_size = mamba_chunk_size
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.max_position_embeddings = max_position_embeddings
        self.time_step_min, self.time_step_max = time_step_min, time_step_max
        self.time_step_floor = time_step_floor
        m = [float(v) for v in ssm_multipliers]
        g = [float(v) for v in mlp_multipliers]
        self.multipliers = dict(zip(MULTIPLIERS, (
            float(embedding_multiplier), float(lm_head_multiplier),
            float(attention_in_multiplier), float(attention_out_multiplier),
            float(key_multiplier), float(ssm_in_multiplier),
            float(ssm_out_multiplier), *m, *g)))
        # the names `nemotron_h.ssm_split` / `ssm_operands` and the
        # serving engine's state-space mixer read
        self.mamba_num_heads, self.mamba_head_dim = mamba_n_heads, mamba_d_head
        self.n_groups, self.ssm_state_size = mamba_n_groups, mamba_d_state
        self.conv_kernel, self.chunk_size = mamba_d_conv, mamba_chunk_size
        self.layer_norm_epsilon = rms_norm_eps

    @property
    def d_inner(self) -> int:
        return self.mamba_d_ssm

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def pattern(self) -> str:
        """The serving engine's spelling: a block of two mixers on one
        norm, then a dense FFN block, a layer."""
        return "[M*]D" * self.num_hidden_layers

    def rope_table(self, n: int):
        return rope_table(self.rope_theta, self.head_dim, n)


def falcon_h1_config(**published) -> FalconH1Config:
    """A `FalconH1Config` from the published keys; keys that say
    nothing of the main pass's shape are taken and dropped
    (``mamba_expand`` beside ``mamba_d_ssm``, ``mlp_expansion_factor``
    beside ``intermediate_size``, ``num_logits_to_keep``)."""
    for k in ("model_type", "num_logits_to_keep", "mlp_expansion_factor"):
        published.pop(k, None)
    return FalconH1Config(**published)


def falcon_h1_tiny_config(**kw) -> FalconH1Config:
    """Toy widths with every mechanism on: 5 query heads a KV head (the
    published group), 4 state heads of 8 in 2 groups over a state of 128
    (fewer heads than lanes over a state of whole registers: the
    state-minor pool), scan chunks of 8, every multiplier its own
    value."""
    base = dict(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                num_attention_heads=10, num_key_value_heads=2, head_dim=16,
                intermediate_size=48, mamba_d_ssm=32, mamba_n_heads=4,
                mamba_d_head=8, mamba_d_state=128, mamba_n_groups=2,
                mamba_chunk_size=8, max_position_embeddings=1024,
                rope_theta=1e4, embedding_multiplier=1.7,
                lm_head_multiplier=0.6, attention_in_multiplier=0.8,
                attention_out_multiplier=0.7, key_multiplier=1.3,
                ssm_in_multiplier=0.9, ssm_out_multiplier=1.2,
                ssm_multipliers=(0.85, 1.15, 0.75, 1.25, 0.65),
                mlp_multipliers=(1.4, 0.55))
    base.update(kw)
    return FalconH1Config(**base)


def mup_vector(c: FalconH1Config, dtype):
    """``m`` [d_ssm + conv_dim + heads]: ``ssm_multipliers[0..4]`` over
    the segments ``[z | x' | B | C | dt]`` of ``W_in``'s output."""
    mu, gn = c.multipliers, c.mamba_n_groups * c.mamba_d_state
    widths = (c.mamba_d_ssm, c.mamba_d_ssm, gn, gn, c.mamba_n_heads)
    return jnp.asarray(np.concatenate([
        np.full(w, mu[k], np.float32) for w, k in zip(
            widths, ("ssm_z", "ssm_x", "ssm_B", "ssm_C", "ssm_dt"))]), dtype)


# ---------------------------------------------------------------------------
# the two mixers and the FFN on one sequence
# ---------------------------------------------------------------------------

def _mamba_forward(a, L, c: FalconH1Config):
    """The state branch on one sequence a [S, hidden] from zero state."""
    H, P, N = c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state
    mu = c.multipliers
    f32 = jnp.float32
    z, u, dt = ssm_split(((a * mu["ssm_in"]) @ L["w_in"])
                         * mup_vector(c, a.dtype), c)
    K = c.mamba_d_conv
    u = ssm_conv(jnp.concatenate([jnp.zeros((K - 1,) + u.shape[1:],
                                            u.dtype), u]),
                 L["conv_w"], L["conv_b"])
    x, dt, dA, bm, cm = ssm_operands(u, dt, L, c)
    xf = x.astype(f32)
    y, _ = ssm_chunk_scan(xf * dt[..., None], dA, bm, cm,
                          jnp.zeros((P, N, H), f32), chunk=c.mamba_chunk_size)
    y = y + L["D"].astype(f32)[None, :, None] * xf
    y = ssm_gated_norm(y.reshape(-1, H * P), z, L["norm_g"],
                       c.mamba_n_groups, c.rms_norm_eps)
    return (y.astype(a.dtype) @ L["w_out"]) * mu["ssm_out"]


def _attention_forward(a, L, c: FalconH1Config):
    """The attention branch on one sequence a [S, hidden]."""
    S = a.shape[0]
    Hq, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    mu = c.multipliers
    a = a * mu["attention_in"]
    cos, sin = c.rope_table(S)
    # (evabyte's rotate-half, on a batch of one)
    q = _rope((a @ L["wq"]).reshape(1, S, Hq, D), cos, sin)
    k = _rope(((a @ L["wk"]) * mu["key"]).reshape(1, S, KV, D), cos, sin)[0]
    q = q.reshape(S, KV, Hq // KV, D)
    v = (a @ L["wv"]).reshape(S, KV, D)
    s = jnp.einsum("tgrd,sgd->grts", q, k).astype(jnp.float32) * D ** -0.5
    t = jnp.arange(S)
    s = jnp.where(t[:, None] >= t[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, -1).astype(v.dtype)
    o = jnp.einsum("grts,sgd->tgrd", p, v)
    return (o.reshape(S, Hq * D) @ L["wo"]) * mu["attention_out"]


def _mlp_forward(b, L, c: FalconH1Config):
    mu = c.multipliers
    return ((jax.nn.silu((b @ L["wg"]) * mu["mlp_gate"]) * (b @ L["wu"]))
            @ L["wd"]) * mu["mlp_down"]


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

class FalconH1Mamba(nn.Layer):
    def __init__(self, c: FalconH1Config):
        super().__init__()
        self.c = c
        H = c.mamba_n_heads
        self.in_proj = _lin(c.hidden_size, c.d_inner + c.conv_dim + H)
        self.conv_weight = self.create_parameter(
            [c.conv_dim, c.mamba_d_conv],
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
        self.norm = nn.RMSNorm(c.d_inner, c.rms_norm_eps)
        self.out_proj = _lin(c.d_inner, c.hidden_size)

    def weights(self) -> dict:
        return dict(w_in=self.in_proj.weight, conv_w=self.conv_weight,
                    conv_b=self.conv_bias, dt_bias=self.dt_bias,
                    A_log=self.A_log, D=self.D, norm_g=self.norm.weight,
                    w_out=self.out_proj.weight)

    def forward(self, a):
        return _apply_mixer("falcon_h1_mamba", _mamba_forward, a,
                            self.weights(), self.c)


class FalconH1Attention(nn.Layer):
    def __init__(self, c: FalconH1Config):
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
        return _apply_mixer("falcon_h1_attention", _attention_forward, a,
                            self.weights(), self.c)


class FalconH1MLP(nn.Layer):
    def __init__(self, c: FalconH1Config):
        super().__init__()
        self.c = c
        self.gate_proj = _lin(c.hidden_size, c.intermediate_size)
        self.up_proj = _lin(c.hidden_size, c.intermediate_size)
        self.down_proj = _lin(c.intermediate_size, c.hidden_size)

    def weights(self) -> dict:
        return dict(wg=self.gate_proj.weight, wu=self.up_proj.weight,
                    wd=self.down_proj.weight)

    def forward(self, b):
        return _apply_mixer("falcon_h1_mlp", _mlp_forward, b,
                            self.weights(), self.c)


class FalconH1Layer(nn.Layer):
    """``x + mamba(a) + attention(a)`` with ``a = RMSNorm(x)`` — two
    mixers on ONE norm — then ``x + mlp(RMSNorm(x))``."""

    def __init__(self, c: FalconH1Config):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.mamba = FalconH1Mamba(c)
        self.self_attn = FalconH1Attention(c)
        self.pre_ff_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.feed_forward = FalconH1MLP(c)

    def forward(self, x):
        a = self.input_layernorm(x)
        x = x + self.mamba(a) + self.self_attn(a)
        return x + self.feed_forward(self.pre_ff_layernorm(x))


class FalconH1Model(nn.Layer):
    def __init__(self, config: FalconH1Config):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [FalconH1Layer(config) for _ in range(config.num_hidden_layers)])
        self.final_layernorm = nn.RMSNorm(config.hidden_size,
                                          config.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids) \
            * self.config.multipliers["embedding"]
        for layer in self.layers:
            x = layer(x)
        return self.final_layernorm(x)


class FalconH1ForCausalLM(nn.Layer):
    def __init__(self, config: FalconH1Config):
        super().__init__()
        self.config = config
        self.model = FalconH1Model(config)
        self.lm_head = _lin(config.hidden_size, config.vocab_size)

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids)) \
            * self.config.multipliers["lm_head"]
