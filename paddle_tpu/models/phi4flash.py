"""Phi-4-mini-flash family (``microsoft/Phi-4-mini-flash-reasoning``,
``model_type`` ``phi4flash``; SambaY, arXiv:2507.06607): a
decoder-hybrid-decoder.  The first half interleaves Mamba-1 state-space
layers (arXiv:2312.00752) with sliding-window attention; ONE full
attention layer follows; the second half keeps NO memory of its own —
its attention layers read that one layer's keys and values
(cross-decoder), its gated memory units read one Mamba layer's scan
output.  Every attention is Differential Attention (arXiv:2410.05258).

Source of the layout: the published ``config.json`` for the widths, the
family's report and public modelling file for the roles (what the config
leaves open is listed as ``assumed`` in
``benchmarks/configs/phi-4-mini-flash-serve-whole.json``).  ``d`` hidden,
LayerNorm with weight AND bias (eps ``layer_norm_eps``), NO positional
encoding anywhere, the head tied to the embedding.  ``n`` layers, ``h =
n / 2``:

1. Every layer ``l``: ``x = x + mixer_l(LN(x))``, then ``x = x + W2
   (silu(g) * u)`` with ``[g | u] = W1 LN'(x)`` (stored here as two
   matrices ``gate_proj`` | ``up_proj``: the same columns).  ``logits =
   LN_f(x) E^T``.
2. Mixer by index (`layer_kinds`): ``l`` even and ``l <= h``: Mamba-1
   (``S``); ``l`` odd and ``l < h``: window attention (``W``); ``l = h +
   1``: full attention (``F``), whose k / v are the cross-decoder's;
   ``l`` even and ``l > h``: gated memory unit (``G``); ``l`` odd and
   ``l > h + 1``: cross attention (``X``).
3. Mamba-1 (``d_inner`` C, ``d_state`` N, ``d_conv`` K, ``dt_rank`` R):
   ``[x | z] = a W_in``; ``x = silu(conv_K(x) + b_c)`` (depthwise,
   causal); ``[r | B | C] = x W_x``; ``dt = softplus(r W_dt + b_dt)``;
   ``A = -exp(A_log)``; ``h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n]
   + dt_t[c] x_t[c] B_t[n]``; ``y_t[c] = sum_n h_t[c, n] C_t[n] + D[c]
   x_t[c]``; ``out = (y * silu(z)) W_out``.  Layer ``h`` also hands on
   ``m_t = y_t`` (with the ``D`` term, BEFORE the gate).
4. Gated memory unit: ``out = (silu(a W_a) * m_t) W_b``.
5. Differential attention: ``q = a W_q + b`` [H x D] and, where the
   layer has its own, ``k, v = a W_k + b, a W_v + b`` [KV x D] (the
   published fused ``Wqkv``: the same columns); cross layers have ``W_q``
   only.  Differential head ``i`` of H / 2, pair ``j = i // 2``: ``a1 =
   softmax(q_2i K_2j^T D^-1/2) [V_2j | V_2j+1]``, ``a2 = softmax(q_2i+1
   K_2j+1^T D^-1/2) [V_2j | V_2j+1]`` (causal; keys ``i - window < j``
   in window layers); ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
   lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``o_i =
   RMSNorm_2D(a1 - lambda a2; g_s) (1 - lambda_init)``; ``out = [o_0 ..]
   W_o + b_o``.

This is the whole-sequence forward from zero state, one dispatched op,
for inference (the scan kernel has no gradient).  The serving engine
keeps a state slot, window pages and ONE full pool a sequence
(`serving.engine.ServingEngine._chain_unified_body`, whose blocks
`serving.engine._chain_of` reads off `Phi4FlashConfig.pattern`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.autograd import no_grad
from ..core.dispatch import apply
from ..nn import initializer as I
from ..ops.pallas_ssm import ssm1_chunk_scan
from .nemotron_h import _lin, arrays, ssm_conv

__all__ = ["arrays", "Phi4FlashConfig", "Phi4FlashForCausalLM",
           "phi4flash_config", "phi4flash_tiny_config", "layer_kinds",
           "lambda_init", "diff_lambda", "diff_combine", "pair_queries",
           "layer_norm", "ssm1_operands"]


def layer_kinds(n: int) -> str:
    """One letter a layer (module docstring, item 2)."""
    if n % 4:
        raise ValueError(f"num_hidden_layers {n}: the two halves are whole "
                         f"pairs of layers")
    h = n // 2
    return "".join(
        ("S" if l <= h else "G") if l % 2 == 0 else
        ("W" if l < h else "F" if l == h + 1 else "X") for l in range(n))


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


class Phi4FlashConfig:
    """The published keys under their published names (defaults:
    Phi-4-mini-flash-reasoning's), and the Mamba-1 constants the config
    does not carry (the modelling file's)."""

    def __init__(self, vocab_size=200064, hidden_size=2560,
                 intermediate_size=10240, num_hidden_layers=32,
                 num_attention_heads=40, num_key_value_heads=20,
                 sliding_window=512, mb_per_layer=2, layer_norm_eps=1e-5,
                 max_position_embeddings=262144, hidden_act="silu",
                 tie_word_embeddings=True, mlp_bias=False,
                 lm_head_bias=False, embd_pdrop=0, resid_pdrop=0,
                 mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
                 mamba_dt_rank="auto"):
        if hidden_act != "silu" or mlp_bias or lm_head_bias \
                or not tie_word_embeddings:
            raise NotImplementedError(
                "silu, no FFN or head bias and a tied head are what is built")
        if mb_per_layer != 2:
            raise NotImplementedError("every second layer is Mamba")
        if num_attention_heads % 4 or \
                num_key_value_heads * 2 != num_attention_heads:
            raise ValueError("differential heads pair adjacent query heads "
                             "over adjacent KV heads: H = 2 KV, H % 4 == 0")
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.sliding_window = int(sliding_window)
        self.layer_norm_eps = self.layer_norm_epsilon = layer_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.d_inner = mamba_expand * hidden_size
        self.ssm_state_size, self.conv_kernel = mamba_d_state, mamba_d_conv
        self.dt_rank = -(-hidden_size // 16) if mamba_dt_rank == "auto" \
            else int(mamba_dt_rank)
        self.kinds = layer_kinds(self.num_hidden_layers)

    @property
    def conv_dim(self) -> int:
        return self.d_inner

    @property
    def memory_layer(self) -> int:
        return self.num_hidden_layers // 2

    @property
    def shared_layer(self) -> int:
        return self.num_hidden_layers // 2 + 1

    @property
    def pattern(self) -> str:
        """The serving engine's spelling (`serving.engine._pattern_blocks`),
        two blocks a layer: ``S`` a Mamba-1 mixer, ``*`` an attention
        mixer with pages of its own, ``G<j>`` a gated unit that reads
        block j's scan output, ``X<j>`` an attention mixer that reads
        block j's pages; ``D`` the FFN."""
        mem, kv = 2 * self.memory_layer, 2 * self.shared_layer
        spell = {"S": "S", "W": "*", "F": "*", "G": f"G{mem}", "X": f"X{kv}"}
        return "".join(spell[k] + "D" for k in self.kinds)


def phi4flash_config(**published) -> Phi4FlashConfig:
    published.pop("model_type", None)
    return Phi4FlashConfig(**published)


def phi4flash_tiny_config(**kw) -> Phi4FlashConfig:
    """Toy widths with all five mixer kinds: eight layers (``SWSWSFGX``),
    8 query heads of 8 over 4 KV heads, a window of 12."""
    base = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
                num_hidden_layers=8, num_attention_heads=8,
                num_key_value_heads=4, sliding_window=12,
                max_position_embeddings=1024, mamba_dt_rank=8)
    base.update(kw)
    return Phi4FlashConfig(**base)


# ---------------------------------------------------------------------------
# the parts the serving engine shares
# ---------------------------------------------------------------------------

def layer_norm(x, g, b, eps: float):
    """LayerNorm with weight and bias, float32 inside, x's type out."""
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def ssm1_operands(u, L, c: Phi4FlashConfig):
    """The recurrence's operands from the convolved rows ``u`` [T, C]:
    (dt [T, C] float32 after the softplus, B, C [T, N] float32)."""
    R, N = c.dt_rank, c.ssm_state_size
    f32 = jnp.float32
    rbc = u @ L["w_x"]
    dt = jax.nn.softplus((rbc[:, :R] @ L["w_dt"]).astype(f32)
                         + L["dt_bias"].astype(f32))
    return dt, rbc[:, R:R + N].astype(f32), rbc[:, R + N:].astype(f32)


def pair_queries(q):
    """q [T, H, D] -> [T, H, 2 D] for a cache whose KV head is a PAIR
    (K_2j | K_2j+1): even heads ``[q | 0]``, odd heads ``[0 | q]``, so a
    2D-wide score is the D-wide score against the one K head a query
    head uses."""
    T, H, D = q.shape
    z = jnp.zeros_like(q)
    even = jnp.arange(H)[None, :, None] % 2 == 0
    return jnp.concatenate([jnp.where(even, q, z), jnp.where(even, z, q)], -1)


def diff_lambda(L, l: int):
    f32 = jnp.float32
    return (jnp.exp(jnp.sum(L["lq1"].astype(f32) * L["lk1"].astype(f32)))
            - jnp.exp(jnp.sum(L["lq2"].astype(f32) * L["lk2"].astype(f32)))
            + lambda_init(l))


def diff_combine(o, L, l: int, eps: float):
    """o [T, H, 2 D] (head 2i: a1, head 2i + 1: a2 of differential head
    i) -> [T, H / 2 * 2 D]: ``RMSNorm(a1 - lambda a2; g_s) (1 -
    lambda_init)``, float32 inside."""
    T, H, W = o.shape
    f32 = jnp.float32
    o = o.astype(f32).reshape(T, H // 2, 2, W)
    v = o[:, :, 0] - diff_lambda(L, l) * o[:, :, 1]
    v = v * jax.lax.rsqrt((v * v).mean(-1, keepdims=True) + eps)
    return (v * L["subln"].astype(f32) * (1.0 - lambda_init(l))
            ).reshape(T, H // 2 * W)


# ---------------------------------------------------------------------------
# the mixers on one sequence, from zero state
# ---------------------------------------------------------------------------

def _mamba(a, L, c: Phi4FlashConfig):
    """-> (the mixer's output [S, d], y [S, C] float32: the memory)."""
    C, K = c.d_inner, c.conv_kernel
    f32 = jnp.float32
    xz = a @ L["w_in"]
    u, z = xz[:, :C], xz[:, C:]
    u = ssm_conv(jnp.concatenate([jnp.zeros((K - 1, C), u.dtype), u]),
                 L["conv_w"], L["conv_b"])
    dt, bm, cm = ssm1_operands(u, L, c)
    uf = u.astype(f32)
    y, _ = ssm1_chunk_scan(dt, uf, -jnp.exp(L["A_log"].astype(f32)).T, bm,
                           cm, jnp.zeros((1, c.ssm_state_size, C), f32))
    y = y + L["D"].astype(f32)[None] * uf
    return (y * jax.nn.silu(z.astype(f32))).astype(a.dtype) @ L["w_out"], y


def _attention(a, L, c: Phi4FlashConfig, l: int, window, kv=None):
    """-> (the mixer's output, (k, v) [S, KV, D]: its own or ``kv``)."""
    S = a.shape[0]
    H, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    q = (a @ L["wq"] + L["bq"]).reshape(S, H, D)
    if kv is None:
        kv = ((a @ L["wk"] + L["bk"]).reshape(S, KV, D),
              (a @ L["wv"] + L["bv"]).reshape(S, KV, D))
    k, v = kv
    # query head h reads K head 2 (h // 4) + h % 2, and the pair's two V
    kh = k[:, 2 * (np.arange(H) // 4) + np.arange(H) % 2]       # [S, H, D]
    vp = v.reshape(S, KV // 2, 2 * D)[:, np.arange(H) // 4]     # [S, H, 2D]
    s = jnp.einsum("thd,shd->hts", q, kh).astype(jnp.float32) * D ** -0.5
    t = jnp.arange(S)
    seen = t[:, None] >= t[None, :]
    if window is not None:
        seen &= t[:, None] - t[None, :] < window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1).astype(v.dtype)
    o = jnp.einsum("hts,shw->thw", p, vp)
    y = diff_combine(o, L, l, c.layer_norm_eps).astype(a.dtype)
    return y @ L["wo"] + L["bo"], kv


def forward_arrays(ids, w, c: Phi4FlashConfig):
    """ids [S] -> logits [S, vocabulary]; ``w``: embed, layers (a dict a
    layer), norm, norm_b."""
    eps = c.layer_norm_eps
    x = w["embed"][ids]
    m = kv = None
    for l, (kind, L) in enumerate(zip(c.kinds, w["layers"])):
        a = layer_norm(x, L["ln1"], L["ln1_b"], eps)
        if kind == "S":
            y, mem = _mamba(a, L, c)
            if l == c.memory_layer:
                m = mem
        elif kind == "G":
            y = (jax.nn.silu((a @ L["w_a"]).astype(jnp.float32)) * m
                 ).astype(a.dtype) @ L["w_b"]
        elif kind == "X":
            y, _ = _attention(a, L, c, l, None, kv)
        else:
            y, own = _attention(a, L, c, l,
                                c.sliding_window if kind == "W" else None)
            if l == c.shared_layer:
                kv = own
        x = x + y
        b = layer_norm(x, L["ln2"], L["ln2_b"], eps)
        x = x + (jax.nn.silu(b @ L["wg"]) * (b @ L["wu"])) @ L["wd"]
    return layer_norm(x, w["norm"], w["norm_b"], eps) @ w["embed"].T


# ---------------------------------------------------------------------------
# layers: the parameters, by the names the draw and the engine read
# ---------------------------------------------------------------------------

def _blin(i, o):
    return nn.Linear(i, o)


class _DtBias(I.Initializer):
    """``dt``'s bias such that softplus(bias) is log-uniform in [1e-3,
    1e-1]."""

    def __call__(self, shape, dtype):
        rng = np.random.default_rng(int(np.prod(shape)))
        dt = np.exp(rng.uniform(math.log(1e-3), math.log(1e-1), shape))
        return jnp.asarray(dt + np.log(-np.expm1(-dt)), dtype)


class Phi4FlashMamba(nn.Layer):
    def __init__(self, c: Phi4FlashConfig):
        super().__init__()
        C, N, R = c.d_inner, c.ssm_state_size, c.dt_rank
        self.in_proj = _lin(c.hidden_size, 2 * C)
        self.conv_weight = self.create_parameter(
            [C, c.conv_kernel], default_initializer=I.Uniform(-0.5, 0.5))
        self.conv_bias = self.create_parameter(
            [C], default_initializer=I.Constant(0.0))
        self.x_proj = _lin(C, R + 2 * N)
        self.dt_proj = _lin(R, C)
        self.dt_bias = self.create_parameter(
            [C], default_initializer=_DtBias())
        self.A_log = self.create_parameter(
            [C, N], default_initializer=I.Assign(np.log(np.tile(
                np.arange(1, N + 1, dtype=np.float32), (C, 1)))))
        self.D = self.create_parameter(
            [C], default_initializer=I.Constant(1.0))
        self.out_proj = _lin(C, c.hidden_size)

    def weights(self) -> dict:
        return dict(w_in=self.in_proj.weight, conv_w=self.conv_weight,
                    conv_b=self.conv_bias, w_x=self.x_proj.weight,
                    w_dt=self.dt_proj.weight, dt_bias=self.dt_bias,
                    A_log=self.A_log, D=self.D, w_out=self.out_proj.weight)


class Phi4FlashAttention(nn.Layer):
    """Differential attention; ``cross``: a query and an output
    projection only (the keys and values are another layer's)."""

    def __init__(self, c: Phi4FlashConfig, cross: bool):
        super().__init__()
        H, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        self.cross = cross
        self.q_proj = _blin(c.hidden_size, H * D)
        if not cross:
            self.k_proj = _blin(c.hidden_size, KV * D)
            self.v_proj = _blin(c.hidden_size, KV * D)
        self.o_proj = _blin(H * D, c.hidden_size)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, self.create_parameter(
                [D], default_initializer=I.Normal(0.0, 0.1)))
        self.subln = self.create_parameter(
            [2 * D], default_initializer=I.Constant(1.0))

    def weights(self) -> dict:
        w = dict(wq=self.q_proj.weight, bq=self.q_proj.bias,
                 wo=self.o_proj.weight, bo=self.o_proj.bias,
                 lq1=self.lambda_q1, lk1=self.lambda_k1, lq2=self.lambda_q2,
                 lk2=self.lambda_k2, subln=self.subln)
        if not self.cross:
            w.update(wk=self.k_proj.weight, bk=self.k_proj.bias,
                     wv=self.v_proj.weight, bv=self.v_proj.bias)
        return w


class Phi4FlashGMU(nn.Layer):
    def __init__(self, c: Phi4FlashConfig):
        super().__init__()
        self.in_proj = _lin(c.hidden_size, c.d_inner)
        self.out_proj = _lin(c.d_inner, c.hidden_size)

    def weights(self) -> dict:
        return dict(w_a=self.in_proj.weight, w_b=self.out_proj.weight)


class Phi4FlashMLP(nn.Layer):
    def __init__(self, c: Phi4FlashConfig):
        super().__init__()
        self.gate_proj = _lin(c.hidden_size, c.intermediate_size)
        self.up_proj = _lin(c.hidden_size, c.intermediate_size)
        self.down_proj = _lin(c.intermediate_size, c.hidden_size)

    def weights(self) -> dict:
        return dict(wg=self.gate_proj.weight, wu=self.up_proj.weight,
                    wd=self.down_proj.weight)


class Phi4FlashLayer(nn.Layer):
    def __init__(self, c: Phi4FlashConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.input_layernorm = nn.LayerNorm(c.hidden_size, c.layer_norm_eps)
        self.mixer = (Phi4FlashMamba(c) if kind == "S" else
                      Phi4FlashGMU(c) if kind == "G" else
                      Phi4FlashAttention(c, cross=kind == "X"))
        self.post_attention_layernorm = nn.LayerNorm(c.hidden_size,
                                                     c.layer_norm_eps)
        self.mlp = Phi4FlashMLP(c)

    def weights(self) -> dict:
        return dict(ln1=self.input_layernorm.weight,
                    ln1_b=self.input_layernorm.bias,
                    ln2=self.post_attention_layernorm.weight,
                    ln2_b=self.post_attention_layernorm.bias,
                    **self.mixer.weights(), **self.mlp.weights())


class Phi4FlashModel(nn.Layer):
    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [Phi4FlashLayer(config, k) for k in config.kinds])
        self.final_layernorm = nn.LayerNorm(config.hidden_size,
                                            config.layer_norm_eps)


class Phi4FlashForCausalLM(nn.Layer):
    """The head is the embedding (``tie_word_embeddings``): no
    ``lm_head`` parameter."""

    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.config = config
        self.model = Phi4FlashModel(config)

    def weights(self) -> dict:
        """The whole tree of parameters, as `forward_arrays` reads it."""
        m = self.model
        return dict(embed=m.embed_tokens.weight,
                    layers=[lyr.weights() for lyr in m.layers],
                    norm=m.final_layernorm.weight,
                    norm_b=m.final_layernorm.bias)

    def forward(self, input_ids):
        c = self.config
        flat, tree = jax.tree_util.tree_flatten(
            self.weights(), is_leaf=lambda t: hasattr(t, "_data"))

        def impl(ids, *vals):
            w = jax.tree_util.tree_unflatten(tree, vals)
            return jax.vmap(lambda s: forward_arrays(s, w, c))(ids)

        # (the scan kernel has no gradient: inference only)
        with no_grad():
            return apply("phi4flash_forward", impl, [input_ids] + flat)
