"""Plain reference of the LFM2-MoE decoder (LiquidAI LFM2-24B-A2B,
``model_type`` ``lfm2_moe``): a pre-norm decoder whose mixer is EITHER a
gated short convolution OR grouped-query attention, over a dense SwiGLU
(the leading layers) or a routed one.

Straightforward ``jax.numpy``: float32 under ``default_matmul_precision
("highest")``, no cache, no kernels, no batching, ONE sequence from its
first token; every expert in a loop (cast to the compute type a few at a
time: a layer's 64 experts are 2.4 GB in float32), attention in query
blocks and the vocabulary in row blocks only so that an 8 k sample fits
beside the engine.  Written from the published ``config.json`` and the
equations of ISSUE 64; independent of ``paddle_tpu/models/lfm2.py``, of
``paddle_tpu/ops`` and of the engine.  Rows x [S, hidden], RMSNorm in
float32 with ``norm_eps``:

1. ``h = RMSNorm(x; operator_norm)``.
   ``conv`` layer: ``(B, C, z) = split3(h W_in)`` in THAT order, no
   bias; ``u = B * z``; ``c_t = sum_{j=0..K-1} w[:, j] u_{t-(K-1)+j}``
   (depthwise, causal, zeros left of the sequence, no bias, NO
   activation; K = ``conv_L_cache``); ``x += (C * c) W_out``.
   ``full_attention`` layer: ``q = h Wq`` [n_q, D], ``k = h Wk``, ``v =
   h Wv`` [n_kv, D], no bias; ``q = RMSNorm_D(q; q_layernorm)``, ``k =
   RMSNorm_D(k; k_layernorm)``, one gain [D] for all heads; THEN
   rotate-half RoPE over all D dims at the absolute position, ``1 /
   theta^(2i/D)``; causal ``softmax(q k^T / sqrt(D)) v``, GQA; ``x += a
   Wo``.
2. ``h2 = RMSNorm(x; ffn_norm)``.  A layer below ``num_dense_layers``:
   ``x += W2 (silu(h2 W1) * h2 W3)``.  The others: ``s =
   sigmoid_f32(h2 Wr)``; ``e = top_k(s + b)``, b the ``expert_bias``
   (it picks, it does not weigh); ``w = s[e] / (sum s[e] + 1e-6)``
   (``norm_topk_prob``); ``w *= routed_scaling_factor``; ``x += sum_j
   w_j W2[e_j] (silu(h2 W1[e_j]) * h2 W3[e_j])``.
3. ``logits = RMSNorm(x; embedding_norm) E^T``, E the embedding.

``ablate`` plants ONE fault — the tests' and the limit tool's negative
controls, never the reference: "conv_silu" (a silu on the convolution),
"gate_b" (u = z: the B gate left out), "bias_weighs" (w = (s + b)[e]),
"renorm" (no renormalisation), "qk_norm" (no q / k norm); and, at the
position ``cut`` where an engine would have adopted a cached prefix,
"tail_zero" (rows cut .. cut + K - 2 read zeros where they should read
the K - 1 rows of u before ``cut``: an adoption without its snapshot)
and "tail_stale" (they read the rows ``page`` positions earlier: the
snapshot of the page BEFORE).  With ``dtype=bfloat16`` the same code runs
in the serving type at the default precision: the yardstick of the
tolerance, not the reference.  ``operands=float8`` rounds the weights and
each layer's input to a lower precision than the configuration states:
the reading that has to come out as not correct.
"""

from __future__ import annotations

import functools
from typing import FrozenSet, Mapping, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ABLATIONS = ("conv_silu", "gate_b", "bias_weighs", "renorm", "qk_norm")
#: ... and the two that need the position an adoption happened at
ADOPTION_ABLATIONS = ("tail_zero", "tail_stale")

CONV_KEYS = ("operator_norm", "w_in", "conv_w", "w_out")
ATTN_KEYS = ("operator_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo")
DENSE_KEYS = ("ffn_norm", "w1", "w3", "w2")
#: the router [hidden, E], the bias [E], the expert stacks [E, in, out]
MOE_KEYS = ("ffn_norm", "router", "bias", "e1", "e3", "e2")


class Spec(NamedTuple):
    nq: int
    nkv: int
    eps: float
    top_k: int
    renorm: bool
    scale: float
    q_block: int                    # queries at a time (0: all)
    expert_block: int               # experts cast at a time (0: all)
    ablate: FrozenSet[str]
    cut: int                        # the adoption's position (0: none)
    page: int


def _cast(a, dtype, operands):
    """`a` in the compute type, rounded through `operands` (float8)
    first where a lower precision is being read."""
    if operands is not None:
        a = a.astype(operands)
    return a.astype(dtype)


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y.astype(x.dtype) * w.astype(x.dtype)


def rope_tables(theta: float, d: int, n: int):
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    f = np.outer(np.arange(n, dtype=np.float64), inv)
    return jnp.asarray(np.cos(f), jnp.float32), \
        jnp.asarray(np.sin(f), jnp.float32)


def _rope(x, cos, sin):
    """x [S, h, D]; rotate-half: dims (i, i + D / 2) are a pair."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c, s = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v, q_block: int):
    """q [S, nkv, rep, D], k / v [S, nkv, D] -> [S, nkv, rep, D], causal,
    `q_block` queries at a time (memory only)."""
    S, nkv, rep, D = q.shape
    qb = min(q_block or S, S)
    nb = -(-S // qb)
    Sp = nb * qb
    qp = jnp.pad(q, ((0, Sp - S), (0, 0), (0, 0), (0, 0)))
    j = jnp.arange(S)[None, :]

    def rows(b):
        i = (b * qb + jnp.arange(qb))[:, None]
        qh = jax.lax.dynamic_slice_in_dim(qp, b * qb, qb, 0)
        s = jnp.einsum("qgrd,kgd->grqk", qh, k).astype(jnp.float32)
        s = jnp.where(j <= i, s / np.sqrt(D), -jnp.inf)
        p = jax.nn.softmax(s, -1).astype(qh.dtype)
        return jnp.einsum("grqk,kgd->qgrd", p, v)

    o = jax.lax.map(rows, jnp.arange(nb))           # [nb, qb, nkv, rep, D]
    return o.reshape(Sp, nkv, rep, D)[:S]


def short_conv(u, w, spec: Spec):
    """``c_t = sum_j w[:, j] u_{t-(K-1)+j}`` over u [S, W], zeros left of
    the sequence; float32 inside.  The two adoption faults replace what
    the rows from ``cut`` on read of the rows BEFORE ``cut``."""
    S, K = u.shape[0], w.shape[1]
    f32 = jnp.float32
    left = jnp.zeros((K - 1, u.shape[1]), u.dtype)

    def run(ext, n):
        acc = 0.0
        for j in range(K):
            acc = acc + w[:, j].astype(f32)[None] * ext[j:j + n].astype(f32)
        return acc

    c = run(jnp.concatenate([left, u]), S)
    cut = spec.cut
    fault = spec.ablate & set(ADOPTION_ABLATIONS)
    if fault and 0 < cut < S:
        if "tail_stale" in fault:
            at = cut - spec.page
            left = u[at - (K - 1):at] if at >= K - 1 else left
        c = jnp.concatenate(
            [c[:cut], run(jnp.concatenate([left, u[cut:]]), S - cut)])
    if "conv_silu" in spec.ablate:
        c = jax.nn.silu(c)
    return c.astype(u.dtype)


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "operands"))
def conv_mixer(x, w, *, spec: Spec, dtype, operands=None):
    """x + the gated short convolution of RMSNorm(x)."""
    if operands is not None:
        x = _cast(x, dtype, operands)
    w = {k: _cast(w[k], dtype, operands) for k in CONV_KEYS}
    h = _rms(x, w["operator_norm"], spec.eps)
    gate_b, gate_c, z = jnp.split(h @ w["w_in"], 3, axis=-1)
    u = z if "gate_b" in spec.ablate else gate_b * z
    return x + (gate_c * short_conv(u, w["conv_w"], spec)) @ w["w_out"]


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "operands"))
def attn_mixer(x, w, cos, sin, *, spec: Spec, dtype, operands=None):
    """x + grouped-query attention of RMSNorm(x)."""
    S = x.shape[0]
    nq, nkv = spec.nq, spec.nkv
    if operands is not None:
        x = _cast(x, dtype, operands)
    w = {k: _cast(w[k], dtype, operands) for k in ATTN_KEYS}
    d = w["q_norm"].shape[0]
    h = _rms(x, w["operator_norm"], spec.eps)
    q = (h @ w["wq"]).reshape(S, nq, d)
    k = (h @ w["wk"]).reshape(S, nkv, d)
    v = (h @ w["wv"]).reshape(S, nkv, d)
    if "qk_norm" not in spec.ablate:
        q, k = _rms(q, w["q_norm"], spec.eps), _rms(k, w["k_norm"], spec.eps)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    o = _attention(q.reshape(S, nkv, nq // nkv, d), k, v, spec.q_block)
    return x + o.reshape(S, nq * d) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "operands"))
def dense_ffn(x, w, *, spec: Spec, dtype, operands=None):
    if operands is not None:
        x = _cast(x, dtype, operands)
    w = {k: _cast(w[k], dtype, operands) for k in DENSE_KEYS}
    h2 = _rms(x, w["ffn_norm"], spec.eps)
    return x + (jax.nn.silu(h2 @ w["w1"]) * (h2 @ w["w3"])) @ w["w2"]


def routing(h2, router, bias, spec: Spec):
    """(weights [S, k], experts [S, k]) over ALL of the router's
    outputs, in float32 whatever the layer's type."""
    s = jax.nn.sigmoid(h2.astype(jnp.float32) @ router.astype(jnp.float32))
    picked = s + bias.astype(jnp.float32)
    pv, e = jax.lax.top_k(picked, spec.top_k)
    w = pv if "bias_weighs" in spec.ablate \
        else jnp.take_along_axis(s, e, -1)
    if spec.renorm and "renorm" not in spec.ablate:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return w * spec.scale, e


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "operands"))
def moe_ffn(x, w, *, spec: Spec, dtype, operands=None):
    """x + sum_e w_e Expert_e(RMSNorm(x)), one expert at a time; the
    stacks are cast to `dtype` `expert_block` experts at a time.  -> (x,
    the experts [S, k] it routed to)."""
    if operands is not None:
        x = _cast(x, dtype, operands)
    h2 = _rms(x, _cast(w["ffn_norm"], dtype, operands), spec.eps)
    wts, ids = routing(h2, _cast(w["router"], dtype, operands),
                       w["bias"], spec)
    E = w["e1"].shape[0]
    eb = spec.expert_block or E

    def group(carry, g):
        stacks = [_cast(jax.lax.dynamic_slice_in_dim(w[k], g * eb, eb, 0),
                        dtype, operands) for k in ("e1", "e3", "e2")]

        def one(acc, ew):
            e, (e1, e3, e2) = ew
            mine = jnp.sum(jnp.where(ids == g * eb + e, wts, 0.0), -1)
            y = (jax.nn.silu(h2 @ e1) * (h2 @ e3)) @ e2
            return acc + y * mine[:, None].astype(y.dtype), None

        return jax.lax.scan(one, carry, (jnp.arange(eb), stacks))[0], None

    out, _ = jax.lax.scan(group, jnp.zeros_like(h2), jnp.arange(E // eb))
    return x + out, ids


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "vocab_block"))
def head_logits(x, norm_w, embed, *, eps, dtype, vocab_block: int = 0):
    """[rows, V] float32 of ``RMSNorm(x) E^T``; the embedding is cast
    `vocab_block` rows at a time (0: whole — 0.5 GB in float32 at the
    published vocabulary)."""
    h = _rms(x, norm_w.astype(dtype), eps)
    V = embed.shape[0]
    vb = vocab_block or V
    if V % vb:
        raise ValueError(f"vocab_block {vb} does not divide {V}")

    def cols(b):
        e = jax.lax.dynamic_slice_in_dim(embed, b * vb, vb, 0)
        return (h @ e.astype(dtype).T).astype(jnp.float32)

    out = jax.lax.map(cols, jnp.arange(V // vb))        # [nb, rows, vb]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)


def layer_kinds(cfg: Mapping):
    """[(the mixer's type, whether the FFN is dense)] of the layers
    held, by their PUBLISHED indices (``layers_held``; all, without)."""
    held = cfg.get("layers_held") or range(cfg["num_hidden_layers"])
    return [(cfg["layer_types"][i], i < cfg["num_dense_layers"])
            for i in held]


def rope_theta(cfg: Mapping) -> float:
    return float(cfg["rope_parameters"]["rope_theta"])


def hidden_states(ids, embed, layers: Sequence[Mapping], cfg: Mapping,
                  dtype=jnp.float32, q_block: int = 0, expert_block: int = 0,
                  ablate: FrozenSet[str] = frozenset(), operands=None,
                  cut: int = 0, page: int = 0):
    """Embedding and every layer over ids [S] (one sequence from its
    first token): (x [S, H], the experts [routed layers, S, k])."""
    S = ids.shape[0]
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    cos, sin = rope_tables(rope_theta(cfg), d, S)
    spec = Spec(
        nq=cfg["num_attention_heads"], nkv=cfg["num_key_value_heads"],
        eps=float(cfg["norm_eps"]), top_k=cfg["num_experts_per_tok"],
        renorm=bool(cfg["norm_topk_prob"]),
        scale=float(cfg["routed_scaling_factor"]), q_block=q_block,
        expert_block=expert_block, ablate=frozenset(ablate), cut=int(cut),
        page=int(page))
    kw = dict(spec=spec, dtype=dtype, operands=operands)
    x = jnp.take(embed, ids, axis=0).astype(dtype)
    routed = []
    kinds = layer_kinds(cfg)
    if len(kinds) != len(layers):
        raise ValueError("the weights are not the configuration's layers")
    for w, (mixer, dense) in zip(layers, kinds):
        if mixer == "conv":
            x = conv_mixer(x, {k: w[k] for k in CONV_KEYS}, **kw)
        else:
            x = attn_mixer(x, {k: w[k] for k in ATTN_KEYS}, cos, sin, **kw)
        if dense:
            x = dense_ffn(x, {k: w[k] for k in DENSE_KEYS}, **kw)
        else:
            x, e = moe_ffn(x, {k: w[k] for k in MOE_KEYS}, **kw)
            routed.append(e)
    return x, routed


def logits(ids, weights: Mapping, cfg: Mapping, dtype=jnp.float32,
           rows=slice(None), vocab_block: int = 0, **kw):
    """The forward over ids [S]: the rows `rows` of [S, V] float32."""
    x, _ = hidden_states(ids, weights["embed"], weights["layers"], cfg,
                         dtype, **kw)
    return head_logits(x[rows], weights["norm"], weights["embed"],
                       eps=float(cfg["norm_eps"]), dtype=dtype,
                       vocab_block=vocab_block)


def highest():
    """The reference's matmul precision: on a TPU a float32 matmul runs
    in lower precision unless this is set."""
    return jax.default_matmul_precision("highest")
