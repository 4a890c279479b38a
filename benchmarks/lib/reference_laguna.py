"""Plain reference of the Laguna decoder (poolside Laguna-S-2.1).

Straightforward ``jax.numpy``: float32 under ``default_matmul_precision
("highest")``, no cache, no kernels, no batching; every expert in a
loop; attention in query and head blocks only so that a 16 k sample
fits beside the engine.  Written from the published ``config.json`` and
independent of ``paddle_tpu/models/laguna.py``.  With ``h = RMSNorm(x)``:

1. ``q = h Wq`` [n_q, D], ``k = h Wk``, ``v = h Wv`` [n_kv, D]; n_q is
   the LAYER's (``num_attention_heads_per_layer``).
2. Rope, rotate-half over the rotary width ``r = D *
   partial_rotary_factor`` (dims r.. pass through).  ``yarn``: HF's
   ``_compute_yarn_parameters`` — interpolated ``1 / (factor theta^(2i/r))``
   and extrapolated ``1 / theta^(2i/r)`` inverse frequencies blended by
   the linear ramp between the correction dims of ``beta_fast`` and
   ``beta_slow`` at ``original_max_position_embeddings``; cos and sin
   times ``attention_factor``.  ``default``: ``1 / theta^(2i/r)``.
3. Causal softmax attention at scale D^-0.5, GQA; a sliding layer's
   query at position i sees keys j with ``i - window < j <= i``.
4. ``g = sigmoid(h Wg)`` [n_q]; head a's output times ``g[a]``;
   ``x += concat(o) Wo``.
5. ``h2 = RMSNorm(x)``.  Dense layers: SwiGLU.  Sparse layers: ``p =
   softmax(h2 Wr)`` over ALL experts in float32, top-k, renormalised,
   times ``moe_routed_scaling_factor``; ``x += sum_e w_e Expert_e(h2) +
   Shared(h2)``.

The share of an expert-parallel deployment (model-configs guide,
section 4): ``held = (first, count)`` names the experts whose weights
are given; routing is over all of them, and what the absent experts
would have added is left out.  A sliced vocabulary is a smaller one.

What the config leaves open is under ``assumed`` in the configuration
file.  ``ablate`` switches one mechanism off ("window", "gate",
"scale"): the tests' negative controls, never the reference.

One departure, stated, as in ``reference_llama``: with
``dtype=bfloat16`` the same code runs in the serving type at the
default precision; that is the yardstick of the tolerance, not the
reference.
"""

from __future__ import annotations

import functools
import math
from typing import FrozenSet, Mapping, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

FULL, SLIDING = "full_attention", "sliding_attention"
#: one layer's weights, [in, out] matrices; the gate is [hidden, n_q]
ATTN_KEYS = ("ln1", "wq", "wk", "wv", "wgate", "wo", "ln2")
DENSE_KEYS = ("wg", "wu", "wd")
#: router [hidden, E]; expert stacks [held, ...]; the shared expert
MOE_KEYS = ("router", "eg", "eu", "ed", "sg", "su", "sd")


class LayerSpec(NamedTuple):
    nq: int
    nkv: int
    d: int
    eps: float
    window: Optional[int]
    gate: bool
    top_k: int                      # 0: a dense layer
    renorm: bool
    scale: float
    held: Optional[Tuple[int, int]]
    q_block: int
    head_block: int                 # KV heads (with their query groups)


# ---------------------------------------------------------------- rope
def inv_frequencies(rp: Mapping, head_dim: int):
    """(inverse frequencies [r/2], attention factor, r)."""
    r = int(head_dim * float(rp.get("partial_rotary_factor", 1.0)))
    theta = float(rp["rope_theta"])
    plain = 1.0 / theta ** (np.arange(0, r, 2, dtype=np.float64) / r)
    kind = rp.get("rope_type", "default")
    if kind == "default":
        return plain, 1.0, r
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}")
    factor = float(rp["factor"])
    orig = float(rp["original_max_position_embeddings"])

    def correction_dim(n_rot):
        return r * math.log(orig / (n_rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rp["beta_slow"]))), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0.0, 1.0)
    inv = plain / factor * ramp + plain * (1.0 - ramp)
    af = rp.get("attention_factor")
    if af is None:
        af = 0.1 * math.log(factor) + 1.0
    return inv, float(af), r


def rope_tables(rp: Mapping, head_dim: int, n: int):
    inv, af, _ = inv_frequencies(rp, head_dim)
    f = np.outer(np.arange(n, dtype=np.float64), inv)
    return (jnp.asarray(np.cos(f) * af, jnp.float32),
            jnp.asarray(np.sin(f) * af, jnp.float32))


def _rope(x, cos, sin):
    """x [S, h, D]; rotate-half over the first 2 * cos.shape[1] dims."""
    r2 = cos.shape[1]
    x1, x2, rest = x[..., :r2], x[..., r2:2 * r2], x[..., 2 * r2:]
    c, s = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y.astype(x.dtype) * w.astype(x.dtype)


# ----------------------------------------------------------- attention
def _attention(q, k, v, window, q_block, head_block):
    """q [S, nkv, rep, D], k / v [S, nkv, D] -> [S, nkv, rep, D].
    Blocks of `q_block` queries and `head_block` KV heads, one after
    another, for memory only: a block meets every key up to its last
    query, or the span its window can see."""
    S, nkv, rep, D = q.shape
    qb = min(q_block or S, S)
    hb = head_block or nkv
    nb = -(-S // qb)
    Sp = nb * qb                                # queries padded to blocks
    span = Sp if window is None else min(Sp, qb + window - 1)

    def pad(a):
        return jnp.pad(a, ((0, Sp - S),) + ((0, 0),) * (a.ndim - 1))

    def groups(a):                              # [s, nkv, ...] -> by hb
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((nkv // hb, hb) + a.shape[1:])

    qg, kg, vg = groups(pad(q)), groups(pad(k)), groups(pad(v))

    def block(b):
        q0 = b * qb
        k0 = jnp.clip(q0 + qb - span, 0, Sp - span)
        i = (q0 + jnp.arange(qb))[:, None]
        j = (k0 + jnp.arange(span))[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window

        def heads(qkv):
            qh, kh, vh = qkv            # [hb, Sp, rep, D], [hb, Sp, D]
            qh = jax.lax.dynamic_slice_in_dim(qh, q0, qb, 1)
            kh = jax.lax.dynamic_slice_in_dim(kh, k0, span, 1)
            vh = jax.lax.dynamic_slice_in_dim(vh, k0, span, 1)
            s = jnp.einsum("gqrd,gkd->grqk", qh, kh).astype(jnp.float32)
            s = jnp.where(seen, s / np.sqrt(D), -jnp.inf)
            p = jax.nn.softmax(s, -1).astype(qh.dtype)
            return jnp.einsum("grqk,gkd->gqrd", p, vh)

        return jax.lax.map(heads, (qg, kg, vg))     # [groups, hb, qb, ..]

    o = jax.lax.map(block, jnp.arange(nb))      # [nb, groups, hb, qb, rep, D]
    o = jnp.moveaxis(o.reshape(nb, nkv, qb, rep, D), 1, 2)
    return o.reshape(Sp, nkv, rep, D)[:S]


# ----------------------------------------------------------------- ffn
def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def routing(h2, router, top_k, renorm, scale):
    """(weights [S, k], experts [S, k]) over ALL of the router's
    outputs, in float32 whatever the layer's type."""
    p = jax.nn.softmax(h2.astype(jnp.float32)
                       @ router.astype(jnp.float32), -1)
    w, e = jax.lax.top_k(p, top_k)
    if renorm:
        w = w / jnp.sum(w, -1, keepdims=True)
    return w * scale, e


def _experts(h2, w, spec: LayerSpec):
    """(sum_e w_e Expert_e(h2) over the held experts, one at a time;
    the experts [S, k] the router chose)."""
    wts, ids = routing(h2, w["router"], spec.top_k, spec.renorm, spec.scale)
    first = spec.held[0] if spec.held else 0

    def one(carry, ew):
        e, (eg, eu, ed) = ew
        mine = jnp.sum(jnp.where(ids == first + e, wts, 0.0), -1)
        y = _swiglu(h2, eg, eu, ed)
        return carry + y * mine[:, None].astype(y.dtype), None

    n = w["eg"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(h2),
                          (jnp.arange(n), (w["eg"], w["eu"], w["ed"])))
    return out, ids


# --------------------------------------------------------------- layer
def _attention_half(x, w, cos, sin, spec: LayerSpec):
    """(x after the attention block, its RMSNorm): equations 1-4 and
    the first line of 5."""
    S = x.shape[0]
    nq, nkv, d = spec.nq, spec.nkv, spec.d
    h = _rms(x, w["ln1"], spec.eps)
    q = _rope((h @ w["wq"]).reshape(S, nq, d), cos, sin)
    k = _rope((h @ w["wk"]).reshape(S, nkv, d), cos, sin)
    v = (h @ w["wv"]).reshape(S, nkv, d)
    o = _attention(q.reshape(S, nkv, nq // nkv, d), k, v, spec.window,
                   spec.q_block, spec.head_block).reshape(S, nq, d)
    if spec.gate:
        o = o * jax.nn.sigmoid(h @ w["wgate"])[..., None]
    x = x + o.reshape(S, nq * d) @ w["wo"]
    return x, _rms(x, w["ln2"], spec.eps)


def _layer(x, w, cos, sin, spec: LayerSpec, dtype, operands=None):
    if operands is not None:
        # a LOWER precision than the configuration states, for the
        # reading that has to come out as not correct: the weights and
        # the layer's input rounded to `operands` (float8), then as dtype
        w = {k: v.astype(operands) for k, v in w.items()}
        x = x.astype(operands).astype(dtype)
    w = {k: v.astype(dtype) for k, v in w.items()}
    x, h2 = _attention_half(x, w, cos, sin, spec)
    if not spec.top_k:
        return x + _swiglu(h2, w["wg"], w["wu"], w["wd"]), None
    routed, ids = _experts(h2, w, spec)
    return x + routed + _swiglu(h2, w["sg"], w["su"], w["sd"]), ids


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "operands"))
def layer(x, w, cos, sin, *, spec: LayerSpec, dtype, operands=None):
    """One decoder layer over x [S, H] (one sequence): (x, the experts
    [S, k] it routed to, or None for a dense layer)."""
    return _layer(x, w, cos, sin, spec, dtype, operands)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def head_logits(x, norm_w, head_w, *, eps, dtype):
    return (_rms(x, norm_w.astype(dtype), eps)
            @ head_w.astype(dtype)).astype(jnp.float32)


def layer_specs(cfg: Mapping, q_block: int = 0, head_block: int = 0,
                ablate: FrozenSet[str] = frozenset()) -> Sequence[LayerSpec]:
    """One LayerSpec a layer from the configuration's published keys
    (``num_experts`` is the ROUTER's width; ``experts_held`` the share)."""
    held = cfg.get("experts_held")
    out = []
    for i in range(cfg["num_hidden_layers"]):
        sliding = cfg["layer_types"][i] == SLIDING
        dense = i in cfg["mlp_only_layers"]
        out.append(LayerSpec(
            nq=cfg["num_attention_heads_per_layer"][i],
            nkv=cfg["num_key_value_heads"], d=cfg["head_dim"],
            eps=cfg["rms_norm_eps"],
            window=(cfg["sliding_window"]
                    if sliding and "window" not in ablate else None),
            gate="gate" not in ablate,
            top_k=0 if dense else cfg["num_experts_per_tok"],
            renorm=bool(cfg["norm_topk_prob"]),
            scale=(1.0 if "scale" in ablate
                   else float(cfg["moe_routed_scaling_factor"])),
            held=tuple(held) if held else None,
            q_block=q_block, head_block=head_block))
    return out


def hidden_states(ids, embed, layers: Sequence[Mapping], cfg: Mapping,
                  dtype=jnp.float32, q_block: int = 0, head_block: int = 0,
                  ablate: FrozenSet[str] = frozenset(), operands=None):
    """Embedding and every decoder layer over ids [S] (one sequence):
    (x [S, H], the experts [sparse layers, S, k] each layer routed to)."""
    S = ids.shape[0]
    tables = {kind: rope_tables(cfg["rope_parameters"][kind],
                                cfg["head_dim"], S)
              for kind in set(cfg["layer_types"][:cfg["num_hidden_layers"]])}
    x = jnp.take(embed, ids, axis=0).astype(dtype)
    routed = []
    for i, (w, spec) in enumerate(zip(
            layers, layer_specs(cfg, q_block, head_block, ablate))):
        keys = ATTN_KEYS + (MOE_KEYS if spec.top_k else DENSE_KEYS)
        x, ids_i = layer(x, {k: w[k] for k in keys},
                         *tables[cfg["layer_types"][i]], spec=spec,
                         dtype=dtype, operands=operands)
        if ids_i is not None:
            routed.append(ids_i)
    return x, routed


def logits(ids, weights: Mapping, cfg: Mapping, dtype=jnp.float32,
           **blocks):
    """The whole forward over ids [S]: [S, vocabulary held] float32."""
    x, _ = hidden_states(ids, weights["embed"], weights["layers"], cfg,
                         dtype, **blocks)
    return head_logits(x, weights["norm"], weights["head"],
                       eps=cfg["rms_norm_eps"], dtype=dtype)


def highest():
    """The reference's matmul precision: on a TPU a float32 matmul runs
    in lower precision unless this is set."""
    return jax.default_matmul_precision("highest")
