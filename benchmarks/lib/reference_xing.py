"""Plain reference of the Xing 4.0 decoder (``XingChen-AGI/
Xing4.0-29B-A4B``, ``xing4_0``).

Straightforward ``jax.numpy``: float32 under ``default_matmul_precision
("highest")``, no cache, no kernels, no batching, the UNABSORBED
attention (`reference_axk1`'s: the same latent attention at other
sizes).  Written from the published ``config.json`` and the two
hyper-connection papers (arXiv:2512.24880 over arXiv:2409.19606), and
independent of ``paddle_tpu/models``.

A token's state is ``X`` [n, C], ``n = hc_mult``.  Entry: ``X_i =
embedding(token)``.  Each SUBLAYER ``F`` (a layer has two) owns ``phi``
[n C, n^2 + 2 n], ``b``, ``a`` = (a_pre, a_post, a_res).  In float32::

    r     = (mean(vec(X)^2) + rms_norm_eps)^-1/2
    u     = r (vec(X) phi)
    Hpre  = sigmoid(a_pre u[0:n] + b[0:n])
    Hpost = 2 sigmoid(a_post u[n:2n] + b[n:2n])
    Z     = clamp(a_res u[2n:] + b[2n:], clamp_min, clamp_max)   [n, n]
    M     = exp(Z); hc_sinkhorn_iters times: M /= colsum(M) + hc_eps,
            M /= rowsum(M) + hc_eps;  Hres = M
    x_in  = sum_j Hpre_j X_j
    y     = F(RMSNorm_g(x_in))
    X'_i  = sum_j Hres[i, j] X_j + Hpost_i y

Exit: ``sum_i X_i``, the last RMSNorm, the head.  The mixer is latent
attention exactly as `reference_axk1` writes it (q-lora, the latent
norm, rope on ``q_pe`` and the one shared ``k_pe``, yarn's ``m^2``).
The FFN: SwiGLU in the first ``first_k_dense_replace`` layers; in the
others ``s = sigmoid(h W_r)`` over ALL experts in float32, the
``num_experts_per_tok`` experts chosen on ``s + bias`` (``noaux_tc``:
the bias picks, it does not weigh), ``w_e = s_e / sum s x
routed_scaling_factor``, plus the shared expert.  ``held = (first,
count)`` names the experts whose weights are given, as in
`reference_axk1`.

Departures for memory only, as there: attention over `head_block` heads
and `q_block` queries at a time, the dense SwiGLU over `ffn_block`
columns, experts one at a time, partial sums kept in float32 and
rounded to the run's type once.  The stream is kept in the run's type
(float32 here; bfloat16 in the yardstick run, as the program stores
it); the coefficients and the two weighted sums are float32 always.

``ablate`` plants one fault ("sinkhorn_1": ONE iteration; "hpost_1":
Hpost without its factor 2; "coef_bf16": the coefficients' products and
values rounded to bfloat16; "bias": the correction bias dropped): the
negative controls of the tests and of the limits, never the reference.
``operands`` rounds the weights and each layer's input to a lower type
first (float8).
"""

from __future__ import annotations

import functools
from typing import FrozenSet, Mapping, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .reference_axk1 import (_attention, _dense, _rms, _rope,  # noqa: F401
                             _swiglu, head_logits, highest, rope_tables,
                             softmax_scale)

ATTN_KEYS = ("ln1", "wqa", "gq", "wqb", "wkva", "gkv", "wkvb", "wo", "ln2")
DENSE_KEYS = ("wg", "wu", "wd")
#: router [hidden, E], its correction bias [E]; expert stacks [held,
#: ...]; the shared expert
MOE_KEYS = ("router", "bias", "eg", "eu", "ed", "sg", "su", "sd")
#: the two sublayers' mixing: phi [n C, n^2 + 2 n], b, a [3]
HC_KEYS = ("phi1", "b1", "a1", "phi2", "b2", "a2")
ABLATIONS = ("sinkhorn_1", "hpost_1", "coef_bf16", "bias")


class LayerSpec(NamedTuple):
    heads: int
    dn: int
    dr: int
    dv: int
    rank: int
    eps: float
    softmax_scale: float
    top_k: int                      # 0: a dense layer
    renorm: bool
    scale: float
    held: Optional[Tuple[int, int]]
    n: int                          # hc_mult
    iters: int
    hc_eps: float
    clamp: Tuple[float, float]
    hpost_gain: float
    coef_bf16: bool
    use_bias: bool
    q_block: int
    head_block: int
    ffn_block: int


# ------------------------------------------------------- the residual
def sinkhorn(z, iters: int, hc_eps: float):
    """exp(z) [S, n, n] (row i, column j), columns normalised first,
    rows last."""
    m = jnp.exp(z)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + hc_eps)   # columns
        m = m / (jnp.sum(m, axis=2, keepdims=True) + hc_eps)   # rows
    return m


def mix(X, phi, b, a, spec: LayerSpec):
    """X [S, n, C] -> (x_in [S, C] float32, Hpost [S, n], Hres [S, n,
    n]) — float32 whatever X's type."""
    f32 = jnp.float32
    S, n, _ = X.shape
    v = X.astype(f32).reshape(S, -1)
    r = jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + spec.eps)
    u = r * jnp.dot(v, phi.astype(f32),
                    precision=jax.lax.Precision.HIGHEST)
    b, a = b.astype(f32), a.astype(f32)
    if spec.coef_bf16:
        u = u.astype(jnp.bfloat16).astype(f32)
    hpre = jax.nn.sigmoid(a[0] * u[:, :n] + b[:n])
    hpost = spec.hpost_gain * jax.nn.sigmoid(
        a[1] * u[:, n:2 * n] + b[n:2 * n])
    z = jnp.clip(a[2] * u[:, 2 * n:] + b[2 * n:], *spec.clamp)
    hres = sinkhorn(z.reshape(S, n, n), spec.iters, spec.hc_eps)
    if spec.coef_bf16:
        hpre, hpost, hres = (t.astype(jnp.bfloat16).astype(f32)
                             for t in (hpre, hpost, hres))
    return jnp.einsum("sj,sjc->sc", hpre, X.astype(f32)), hpost, hres


def leave(X, y, hpost, hres):
    """X'_i = sum_j Hres[i, j] X_j + Hpost_i y, in float32, in X's
    type."""
    f32 = jnp.float32
    out = jnp.einsum("sij,sjc->sic", hres, X.astype(f32)) \
        + hpost[:, :, None] * y.astype(f32)[:, None, :]
    return out.astype(X.dtype)


# ----------------------------------------------------------------- ffn
def routing(h2, router, bias, spec: LayerSpec):
    """(weights [S, k], experts [S, k]) over ALL of the router's
    outputs, in float32 whatever the layer's type: chosen on the score
    plus the bias, weighed by the score alone."""
    sc = jax.nn.sigmoid(h2.astype(jnp.float32) @ router.astype(jnp.float32))
    pick = sc + bias.astype(jnp.float32) if spec.use_bias else sc
    _, e = jax.lax.top_k(pick, spec.top_k)
    wts = jnp.take_along_axis(sc, e, -1)
    if spec.renorm:
        wts = wts / jnp.sum(wts, -1, keepdims=True)
    return wts * spec.scale, e


def _experts(h2, w, spec: LayerSpec, dtype):
    """(sum_e w_e Expert_e(h2) over the held experts in float32, one at
    a time; the experts [S, k] the router chose)."""
    wts, ids = routing(h2, w["router"], w["bias"], spec)
    first = spec.held[0] if spec.held else 0

    def one(acc, ew):
        e, ws = ew
        eg, eu, ed = (m.astype(dtype) for m in ws)
        mine = jnp.sum(jnp.where(ids == first + e, wts, 0.0), -1)
        return acc + _swiglu(h2, eg, eu, ed) * mine[:, None], None

    n = w["eg"].shape[0]
    acc, _ = jax.lax.scan(one, jnp.zeros(h2.shape, jnp.float32),
                          (jnp.arange(n), (w["eg"], w["eu"], w["ed"])))
    return acc, ids


# --------------------------------------------------------------- layer
def _layer(X, w, cos, sin, spec: LayerSpec, dtype, operands=None):
    if operands is not None:
        w = {k: v.astype(operands).astype(v.dtype) for k, v in w.items()}
        X = X.astype(operands).astype(dtype)

    def t(k):
        return w[k].astype(dtype)

    a, hpost, hres = mix(X, w["phi1"], w["b1"], w["a1"], spec)
    h = _rms(a.astype(dtype), w["ln1"], spec.eps)
    c_q = _rms(h @ t("wqa"), w["gq"], spec.eps)
    kv_a = h @ t("wkva")
    c = _rms(kv_a[:, :spec.rank], w["gkv"], spec.eps)
    k_pe = _rope(kv_a[:, spec.rank:], cos, sin)
    y = _attention(c_q, c, k_pe, w, cos, sin, spec, dtype).astype(dtype)
    X = leave(X, y, hpost, hres)
    a, hpost, hres = mix(X, w["phi2"], w["b2"], w["a2"], spec)
    h2 = _rms(a.astype(dtype), w["ln2"], spec.eps)
    if not spec.top_k:
        return leave(X, _dense(h2, w, spec, dtype).astype(dtype), hpost,
                     hres), None
    y, ids = _experts(h2, w, spec, dtype)
    y = y + _swiglu(h2, t("sg"), t("su"), t("sd"))
    return leave(X, y.astype(dtype), hpost, hres), ids


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "operands"),
                   donate_argnums=0)
def layer(X, w, cos, sin, *, spec: LayerSpec, dtype, operands=None):
    """One decoder layer over the stream X [S, n, C] (one sequence):
    (X, the experts [S, k] it routed to, or None for a dense layer)."""
    return _layer(X, w, cos, sin, spec, dtype, operands)


def layer_specs(cfg: Mapping, q_block: int = 0, head_block: int = 0,
                ffn_block: int = 0,
                ablate: FrozenSet[str] = frozenset()) -> Sequence[LayerSpec]:
    """One LayerSpec a layer from the configuration's published keys
    (``n_routed_experts`` is the ROUTER's width; ``experts_held`` the
    share)."""
    unknown = set(ablate) - set(ABLATIONS)
    if unknown:
        raise ValueError(f"unknown ablation {sorted(unknown)}")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise NotImplementedError("a group limit: see reference_axk1")
    held = cfg.get("experts_held")
    out = []
    for i in range(cfg["num_hidden_layers"]):
        dense = i < cfg["first_k_dense_replace"]
        out.append(LayerSpec(
            heads=cfg["num_attention_heads"],
            dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
            dv=cfg["v_head_dim"], rank=cfg["kv_lora_rank"],
            eps=cfg["rms_norm_eps"], softmax_scale=softmax_scale(cfg),
            top_k=0 if dense else cfg["num_experts_per_tok"],
            renorm=bool(cfg["norm_topk_prob"]),
            scale=float(cfg["routed_scaling_factor"]),
            held=tuple(held) if held else None,
            n=cfg["hc_mult"],
            iters=1 if "sinkhorn_1" in ablate else cfg["hc_sinkhorn_iters"],
            hc_eps=float(cfg["hc_eps"]),
            clamp=(float(cfg["mhc_h_res_clamp_min"]),
                   float(cfg["mhc_h_res_clamp_max"])),
            hpost_gain=1.0 if "hpost_1" in ablate else 2.0,
            coef_bf16="coef_bf16" in ablate,
            use_bias="bias" not in ablate,
            q_block=q_block, head_block=head_block, ffn_block=ffn_block))
    return out


def hidden_states(ids, embed, layers: Sequence[Mapping], cfg: Mapping,
                  dtype=jnp.float32, q_block: int = 0, head_block: int = 0,
                  ffn_block: int = 0,
                  ablate: FrozenSet[str] = frozenset(), operands=None,
                  stream: bool = False):
    """Embedding, every decoder layer and the exit over ids [S] (one
    sequence): (x [S, C] = the SUM of the streams — or, with `stream`,
    the streams themselves [S, n, C] —, the experts [sparse layers, S,
    k] each layer routed to)."""
    cos, sin = rope_tables(cfg, ids.shape[0])
    x = jnp.take(embed, ids, axis=0).astype(dtype)
    X = jnp.repeat(x[:, None, :], cfg["hc_mult"], axis=1)
    routed = []
    for w, spec in zip(layers, layer_specs(cfg, q_block, head_block,
                                           ffn_block, ablate)):
        keys = ATTN_KEYS + HC_KEYS + (MOE_KEYS if spec.top_k
                                      else DENSE_KEYS)
        X, ids_i = layer(X, {k: w[k] for k in keys}, cos, sin, spec=spec,
                         dtype=dtype, operands=operands)
        if ids_i is not None:
            routed.append(ids_i)
    if stream:
        return X, routed
    return jnp.sum(X.astype(jnp.float32), 1).astype(dtype), routed


def logits(ids, weights: Mapping, cfg: Mapping, dtype=jnp.float32,
           **blocks):
    """The whole forward over ids [S]: [S, vocabulary held] float32."""
    x, _ = hidden_states(ids, weights["embed"], weights["layers"], cfg,
                         dtype, **blocks)
    return head_logits(x, weights["norm"], weights["head"],
                       eps=cfg["rms_norm_eps"], dtype=dtype)
