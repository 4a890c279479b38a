"""Plain reference of the A.X-K1 decoder (``skt/A.X-K1``, ``axk1``).

Straightforward ``jax.numpy``: float32 under ``default_matmul_precision
("highest")``, no cache, no kernels, no batching, the UNABSORBED
equations (every head's own keys and values are built from the latent;
the program runs the absorbed form).  Written from the published
``config.json``, whose keys read as DeepSeek-V3's of the same names, and
independent of ``paddle_tpu/models``.  With ``h = RMSNorm(x)``:

1. ``c_q = RMSNorm(h W_qa)`` [q_lora_rank]; ``q = c_q W_qb`` -> heads of
   ``(q_nope [128] | q_pe [64])``.
2. ``kv_a = h W_kva`` [512 + 64]; ``c = RMSNorm(kv_a[:512])``; ``k_pe =
   RoPE(kv_a[512:])``, ONE head shared by all query heads.
3. ``(k_nope_a | v_a) = c W_kvb`` for head a (128 | 128).
   ``score_a(i, j) = s (q_nope_a(i) . k_nope_a(j) + RoPE(q_pe_a(i)) .
   k_pe(j))``, causal; ``s = 192^-1/2 x m^2``, ``m = 0.1 x
   mscale_all_dim x ln(factor) + 1``.  Rope: rotate-half, yarn inverse
   frequencies (HF's ``_compute_yarn_parameters``), cos and sin times
   ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
   ``x += concat_a (softmax(score_a) v_a) W_o``.
4. ``h2 = RMSNorm(x)``.  The first ``first_k_dense_replace`` layers:
   SwiGLU.  The others: ``sc = sigmoid(h2 W_g)`` over ALL experts in
   float32; ``n_group`` groups of consecutive experts, a group's score
   the sum of its two largest ``sc``, the ``topk_group`` best stay; top-k
   of ``sc`` inside them; ``w_e = sc_e / sum_topk sc x
   routed_scaling_factor``; ``x += sum_e w_e Expert_e(h2) + Shared(h2)``.

The share of an expert-parallel deployment (model-configs guide,
section 4): ``held = (first, count)`` names the experts whose weights
are given; routing is over all of them, and what the absent experts
would have added is left out.  A sliced vocabulary is a smaller one.

Departures from the published description, for memory only (a 16 k
sample beside the resident engine), none of which changes a number
beyond float32 summation order: attention runs over `head_block` heads
and `q_block` queries at a time, each block against every key under the
causal mask, and a head block's output goes through its own rows of
``W_o`` at once; the dense SwiGLU runs over `ffn_block` of its columns
at a time; experts run one at a time; each of those partial sums is
kept in float32 and rounded to the run's type once, when it is whole
(as an unblocked product is; else the bfloat16 run, the tolerance's
yardstick, would round the residual stream 32 times a layer); a matrix
is cast to the run's type where it is used, one at a time.

``ablate`` switches one mechanism off ("k_rope", "mscale",
"latent_norm", "group_limit", "shared", "scale"): the negative controls
of the tests and of the limits, never the reference.  With
``dtype=bfloat16`` the same code runs in the serving type at the default
precision: the yardstick of the tolerance, not the reference.
``operands`` rounds the weights and each layer's input to a lower type
first (float8): the reading that has to come out as not correct.
"""

from __future__ import annotations

import functools
import math
from typing import FrozenSet, Mapping, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import reference_laguna as _laguna
from .reference_laguna import _rms, _swiglu, highest  # noqa: F401

#: one layer's weights, [in, out] matrices
ATTN_KEYS = ("ln1", "wqa", "gq", "wqb", "wkva", "gkv", "wkvb", "wo", "ln2")
DENSE_KEYS = ("wg", "wu", "wd")
#: router [hidden, E]; expert stacks [held, ...]; the shared expert
MOE_KEYS = ("router", "eg", "eu", "ed", "sg", "su", "sd")
ABLATIONS = ("k_rope", "mscale", "latent_norm", "group_limit", "shared",
             "scale")


class LayerSpec(NamedTuple):
    heads: int
    dn: int
    dr: int
    dv: int
    rank: int                       # kv_lora_rank
    eps: float
    softmax_scale: float
    top_k: int                      # 0: a dense layer
    n_group: int
    topk_group: int
    renorm: bool
    scale: float
    held: Optional[Tuple[int, int]]
    k_rope: bool
    latent_norm: bool
    shared: bool
    q_block: int
    head_block: int
    ffn_block: int


# ---------------------------------------------------------------- rope
def yarn_mscale(factor: float, mscale: float) -> float:
    if factor <= 1 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def rope_tables(cfg: Mapping, n: int):
    """(cos, sin) float32 [n, qk_rope_head_dim / 2]: the default table,
    or yarn's (HF's ``_compute_yarn_parameters``, as `reference_laguna`
    writes it out) with cos and sin times mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)."""
    rp = {"rope_theta": cfg["rope_theta"]}
    rs = cfg.get("rope_scaling")
    if rs:
        factor = float(rs["factor"])
        rp.update(
            rope_type="yarn", factor=factor,
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            attention_factor=yarn_mscale(factor, float(rs.get("mscale", 1)))
            / yarn_mscale(factor, float(rs.get("mscale_all_dim", 0))))
    return _laguna.rope_tables(rp, int(cfg["qk_rope_head_dim"]), n)


def softmax_scale(cfg: Mapping, mscale: bool = True) -> float:
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if mscale and rs and rs.get("mscale_all_dim"):
        s *= yarn_mscale(float(rs["factor"]),
                         float(rs["mscale_all_dim"])) ** 2
    return float(s)


def _rope(x, cos, sin):
    """x [S, ..., r]; rotate-half over the last dim."""
    r2 = x.shape[-1] // 2
    x1, x2 = x[..., :r2], x[..., r2:]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (r2,)
    c, s = cos.reshape(shape).astype(x.dtype), sin.reshape(shape).astype(
        x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _f32_dot(a, b):
    """a @ b with a float32 result: a block's share of a sum that is
    rounded to the run's type ONCE, when the whole sum is there, as an
    unblocked product would be (the blocks are for memory only)."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


# ----------------------------------------------------------- attention
def _attention(c_q, c, k_pe, w, cos, sin, spec: LayerSpec, dtype):
    """concat_a(softmax(score_a) v_a) W_o in float32, `head_block` heads
    and `q_block` queries at a time."""
    S = c_q.shape[0]
    nh, dn, dr, dv = spec.heads, spec.dn, spec.dr, spec.dv
    hb = min(spec.head_block or nh, nh)
    qb = min(spec.q_block or S, S)
    nb = -(-S // qb)
    Sp = nb * qb
    G = nh // hb

    def by_group(m, width):         # [in, nh * width] -> [G, in, hb, width]
        return jnp.moveaxis(m.reshape(m.shape[0], G, hb, width), 1, 0)

    def pad(a):
        return jnp.pad(a, ((0, Sp - S),) + ((0, 0),) * (a.ndim - 1))

    i = jnp.arange(qb)[:, None]
    j = jnp.arange(Sp)[None, :]
    kp = pad(k_pe)

    def group(acc, ws):
        wq, wkv, wo = (m.astype(dtype) for m in ws)
        q = pad(jnp.einsum("sr,rhd->shd", c_q, wq))        # [Sp, hb, dn+dr]
        q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], *(
            pad(t) for t in (cos, sin)))
        kv = pad(jnp.einsum("sr,rhd->shd", c, wkv))        # [Sp, hb, dn+dv]
        k_nope, v = kv[..., :dn], kv[..., dn:]

        def block(b):
            q0 = b * qb
            qn = jax.lax.dynamic_slice_in_dim(q_nope, q0, qb, 0)
            qp = jax.lax.dynamic_slice_in_dim(q_pe, q0, qb, 0)
            s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                 + jnp.einsum("qhd,kd->hqk", qp, kp)).astype(jnp.float32)
            s = jnp.where(j <= q0 + i, s * spec.softmax_scale, -jnp.inf)
            p = jax.nn.softmax(s, -1).astype(v.dtype)
            return jnp.einsum("hqk,khd->qhd", p, v)

        o = jax.lax.map(block, jnp.arange(nb)).reshape(Sp, hb * dv)[:S]
        return acc + _f32_dot(o, wo), None

    wo = w["wo"].reshape(G, hb * dv, w["wo"].shape[1])
    acc, _ = jax.lax.scan(
        group, jnp.zeros((S, wo.shape[-1]), jnp.float32),
        (by_group(w["wqb"], dn + dr), by_group(w["wkvb"], dn + dv), wo))
    return acc


# ----------------------------------------------------------------- ffn
def _dense(h2, w, spec: LayerSpec, dtype):
    """SwiGLU(h2) in float32, `ffn_block` columns of the intermediate
    at a time (a column block's product goes through its own rows of
    W_d)."""
    H, I = w["wg"].shape
    blk = min(spec.ffn_block or I, I)
    n = I // blk

    def cols(m):                    # [H, I] -> [n, H, blk]
        return jnp.moveaxis(m.reshape(H, n, blk), 1, 0)

    def one(acc, ws):
        wg, wu, wd = (m.astype(dtype) for m in ws)
        return acc + _f32_dot(jax.nn.silu(h2 @ wg) * (h2 @ wu), wd), None

    acc, _ = jax.lax.scan(one, jnp.zeros(h2.shape, jnp.float32),
                          (cols(w["wg"]), cols(w["wu"]),
                           w["wd"].reshape(n, blk, H)))
    return acc


def routing(h2, router, spec: LayerSpec):
    """(weights [S, k], experts [S, k]) over ALL of the router's
    outputs, in float32 whatever the layer's type."""
    sc = jax.nn.sigmoid(h2.astype(jnp.float32) @ router.astype(jnp.float32))
    S, E = sc.shape
    pick = sc
    if spec.n_group > 1:
        per = E // spec.n_group
        g = sc.reshape(S, spec.n_group, per)
        g2 = jnp.sort(g, -1)[..., -2:].sum(-1)              # [S, groups]
        rank = jnp.argsort(jnp.argsort(-g2, -1), -1)
        stay = jnp.repeat(rank < spec.topk_group, per, axis=1)
        pick = jnp.where(stay, sc, -jnp.inf)
    _, e = jax.lax.top_k(pick, spec.top_k)
    wts = jnp.take_along_axis(sc, e, -1)
    if spec.renorm:
        wts = wts / jnp.sum(wts, -1, keepdims=True)
    return wts * spec.scale, e


def _experts(h2, w, spec: LayerSpec, dtype):
    """(sum_e w_e Expert_e(h2) over the held experts in float32, one at
    a time; the experts [S, k] the router chose)."""
    wts, ids = routing(h2, w["router"], spec)
    first = spec.held[0] if spec.held else 0

    def one(acc, ew):
        e, ws = ew
        eg, eu, ed = (m.astype(dtype) for m in ws)
        mine = jnp.sum(jnp.where(ids == first + e, wts, 0.0), -1)
        # an expert's output in the run's type, weighed in float32
        return acc + _swiglu(h2, eg, eu, ed) * mine[:, None], None

    n = w["eg"].shape[0]
    acc, _ = jax.lax.scan(one, jnp.zeros(h2.shape, jnp.float32),
                          (jnp.arange(n), (w["eg"], w["eu"], w["ed"])))
    return acc, ids


# --------------------------------------------------------------- layer
def _layer(x, w, cos, sin, spec: LayerSpec, dtype, operands=None):
    if operands is not None:
        # a LOWER precision than the configuration states: the weights
        # and the layer's input rounded to `operands` (float8)
        w = {k: v.astype(operands).astype(v.dtype) for k, v in w.items()}
        x = x.astype(operands).astype(dtype)

    def t(k):
        return w[k].astype(dtype)

    h = _rms(x, w["ln1"], spec.eps)
    c_q = _rms(h @ t("wqa"), w["gq"], spec.eps)
    kv_a = h @ t("wkva")
    c, k_pe = kv_a[:, :spec.rank], kv_a[:, spec.rank:]
    if spec.latent_norm:
        c = _rms(c, w["gkv"], spec.eps)
    if spec.k_rope:
        k_pe = _rope(k_pe, cos, sin)
    x = x + _attention(c_q, c, k_pe, w, cos, sin, spec, dtype).astype(dtype)
    h2 = _rms(x, w["ln2"], spec.eps)
    if not spec.top_k:
        return x + _dense(h2, w, spec, dtype).astype(dtype), None
    y, ids = _experts(h2, w, spec, dtype)
    if spec.shared:
        y = y + _swiglu(h2, t("sg"), t("su"), t("sd"))
    return x + y.astype(dtype), ids


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "operands"),
                   donate_argnums=0)
def layer(x, w, cos, sin, *, spec: LayerSpec, dtype, operands=None):
    """One decoder layer over x [S, H] (one sequence): (x, the experts
    [S, k] it routed to, or None for a dense layer)."""
    return _layer(x, w, cos, sin, spec, dtype, operands)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def head_logits(x, norm_w, head_w, *, eps, dtype):
    return (_rms(x, norm_w.astype(dtype), eps)
            @ head_w.astype(dtype)).astype(jnp.float32)


def layer_specs(cfg: Mapping, q_block: int = 0, head_block: int = 0,
                ffn_block: int = 0,
                ablate: FrozenSet[str] = frozenset()) -> Sequence[LayerSpec]:
    """One LayerSpec a layer from the configuration's published keys
    (``n_routed_experts`` is the ROUTER's width; ``experts_held`` the
    share)."""
    unknown = set(ablate) - set(ABLATIONS)
    if unknown:
        raise ValueError(f"unknown ablation {sorted(unknown)}")
    held = cfg.get("experts_held")
    grouped = "group_limit" not in ablate
    out = []
    for i in range(cfg["num_hidden_layers"]):
        dense = i < cfg["first_k_dense_replace"]
        out.append(LayerSpec(
            heads=cfg["num_attention_heads"],
            dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
            dv=cfg["v_head_dim"], rank=cfg["kv_lora_rank"],
            eps=cfg["rms_norm_eps"],
            softmax_scale=softmax_scale(cfg, "mscale" not in ablate),
            top_k=0 if dense else cfg["num_experts_per_tok"],
            n_group=cfg["n_group"] if grouped else 1,
            topk_group=cfg["topk_group"] if grouped else 1,
            renorm=bool(cfg["norm_topk_prob"]),
            scale=(1.0 if "scale" in ablate
                   else float(cfg["routed_scaling_factor"])),
            held=tuple(held) if held else None,
            k_rope="k_rope" not in ablate,
            latent_norm="latent_norm" not in ablate,
            shared="shared" not in ablate,
            q_block=q_block, head_block=head_block, ffn_block=ffn_block))
    return out


def hidden_states(ids, embed, layers: Sequence[Mapping], cfg: Mapping,
                  dtype=jnp.float32, q_block: int = 0, head_block: int = 0,
                  ffn_block: int = 0,
                  ablate: FrozenSet[str] = frozenset(), operands=None):
    """Embedding and every decoder layer over ids [S] (one sequence):
    (x [S, H], the experts [sparse layers, S, k] each layer routed to)."""
    cos, sin = rope_tables(cfg, ids.shape[0])
    x = jnp.take(embed, ids, axis=0).astype(dtype)
    routed = []
    for w, spec in zip(layers, layer_specs(cfg, q_block, head_block,
                                           ffn_block, ablate)):
        keys = ATTN_KEYS + (MOE_KEYS if spec.top_k else DENSE_KEYS)
        x, ids_i = layer(x, {k: w[k] for k in keys}, cos, sin, spec=spec,
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
