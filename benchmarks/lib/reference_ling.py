"""Plain reference of the Ling 3.0 hybrid decoder
(``inclusionAI/Ling-3.0-flash``, ``bailing_hybrid``).

Straightforward ``jax.numpy``: float32 under ``default_matmul_precision
("highest")``, no cache, no kernels, no batching, the delta-rule
recurrence TOKEN BY TOKEN over the whole sequence (the program runs one
step a decode row in place and a WY-form scan over a prefill chunk).
Written from the published ``config.json`` and the families' forms (KDA,
arXiv:2510.26692; DeepSeek's latent attention; a sigmoid router with a
bias of the choice, limited to the best groups), independent of
``paddle_tpu``.  ``H`` hidden, RMSNorm with plain gain (eps
``rms_norm_eps``), no bias, positions from 0.  Published layer ``i`` is
``x += mixer_i(RMSNorm(x))``, ``x += ffn_i(RMSNorm(x))``: two blocks,
in the letters `pattern` derives from the config:

1. ``x_0 = Emb[tok]``; after the last block ``logits = RMSNorm(x; g_f)
   W_head``.
2. ``K`` where ``(i + 1) % layer_group_size != 0`` (KDA; h heads x d):
   ``q~, k~, v~ = a W_q, a W_k, a W_v``; each ``u_t <- silu(sum_{j<K}
   w[:, j] u_{t-K+1+j})``, zeros before position 0, no bias; per head
   ``q = q~ / sqrt(|q~|^2 + 1e-6) x d^-1/2``, ``k = k~ / sqrt(|k~|^2 +
   1e-6)``; ``g_t = kda_lower_bound x sigmoid(exp(A_log[h]) x (a W_f +
   dt_bias))`` per key channel; ``beta_t = sigmoid(a W_beta)`` per head;
   state ``S_h`` [d, d] float32, ``S_{-1} = 0``: ``S' = Diag(exp(g_t))
   S_{t-1}``; ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``; ``o_t = S_t^T
   q_t``; ``y = RMSNorm(o; g_n [d]) x sigmoid(a W_g)[h]`` (the norm
   FIRST, then the gate); out ``y W_o``.
3. ``L`` otherwise (latent attention): ``q = a W_q`` -> heads of
   ``(q_nope | q_pe)``; ``a W_kva = (c | k_pe)``; ``c <- RMSNorm(c)``;
   RoPE at ``rope_theta`` on INTERLEAVED pairs (2j, 2j + 1) of ``q_pe``
   and ``k_pe`` (one rope key for all heads); ``(k_nope | v) = c W_kvb``
   a head; causal softmax at ``(d_nope + d_rope)^-1/2``; each head's
   output times ``sigmoid(a W_g)[h]``; out ``o W_o``.
4. ``D`` where ``i < first_k_dense_replace``: ``(silu(a W_g) x a W_u)
   W_d``.
5. ``E`` otherwise: ``s = sigmoid(a_f32 W_r)`` over all experts; the
   choice on ``s + b``: ``n_group`` groups of consecutive experts, a
   group's mark the sum of its two largest, the ``topk_group`` best
   stay, the top-k inside them; ``w_e = routed_scaling_factor x s_e /
   (sum of the chosen s + 1e-20)``; SwiGLU experts; one shared SwiGLU
   expert added ungated.

The share of an expert-parallel deployment (model-configs guide,
section 4): ``experts_held = (first, count)`` names the experts whose
weights are given; routing is over all of them, and what the absent
experts would have added is left out.  A sliced vocabulary is a smaller
one.  ``layers_held`` are the published indices of the layers given.

Departures from the description, for memory only, none of which changes
a number beyond float32 summation order: the experts run
``expert_block`` at a time, attention ``q_block`` queries at a time;
each partial sum is kept in float32 and rounded to the run's type once.

``ablate`` plants one fault ("state_bf16": the recurrent state rounded
to bfloat16 after every token; "expert_bias": dropped from the choice;
"group_limit": the top-k over all groups; "gate_order": the gate first,
then the norm; "attn_gate": no gate on the latent mixer's heads;
"channel_decay": every channel of a head decays by the head's mean log
gate): the negative controls of the tests and of the limits, never the
reference.  With ``dtype=bfloat16`` the same code runs in the serving
type at the default precision (the state and the recurrence's operands
stay float32, as the configuration states): the yardstick of the
tolerance, not the reference.  ``operands`` rounds the weights and each
block's input to a lower type first (float8): a reading that has to
come out as not correct.
"""

from __future__ import annotations

import functools
from typing import FrozenSet, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

ABLATIONS = ("state_bf16", "expert_bias", "group_limit", "gate_order",
             "attn_gate", "channel_decay")


def highest():
    """The reference's matmul precision: on a TPU a float32 matmul runs
    in lower precision unless this is set."""
    return jax.default_matmul_precision("highest")


def pattern(cfg: Mapping) -> str:
    """Two letters a layer given: its mixer, its FFN."""
    held = cfg.get("layers_held") or range(cfg["num_hidden_layers"])
    return "".join(
        ("L" if (i + 1) % cfg["layer_group_size"] == 0 else "K")
        + ("D" if i < cfg["first_k_dense_replace"] else "E") for i in held)


class Spec(NamedTuple):
    kind: str
    eps: float
    heads: int = 0                  # both mixers
    head_dim: int = 0               # KDA
    kernel: int = 0
    lower: float = -5.0
    rank: int = 0                   # latent attention
    d_nope: int = 0
    d_rope: int = 0
    d_v: int = 0
    theta: float = 0.0
    q_block: int = 0
    top_k: int = 0                  # routed FFN
    n_group: int = 1
    topk_group: int = 1
    renorm: bool = True
    scale: float = 1.0
    held: Optional[Tuple[int, int]] = None
    expert_block: int = 1
    ablate: FrozenSet[str] = frozenset()


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y.astype(x.dtype) * w.astype(x.dtype)


def _f32_dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


# ----------------------------------------------------------------- KDA
def _conv_silu(u, w, K: int):
    """The depthwise causal convolution (zeros before position 0, no
    bias) and its silu: u [S, W], w [W, K]."""
    S = u.shape[0]
    f32 = jnp.float32
    up = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    acc = jnp.zeros((S, u.shape[1]), f32)
    for j in range(K):
        acc = acc + w[:, j].astype(f32)[None] * up[j:j + S].astype(f32)
    return jax.nn.silu(acc).astype(u.dtype)


def _kda(a, w, s: Spec, dtype):
    S = a.shape[0]
    H, D, K = s.heads, s.head_dim, s.kernel
    f32 = jnp.float32

    def stream(proj, conv):
        u = _conv_silu(a @ w[proj].astype(dtype), w[conv], K)
        return u.reshape(S, H, D).astype(f32)

    q, k, v = (stream(p, c) for p, c in (("wq", "q_conv"), ("wk", "k_conv"),
                                        ("wv", "v_conv")))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        * D ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    f = (a @ w["wf"].astype(dtype)).astype(f32) + w["dt_bias"].astype(f32)
    g = s.lower * jax.nn.sigmoid(
        jnp.exp(w["A_log"].astype(f32))[None, :, None] * f.reshape(S, H, D))
    if "channel_decay" in s.ablate:
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid((a @ w["wb"].astype(dtype)).astype(f32))  # [S, H]

    def token(state, row):                              # state [H, D, D]
        qt, kt, vt, gt, bt = row
        state = jnp.exp(gt)[:, :, None] * state
        seen = jnp.sum(state * kt[:, :, None], 1)       # S'^T k [H, D]
        state = state + kt[:, :, None] * (bt[:, None] * (vt - seen))[:, None]
        if "state_bf16" in s.ablate:
            # (not a pair of casts: the TPU compiler keeps the excess
            # precision of float32 -> bfloat16 -> float32 and the fault
            # would not be planted)
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, jnp.sum(state * qt[:, :, None], 1)

    _, o = jax.lax.scan(token, jnp.zeros((H, D, D), f32),
                        (q, k, v, g, beta))
    gate = jax.nn.sigmoid((a @ w["wgate"].astype(dtype)).astype(f32))

    def norm(y):
        return y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + s.eps)

    gn = w["norm_g"].astype(f32)
    if "gate_order" in s.ablate:
        y = norm(o * gate[..., None]) * gn
    else:
        y = norm(o) * gn * gate[..., None]
    return _f32_dot(y.reshape(S, H * D).astype(dtype), w["wo"].astype(dtype))


# ---------------------------------------------------- latent attention
def _rope_pairs(t, theta: float):
    """t [S, ..., d] turned on INTERLEAVED pairs (2j, 2j + 1) by the
    angle ``position x theta^(-2j / d)``; float32 inside."""
    S, d = t.shape[0], t.shape[-1]
    f32 = jnp.float32
    inv = theta ** (-jnp.arange(0, d, 2, dtype=f32) / d)
    ang = jnp.arange(S, dtype=f32)[:, None] * inv[None]        # [S, d/2]
    ex = (slice(None),) + (None,) * (t.ndim - 2)
    c, sn = jnp.cos(ang)[ex], jnp.sin(ang)[ex]
    p = t.astype(f32).reshape(t.shape[:-1] + (d // 2, 2))
    out = jnp.stack([p[..., 0] * c - p[..., 1] * sn,
                     p[..., 1] * c + p[..., 0] * sn], -1)
    return out.reshape(t.shape).astype(t.dtype)


def _latent(a, w, s: Spec, dtype):
    S = a.shape[0]
    nh, dn, dr, dv, r = s.heads, s.d_nope, s.d_rope, s.d_v, s.rank
    f32 = jnp.float32
    q = (a @ w["wq"].astype(dtype)).reshape(S, nh, dn + dr)
    kva = a @ w["wkva"].astype(dtype)
    c = _rms(kva[:, :r], w["gkv"], s.eps)
    q_pe = _rope_pairs(q[..., dn:], s.theta)
    k_pe = _rope_pairs(kva[:, r:], s.theta)                     # [S, dr]
    kv = (c @ w["wkvb"].astype(dtype)).reshape(S, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    qb = min(s.q_block or S, S)
    nb = -(-S // qb)
    pad = ((0, nb * qb - S), (0, 0), (0, 0))
    qn, qp = jnp.pad(q[..., :dn], pad), jnp.pad(q_pe, pad)
    i, j = jnp.arange(qb)[:, None], jnp.arange(S)[None, :]

    def block(b):
        q0 = b * qb
        take = lambda m: jax.lax.dynamic_slice_in_dim(m, q0, qb, 0)  # noqa
        sc = (jnp.einsum("qnd,knd->nqk", take(qn), k_nope)
              + jnp.einsum("qnd,kd->nqk", take(qp), k_pe)).astype(f32) \
            * (dn + dr) ** -0.5
        sc = jnp.where(j <= q0 + i, sc, -jnp.inf)
        p = jax.nn.softmax(sc, -1).astype(v.dtype)
        return jnp.einsum("nqk,knd->qnd", p, v)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(nb * qb, nh, dv)[:S]
    if "attn_gate" not in s.ablate:
        gate = jax.nn.sigmoid((a @ w["wgate"].astype(dtype)).astype(f32))
        o = (o.astype(f32) * gate[..., None]).astype(dtype)
    return _f32_dot(o.reshape(S, nh * dv), w["wo"].astype(dtype))


# ---------------------------------------------------------------- FFNs
def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def routing(a, router, bias, s: Spec):
    """(weights [S, k], experts [S, k]) over ALL of the router's
    outputs, in float32 whatever the block's type."""
    f32 = jnp.float32
    sc = jax.nn.sigmoid(a.astype(f32) @ router.astype(f32))
    pick = sc if "expert_bias" in s.ablate else sc + bias.astype(f32)
    S, E = pick.shape
    if s.n_group > 1 and "group_limit" not in s.ablate:
        per = E // s.n_group
        g2 = jnp.sort(pick.reshape(S, s.n_group, per), -1)[..., -2:].sum(-1)
        rank = jnp.argsort(jnp.argsort(-g2, -1), -1)    # 0 = the best
        stay = jnp.repeat(rank < s.topk_group, per, axis=1)
        pick = jnp.where(stay, pick, -jnp.inf)
    _, e = jax.lax.top_k(pick, s.top_k)
    wts = jnp.take_along_axis(sc, e, -1)
    if s.renorm:
        wts = wts / (jnp.sum(wts, -1, keepdims=True) + 1e-20)
    return wts * s.scale, e


def _moe(a, w, s: Spec, dtype):
    wts, ids = routing(a, w["router"], w["bias"], s)
    first = s.held[0] if s.held else 0
    n = w["eu"].shape[0]
    blk = max(1, min(s.expert_block, n))
    while n % blk:
        blk -= 1

    def some(acc, ew):
        e0, (eg, eu, ed) = ew
        eg, eu, ed = (m.astype(dtype) for m in (eg, eu, ed))
        e = first + e0 + jnp.arange(blk)
        mine = jnp.sum(jnp.where(ids[None] == e[:, None, None],
                                 wts[None], 0.0), -1)        # [blk, S]
        f = jnp.einsum("esi,eih->esh", jax.nn.silu(
            jnp.einsum("sh,ehi->esi", a, eg))
            * jnp.einsum("sh,ehi->esi", a, eu), ed)
        # an expert's output in the run's type, weighed in float32
        return acc + jnp.einsum("es,esh->sh", mine, f.astype(jnp.float32)), \
            None

    def blocks(m):
        return m.reshape((n // blk, blk) + m.shape[1:])

    acc, _ = jax.lax.scan(
        some, jnp.zeros(a.shape, jnp.float32),
        (jnp.arange(0, n, blk),
         (blocks(w["eg"]), blocks(w["eu"]), blocks(w["ed"]))))
    y = acc + _swiglu(a, *(w[k].astype(dtype)
                           for k in ("sg", "su", "sd"))).astype(jnp.float32)
    return y, ids


# --------------------------------------------------------------- block
@functools.partial(jax.jit, static_argnames=("spec", "dtype", "operands"))
def block(x, w, *, spec: Spec, dtype, operands=None):
    """One block over x [S, hidden] (one sequence): (x, the experts [S,
    k] an ``E`` block routed to, else None)."""
    if operands is not None:
        w = {k: v.astype(operands).astype(v.dtype) for k, v in w.items()}
        x = x.astype(operands).astype(dtype)
    a = _rms(x, w["norm"], spec.eps)
    if spec.kind == "K":
        return x + _kda(a, w, spec, dtype).astype(dtype), None
    if spec.kind == "L":
        return x + _latent(a, w, spec, dtype).astype(dtype), None
    if spec.kind == "D":
        return x + _swiglu(a, *(w[k].astype(dtype)
                                for k in ("wg", "wu", "wd"))), None
    y, ids = _moe(a, w, spec, dtype)
    return x + y.astype(dtype), ids


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def head_logits(x, norm_w, head_w, *, eps, dtype):
    return (_rms(x, norm_w.astype(dtype), eps)
            @ head_w.astype(dtype)).astype(jnp.float32)


def specs(cfg: Mapping, ablate: FrozenSet[str] = frozenset(),
          q_block: int = 0, expert_block: int = 1):
    """One Spec a block from the configuration's published keys
    (``num_experts`` is the ROUTER's width; ``experts_held`` the
    share)."""
    unknown = set(ablate) - set(ABLATIONS)
    if unknown:
        raise ValueError(f"unknown ablation {sorted(unknown)}")
    held = cfg.get("experts_held")
    common = dict(eps=float(cfg["rms_norm_eps"]), ablate=frozenset(ablate))
    kinds = {
        "K": dict(heads=cfg["num_attention_heads"],
                  head_dim=cfg["head_dim"],
                  kernel=cfg["short_conv_kernel_size"],
                  lower=float(cfg["kda_lower_bound"])),
        "L": dict(heads=cfg["num_attention_heads"],
                  rank=cfg["kv_lora_rank"], d_nope=cfg["qk_nope_head_dim"],
                  d_rope=cfg["qk_rope_head_dim"], d_v=cfg["v_head_dim"],
                  theta=float(cfg["rope_theta"]), q_block=q_block),
        "D": {},
        "E": dict(top_k=cfg["num_experts_per_tok"],
                  n_group=cfg["n_group"], topk_group=cfg["topk_group"],
                  renorm=bool(cfg["norm_topk_prob"]),
                  scale=float(cfg["routed_scaling_factor"]),
                  held=tuple(held) if held else None,
                  expert_block=expert_block)}
    return [Spec(kind=k, **common, **kinds[k]) for k in pattern(cfg)]


def hidden_states(ids, embed, layers, cfg: Mapping, dtype,
                  ablate: FrozenSet[str] = frozenset(), operands=None,
                  q_block: int = 0, expert_block: int = 1):
    """ids [S] -> (x [S, hidden] before the last norm, the experts [S,
    k] each ``E`` block routed to)."""
    x = embed[ids].astype(dtype)
    routed = []
    for w, spec in zip(layers, specs(cfg, ablate, q_block, expert_block)):
        x, e = block(x, w, spec=spec, dtype=dtype, operands=operands)
        if e is not None:
            routed.append(e)
    return x, routed


def logits(ids, w: Mapping, cfg: Mapping, dtype=jnp.float32, **kw):
    """float32 logits [S, vocabulary] of one sequence: the whole
    forward (``w``: embed, layers, norm, head)."""
    x, _ = hidden_states(ids, w["embed"], w["layers"], cfg, dtype, **kw)
    return head_logits(x, w["norm"], w["head"],
                       eps=float(cfg["rms_norm_eps"]), dtype=dtype)
