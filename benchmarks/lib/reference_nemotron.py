"""Plain reference of the Nemotron-H hybrid decoder
(``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``, ``nemotron_h``).

Straightforward ``jax.numpy``: float32 under ``default_matmul_precision
("highest")``, no cache, no kernels, no batching, the recurrence TOKEN BY
TOKEN (the program runs one step a decode row in place and a chunked
scan over a prefill chunk).  Written from the published ``config.json``
and the family's forms (Mamba-2 / SSD, arXiv:2405.21060; a sigmoid
router with a correction bias; experts in a latent), independent of
``paddle_tpu``.  ``H`` hidden, RMSNorm with plain gain (eps
``layer_norm_epsilon``), no bias but the convolution's, positions from
0.  Block ``l`` is ``x <- x + mixer_l(RMSNorm(x; g_l))`` with ONE mixer,
``hybrid_override_pattern``'s letter:

1. ``x_0 = Emb[tok]``; after the last block ``logits = RMSNorm(x; g_f)
   W_head``.
2. ``M`` (Mamba-2; H_m heads x P, G groups, state N, kernel K): ``[z | u
   | dt] = a W_in`` (widths H_m P, H_m P + 2 G N, H_m); ``u = [x' | B |
   C]``; ``u_t <- silu(b_c + sum_{j<K} w_c[:, j] u_{t-K+1+j})``, zeros
   before position 0; ``dt_t = softplus(dt_t + dt_bias)``, ``A =
   -exp(A_log)``, float32; for head h of group g = h // (H_m / G), state
   ``S_h`` [P, N], ``S_{-1} = 0``: ``S_t = exp(dt_t[h] A[h]) S_{t-1} +
   dt_t[h] x'_t[h] (outer) B_t[g]``; ``y_t[h] = S_t C_t[g] + D[h]
   x'_t[h]``; ``y <- GroupRMSNorm(y silu(z); g_n)`` over G groups (gate
   FIRST, then the norm); out ``y W_out``.
3. ``*``: ``q, k, v = a Wq, a Wk, a Wv``; causal softmax at scale
   ``head_dim^-1/2``; NO rotary embedding; out ``o Wo``.
4. ``E``: ``s = sigmoid(a_f32 W_r)`` over all experts; the top-k of ``s +
   b`` (the correction bias picks, it does not weigh); ``w_e =
   routed_scaling_factor x s_e / (sum of the chosen s + 1e-20)``; ``c = a
   W_dn``; ``f_e(c) = relu(c U_e)^2 V_e``; ``y = (sum_e w_e f_e(c)) W_up +
   relu(a U_s)^2 V_s`` (the shared expert reads the full width).

The share of an expert-parallel deployment (model-configs guide,
section 4): ``held = (first, count)`` names the experts whose weights
are given; routing is over all of them, and what the absent experts
would have added is left out.  A sliced vocabulary is a smaller one.

Departures from the description, for memory only, none of which changes
a number beyond float32 summation order: the experts run
``expert_block`` at a time, attention ``q_block`` queries at a time;
each partial sum is kept in float32 and rounded to the run's type once.
The program divides the chosen scores by ``max(sum, 1e-9)`` where this
adds 1e-20: 22 sigmoids sum far above either.

``ablate`` plants one fault ("state_bf16": the recurrent state rounded
to bfloat16 after every token; "correction_bias": dropped from the
choice; "gate_order": the norm first, then the gate; "d_term": no ``D
x'``): the negative controls of the tests and of the limits, never the
reference.  With ``dtype=bfloat16`` the same code runs in the serving
type at the default precision (the state stays float32, as the
configuration states): the yardstick of the tolerance, not the
reference.  ``operands`` rounds the weights and each block's input to a
lower type first (float8): a reading that has to come out as not
correct.
"""

from __future__ import annotations

import functools
from typing import FrozenSet, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

ABLATIONS = ("state_bf16", "correction_bias", "gate_order", "d_term")
MAMBA_KEYS = ("norm", "w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
              "norm_g", "w_out")
ATTN_KEYS = ("norm", "wq", "wk", "wv", "wo")
MOE_KEYS = ("norm", "router", "bias", "w_dn", "w_up", "eu", "ed", "su", "sd")


def highest():
    """The reference's matmul precision: on a TPU a float32 matmul runs
    in lower precision unless this is set."""
    return jax.default_matmul_precision("highest")


class Spec(NamedTuple):
    kind: str
    eps: float
    heads: int = 0                  # attention
    kv_heads: int = 0
    head_dim: int = 0
    q_block: int = 0
    m_heads: int = 0                # Mamba-2
    m_dim: int = 0
    groups: int = 0
    state: int = 0
    kernel: int = 0
    top_k: int = 0                  # routed FFN
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


# ------------------------------------------------------------- Mamba-2
def _mamba(a, w, s: Spec, dtype):
    S = a.shape[0]
    Hm, P, G, N, K = s.m_heads, s.m_dim, s.groups, s.state, s.kernel
    f32 = jnp.float32
    d = Hm * P
    zxbcdt = a @ w["w_in"].astype(dtype)
    z, u, dt = zxbcdt[:, :d], zxbcdt[:, d:d + d + 2 * G * N], \
        zxbcdt[:, 2 * d + 2 * G * N:]
    # the depthwise causal convolution: zeros before position 0
    up = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    acc = w["conv_b"].astype(f32)[None]
    for j in range(K):
        acc = acc + w["conv_w"][:, j].astype(f32)[None] \
            * up[j:j + S].astype(f32)
    u = jax.nn.silu(acc).astype(dtype)
    x = u[:, :d].reshape(S, Hm, P).astype(f32)
    bm = u[:, d:d + G * N].reshape(S, G, N).astype(f32)
    cm = u[:, d + G * N:].reshape(S, G, N).astype(f32)
    dt = jax.nn.softplus(dt.astype(f32) + w["dt_bias"].astype(f32))
    A = -jnp.exp(w["A_log"].astype(f32))
    rep = Hm // G

    def token(state, row):
        xt, dtt, bt, ct = row
        bh, ch = jnp.repeat(bt, rep, 0), jnp.repeat(ct, rep, 0)  # [Hm, N]
        state = jnp.exp(dtt * A)[:, None, None] * state \
            + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :]
        if "state_bf16" in s.ablate:
            # (not a pair of casts: the TPU compiler keeps the excess
            # precision of float32 -> bfloat16 -> float32 and the fault
            # would not be planted)
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, jnp.sum(state * ch[:, None, :], -1)       # [Hm, P]

    _, y = jax.lax.scan(token, jnp.zeros((Hm, P, N), f32), (x, dt, bm, cm))
    if "d_term" not in s.ablate:
        y = y + w["D"].astype(f32)[None, :, None] * x
    y, zf = y.reshape(S, d), z.astype(f32)

    def norm(v):
        v = v.reshape(S, G, d // G)
        v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + s.eps)
        return v.reshape(S, d)

    g = w["norm_g"].astype(f32)
    if "gate_order" in s.ablate:
        y = norm(y) * g * jax.nn.silu(zf)
    else:
        y = norm(y * jax.nn.silu(zf)) * g
    return _f32_dot(y.astype(dtype), w["w_out"].astype(dtype))


# ----------------------------------------------------------- attention
def _attention(a, w, s: Spec, dtype):
    S = a.shape[0]
    Hq, KV, D = s.heads, s.kv_heads, s.head_dim
    q = (a @ w["wq"].astype(dtype)).reshape(S, KV, Hq // KV, D)
    k = (a @ w["wk"].astype(dtype)).reshape(S, KV, D)
    v = (a @ w["wv"].astype(dtype)).reshape(S, KV, D)
    qb = min(s.q_block or S, S)
    nb = -(-S // qb)
    qp = jnp.pad(q, ((0, nb * qb - S), (0, 0), (0, 0), (0, 0)))
    i, j = jnp.arange(qb)[:, None], jnp.arange(S)[None, :]

    def block(b):
        q0 = b * qb
        sc = jnp.einsum("qgrd,kgd->grqk",
                        jax.lax.dynamic_slice_in_dim(qp, q0, qb, 0),
                        k).astype(jnp.float32) * D ** -0.5
        sc = jnp.where(j <= q0 + i, sc, -jnp.inf)
        p = jax.nn.softmax(sc, -1).astype(v.dtype)
        return jnp.einsum("grqk,kgd->qgrd", p, v)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(nb * qb, Hq * D)[:S]
    return _f32_dot(o, w["wo"].astype(dtype))


# ---------------------------------------------------- latent routed FFN
def routing(a, router, bias, s: Spec):
    """(weights [S, k], experts [S, k]) over ALL of the router's
    outputs, in float32 whatever the block's type."""
    sc = jax.nn.sigmoid(a.astype(jnp.float32) @ router.astype(jnp.float32))
    pick = sc if "correction_bias" in s.ablate \
        else sc + bias.astype(jnp.float32)
    _, e = jax.lax.top_k(pick, s.top_k)
    wts = jnp.take_along_axis(sc, e, -1)
    if s.renorm:
        wts = wts / (jnp.sum(wts, -1, keepdims=True) + 1e-20)
    return wts * s.scale, e


def _relu2(h, wu, wd):
    return jnp.square(jax.nn.relu(h @ wu)) @ wd


def _moe(a, w, s: Spec, dtype):
    wts, ids = routing(a, w["router"], w["bias"], s)
    first = s.held[0] if s.held else 0
    c = a @ w["w_dn"].astype(dtype)                     # the latent row
    n = w["eu"].shape[0]
    blk = max(1, min(s.expert_block, n))
    while n % blk:
        blk -= 1

    def some(acc, ew):
        e0, (eu, ed) = ew
        eu, ed = eu.astype(dtype), ed.astype(dtype)
        e = first + e0 + jnp.arange(blk)
        mine = jnp.sum(jnp.where(ids[None] == e[:, None, None],
                                 wts[None], 0.0), -1)        # [blk, S]
        f = jnp.einsum("esi,eiz->esz", jnp.square(jax.nn.relu(
            jnp.einsum("sz,ezi->esi", c, eu))), ed)
        # an expert's output in the run's type, weighed in float32
        return acc + jnp.einsum("es,esz->sz", mine, f.astype(jnp.float32)), \
            None

    acc, _ = jax.lax.scan(
        some, jnp.zeros(c.shape, jnp.float32),
        (jnp.arange(0, n, blk),
         (w["eu"].reshape((n // blk, blk) + w["eu"].shape[1:]),
          w["ed"].reshape((n // blk, blk) + w["ed"].shape[1:]))))
    y = _f32_dot(acc.astype(dtype), w["w_up"].astype(dtype))
    y = y + _relu2(a, w["su"].astype(dtype), w["sd"].astype(dtype))
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
    if spec.kind == "M":
        return x + _mamba(a, w, spec, dtype).astype(dtype), None
    if spec.kind == "*":
        return x + _attention(a, w, spec, dtype).astype(dtype), None
    y, ids = _moe(a, w, spec, dtype)
    return x + y.astype(dtype), ids


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def head_logits(x, norm_w, head_w, *, eps, dtype):
    return (_rms(x, norm_w.astype(dtype), eps)
            @ head_w.astype(dtype)).astype(jnp.float32)


def specs(cfg: Mapping, ablate: FrozenSet[str] = frozenset(),
          q_block: int = 0, expert_block: int = 1):
    """One Spec a block from the configuration's published keys
    (``n_routed_experts`` is the ROUTER's width; ``experts_held`` the
    share)."""
    unknown = set(ablate) - set(ABLATIONS)
    if unknown:
        raise ValueError(f"unknown ablation {sorted(unknown)}")
    held = cfg.get("experts_held")
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    common = dict(eps=float(cfg["layer_norm_epsilon"]),
                  ablate=frozenset(ablate))
    kinds = {
        "M": dict(m_heads=cfg["mamba_num_heads"],
                  m_dim=cfg["mamba_head_dim"], groups=cfg["n_groups"],
                  state=cfg["ssm_state_size"], kernel=cfg["conv_kernel"]),
        "*": dict(heads=cfg["num_attention_heads"],
                  kv_heads=cfg["num_key_value_heads"],
                  head_dim=cfg["head_dim"], q_block=q_block),
        "E": dict(top_k=cfg["num_experts_per_tok"],
                  renorm=bool(cfg["norm_topk_prob"]),
                  scale=float(cfg["routed_scaling_factor"]),
                  held=tuple(held) if held else None,
                  expert_block=expert_block)}
    return [Spec(kind=k, **common, **kinds[k]) for k in pattern]


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
                       eps=float(cfg["layer_norm_epsilon"]), dtype=dtype)
