"""Plain reference of the Falcon-H1 decoder
(``tiiuae/Falcon-H1-34B-Instruct``, ``falcon_h1``; arXiv:2507.22448).

Straightforward ``jax.numpy``: float32 under ``default_matmul_precision
("highest")``, no cache, no kernels, no batching, the recurrence TOKEN BY
TOKEN (the program runs one step a decode row in place and a chunked
scan over a prefill chunk).  Written from the published ``config.json``
and the family's public description, independent of ``paddle_tpu``.
``d`` hidden, RMSNorm with plain gain (eps ``rms_norm_eps``), no bias
but the convolution's, positions from 0.  A layer:

1. ``x_0 = Emb[tok] * embedding_multiplier``; after the last layer
   ``logits = (RMSNorm(x; g_f) W_head) * lm_head_multiplier``.
2. ``a = RMSNorm(x; g_1)`` — ONE norm for both mixers.
3. State branch (Mamba-2; H heads x P, G groups, state N, kernel K):
   ``[z | x' | B | C | dt] = ((a * ssm_in_multiplier) W_in) * m``
   (widths H P, H P, G N, G N, H; ``m`` constant over each segment,
   ``ssm_multipliers[0..4]`` in that order); ``u = [x' | B | C]``, ``u_t
   <- silu(b_c + sum_{j<K} w_c[:, j] u_{t-K+1+j})``, zeros before
   position 0; ``dt_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``,
   float32; for head h of group g = h // (H / G), state ``S_h`` [P, N],
   ``S_{-1} = 0``: ``S_t = exp(dt_t[h] A[h]) S_{t-1} + dt_t[h] x'_t[h]
   (outer) B_t[g]``; ``y_t[h] = S_t C_t[g] + D[h] x'_t[h]``; ``y <-
   GroupRMSNorm(y silu(z); g_n)`` over G groups (gate FIRST, then the
   norm); ``out_s = (y W_out) * ssm_out_multiplier``.
4. Attention branch: ``a' = a * attention_in_multiplier``; ``q = a'
   Wq``, ``k = (a' Wk) * key_multiplier``, ``v = a' Wv``; rotary on all
   ``head_dim`` dims of q and k, pairs (j, j + head_dim / 2), angles
   ``pos * rope_theta^(-2j / head_dim)``; causal softmax at
   ``head_dim^-1/2``; ``out_a = (o Wo) * attention_out_multiplier``.
5. ``x <- x + out_s + out_a``; ``b = RMSNorm(x; g_2)``; ``x <- x +
   ((silu((b Wg) * mlp_multipliers[0]) (b Wu)) Wd) *
   mlp_multipliers[1]``.

A sliced vocabulary is a smaller one (model-configs guide, section 4).

Departures from the description, for memory only, none of which changes
a number beyond float32 summation order: attention runs ``q_block``
queries at a time; the FFN runs ``ffn_block`` of its columns at a time,
each block's weights cast from the resident arrays, the partial sums
kept in float32 and rounded to the run's type once.

``ablate`` plants one fault: ``state_bf16`` (the recurrent state rounded
to bfloat16 after every token), ``mup_order`` (``m`` with the B and C
multipliers where x' and z's belong: the vector in another column
order), ``rope_pairs`` (interleaved pairs (2j, 2j + 1) instead of
halves), ``no_rope`` (no rotation at all), and ``drop_<name>`` for each
of the fourteen multipliers (that multiplier read as 1): the negative
controls of the tests and of the limits, never the reference.  With
``dtype=bfloat16`` the same code runs in the serving type at the default
precision (the state stays float32, as the configuration states): the
yardstick of the tolerance, not the reference.  ``operands`` rounds the
weights and each layer's input to a lower type first (float8): a reading
that has to come out as not correct.
"""

from __future__ import annotations

import functools
from typing import FrozenSet, Mapping, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MULTIPLIERS = ("embedding", "lm_head", "attention_in", "attention_out",
               "key", "ssm_in", "ssm_out", "ssm_z", "ssm_x", "ssm_B",
               "ssm_C", "ssm_dt", "mlp_gate", "mlp_down")
FAULTS = ("state_bf16", "mup_order", "rope_pairs", "no_rope")
ABLATIONS = FAULTS + tuple("drop_" + m for m in MULTIPLIERS)
LAYER_KEYS = ("norm1", "w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
              "norm_g", "w_out", "wq", "wk", "wv", "wo", "norm2", "wg",
              "wu", "wd")


def highest():
    """The reference's matmul precision: on a TPU a float32 matmul runs
    in lower precision unless this is set."""
    return jax.default_matmul_precision("highest")


def multipliers(cfg: Mapping) -> dict:
    """The fourteen, by name, from the published keys."""
    m, g = cfg["ssm_multipliers"], cfg["mlp_multipliers"]
    return dict(zip(MULTIPLIERS, (float(v) for v in (
        cfg["embedding_multiplier"], cfg["lm_head_multiplier"],
        cfg["attention_in_multiplier"], cfg["attention_out_multiplier"],
        cfg["key_multiplier"], cfg["ssm_in_multiplier"],
        cfg["ssm_out_multiplier"], *m, *g))))


class Spec(NamedTuple):
    eps: float
    heads: int
    kv_heads: int
    head_dim: int
    theta: float
    m_heads: int
    m_dim: int
    groups: int
    state: int
    kernel: int
    mults: Tuple[Tuple[str, float], ...]
    q_block: int = 0
    ffn_block: int = 0
    ablate: FrozenSet[str] = frozenset()


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y.astype(x.dtype) * w.astype(x.dtype)


def _f32_dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _mults(s: Spec) -> dict:
    mu = dict(s.mults)
    for name in MULTIPLIERS:
        if "drop_" + name in s.ablate:
            mu[name] = 1.0
    return mu


# ------------------------------------------------------------- Mamba-2
def _mamba(a, w, s: Spec, dtype, keep_state: bool = False):
    S = a.shape[0]
    Hm, P, G, N, K = s.m_heads, s.m_dim, s.groups, s.state, s.kernel
    f32 = jnp.float32
    mu = _mults(s)
    d = Hm * P
    order = ("ssm_B", "ssm_C", "ssm_z", "ssm_x", "ssm_dt") \
        if "mup_order" in s.ablate \
        else ("ssm_z", "ssm_x", "ssm_B", "ssm_C", "ssm_dt")
    m = np.concatenate([np.full(wd, mu[k], np.float32) for wd, k in zip(
        (d, d, G * N, G * N, Hm), order)])
    zxbcdt = ((a * mu["ssm_in"]) @ w["w_in"].astype(dtype)) \
        * jnp.asarray(m, dtype)
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

    last, y = jax.lax.scan(token, jnp.zeros((Hm, P, N), f32),
                           (x, dt, bm, cm))
    y = y + w["D"].astype(f32)[None, :, None] * x
    y = (y.reshape(S, d) * jax.nn.silu(z.astype(f32))).reshape(S, G, d // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + s.eps)
    y = y.reshape(S, d) * w["norm_g"].astype(f32)
    out = _f32_dot(y.astype(dtype), w["w_out"].astype(dtype)) \
        * mu["ssm_out"]
    return (out, last) if keep_state else out


# ----------------------------------------------------------- attention
def _rotate(t, s: Spec):
    """Rotary on t [S, h, D], positions 0 .. S - 1, float32 angles."""
    if "no_rope" in s.ablate:
        return t
    S, _, D = t.shape
    inv = 1.0 / s.theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    f = np.outer(np.arange(S, dtype=np.float64), inv)
    cos = jnp.asarray(np.cos(f), jnp.float32)[:, None].astype(t.dtype)
    sin = jnp.asarray(np.sin(f), jnp.float32)[:, None].astype(t.dtype)
    if "rope_pairs" in s.ablate:
        t1, t2 = t[..., 0::2], t[..., 1::2]
        return jnp.stack([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                         -1).reshape(t.shape)
    t1, t2 = t[..., :D // 2], t[..., D // 2:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


def _attention(a, w, s: Spec, dtype):
    S = a.shape[0]
    Hq, KV, D = s.heads, s.kv_heads, s.head_dim
    mu = _mults(s)
    a = a * mu["attention_in"]
    q = _rotate((a @ w["wq"].astype(dtype)).reshape(S, Hq, D), s)
    k = _rotate(((a @ w["wk"].astype(dtype)) * mu["key"])
                .reshape(S, KV, D), s)
    v = (a @ w["wv"].astype(dtype)).reshape(S, KV, D)
    q = q.reshape(S, KV, Hq // KV, D)
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
    return _f32_dot(o, w["wo"].astype(dtype)) * mu["attention_out"]


# ----------------------------------------------------------------- FFN
def _ffn(b, w, s: Spec, dtype):
    mu = _mults(s)
    width = w["wg"].shape[1]
    blk = min(s.ffn_block or width, width)
    while width % blk:
        blk -= 1

    def some(acc, c0):
        wg = jax.lax.dynamic_slice_in_dim(w["wg"], c0, blk, 1).astype(dtype)
        wu = jax.lax.dynamic_slice_in_dim(w["wu"], c0, blk, 1).astype(dtype)
        wd = jax.lax.dynamic_slice_in_dim(w["wd"], c0, blk, 0).astype(dtype)
        h = jax.nn.silu((b @ wg) * mu["mlp_gate"]) * (b @ wu)
        return acc + _f32_dot(h, wd), None

    acc, _ = jax.lax.scan(some, jnp.zeros(b.shape, jnp.float32),
                          jnp.arange(0, width, blk))
    return acc * mu["mlp_down"]


# --------------------------------------------------------------- layer
@functools.partial(jax.jit, static_argnames=("spec", "dtype", "operands",
                                             "keep_state"))
def layer(x, w, *, spec: Spec, dtype, operands=None, keep_state=False):
    """One layer over x [S, hidden] (one sequence) -> x, or (x, the
    state branch's state [H, P, N] after the last position)."""
    if operands is not None:
        w = {k: v.astype(operands).astype(v.dtype) for k, v in w.items()}
        x = x.astype(operands).astype(dtype)
    a = _rms(x, w["norm1"], spec.eps)
    out_s = _mamba(a, w, spec, dtype, keep_state)
    last = None
    if keep_state:
        out_s, last = out_s
    x = x + out_s.astype(dtype) + _attention(a, w, spec, dtype).astype(dtype)
    b = _rms(x, w["norm2"], spec.eps)
    x = x + _ffn(b, w, spec, dtype).astype(dtype)
    return (x, last) if keep_state else x


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "mult"))
def head_logits(x, norm_w, head_w, *, eps, dtype, mult=1.0):
    return ((_rms(x, norm_w.astype(dtype), eps) @ head_w.astype(dtype))
            * mult).astype(jnp.float32)


def spec(cfg: Mapping, ablate: FrozenSet[str] = frozenset(),
         q_block: int = 0, ffn_block: int = 0) -> Spec:
    """The layers' one Spec from the configuration's published keys."""
    unknown = set(ablate) - set(ABLATIONS)
    if unknown:
        raise ValueError(f"unknown ablation {sorted(unknown)}")
    return Spec(
        eps=float(cfg["rms_norm_eps"]), heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        theta=float(cfg["rope_theta"]), m_heads=cfg["mamba_n_heads"],
        m_dim=cfg["mamba_d_head"], groups=cfg["mamba_n_groups"],
        state=cfg["mamba_d_state"], kernel=cfg["mamba_d_conv"],
        mults=tuple(sorted(multipliers(cfg).items())), q_block=q_block,
        ffn_block=ffn_block, ablate=frozenset(ablate))


def hidden_states(ids, embed, layers, cfg: Mapping, dtype,
                  ablate: FrozenSet[str] = frozenset(), operands=None,
                  q_block: int = 0, ffn_block: int = 0,
                  state_of: int = -1):
    """ids [S] -> (x [S, hidden] before the last norm, the state [H, P,
    N] layer ``state_of`` holds after the last position, or None)."""
    sp = spec(cfg, ablate, q_block, ffn_block)
    x = embed[ids].astype(dtype) * _mults(sp)["embedding"]
    state = None
    for i, w in enumerate(layers):
        if i == state_of:
            x, state = layer(x, w, spec=sp, dtype=dtype, operands=operands,
                             keep_state=True)
        else:
            x = layer(x, w, spec=sp, dtype=dtype, operands=operands)
    return x, state


def logits(ids, w: Mapping, cfg: Mapping, dtype=jnp.float32, **kw):
    """float32 logits [S, vocabulary] of one sequence: the whole
    forward (``w``: embed, layers, norm, head)."""
    sp = spec(cfg, kw.get("ablate", frozenset()))
    x, _ = hidden_states(ids, w["embed"], w["layers"], cfg, dtype, **kw)
    return head_logits(x, w["norm"], w["head"],
                       eps=float(cfg["rms_norm_eps"]), dtype=dtype,
                       mult=_mults(sp)["lm_head"])
