"""Plain reference of the Phi-4-mini-flash decoder
(``microsoft/Phi-4-mini-flash-reasoning``, ``phi4flash``; SambaY,
arXiv:2507.06607; Differential Attention, arXiv:2410.05258; Mamba-1,
arXiv:2312.00752).

Straightforward ``jax.numpy``: float32 under ``default_matmul_precision
("highest")``, no cache, no kernels, no batching, the recurrence TOKEN BY
TOKEN (``lax.scan`` over rows; the program runs one step a decode row in
place and a selective-scan kernel over a prefill chunk), the two
softmaxes of a differential head computed SEPARATELY on unpadded heads
(the program stores a pair of heads as one and pads the queries).
Written from the published ``config.json`` and the family's public
description, independent of ``paddle_tpu``.  ``d`` hidden, LayerNorm with
weight and bias (eps ``layer_norm_eps``), NO positional encoding, the
head tied to the embedding, ``n`` layers, ``h = n / 2``.  Layer ``l``:

1. ``a = LN(x; g, b)``; ``x <- x + mixer_l(a)``; ``x <- x + (silu(b'
   Wg) (b' Wu)) Wd`` with ``b' = LN'(x)``.  ``logits = LN_f(x) E^T``.
2. ``l`` even, ``l <= h``: Mamba-1.  ``[x | z] = a W_in``; ``x =
   silu(conv_K(x) + b_c)`` (depthwise, causal, zeros before position 0);
   ``[r | B | C] = x W_x``; ``dt = softplus(r W_dt + b_dt)``; ``A =
   -exp(A_log)`` [C, N]; ``h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c,
   n] + dt_t[c] x_t[c] B_t[n]``, ``h_{-1} = 0``, float32; ``y_t[c] =
   sum_n h_t[c, n] C_t[n] + D[c] x_t[c]``; ``out = (y silu(z)) W_out``.
   Layer ``h`` hands on ``m_t = y_t`` (with ``D``, before the gate).
3. ``l`` odd, ``l < h``: differential attention over the last
   ``sliding_window`` keys; ``l = h + 1``: over all keys, and its k, v
   are the cross-decoder's.  ``q = a Wq + bq`` [H x D], ``k, v = a Wk +
   bk, a Wv + bv`` [KV x D].  Differential head ``i`` of H / 2, pair ``j
   = i // 2``: ``a1 = softmax(q_2i K_2j^T D^-1/2) [V_2j | V_2j+1]``,
   ``a2 = softmax(q_2i+1 K_2j+1^T D^-1/2) [V_2j | V_2j+1]`` (causal);
   ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)``,
   ``lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)``; ``o_i = RMSNorm_2D(a1 -
   lambda a2; g_s) (1 - lambda_init(l))``; ``out = [o_0 ..] Wo + bo``.
4. ``l`` even, ``l > h``: gated memory unit, ``out = (silu(a W_a) m_t)
   W_b``.  ``l`` odd, ``l > h + 1``: cross attention — item 3 with
   ``Wq`` only, over layer ``h + 1``'s k, v.

Departures from the description, for memory only, none of which changes
a number beyond float32 summation order: attention runs ``q_block``
queries at a time; the FFN runs ``ffn_block`` of its columns at a time,
each block's weights cast from the resident arrays, the partial sums
kept in float32 and rounded to the run's type once; the head runs in
column blocks of the vocabulary.

``ablate`` plants one fault (`ABLATIONS`): ``state_bf16`` (the recurrent
state rounded to bfloat16 after every token), ``m_after_gate`` (the
memory taken after the gate), ``m_no_D`` (without the ``D`` term),
``lambda_layer`` (``lambda_init`` of the NEXT layer's index),
``pair_far`` (query heads (i, i + H / 2) and KV heads (j, j + KV / 2)
paired instead of adjacent ones), ``window_minus`` / ``window_plus`` (a
window of 511 / 513), ``cross_stale`` (a cross layer that does not see
its own row's key and value: the pages as they were before the append of
the launch's rows): the negative controls of the tests and of the
limits, never the reference.  With ``dtype=bfloat16`` the same code runs
in the serving type at the default precision (the state stays float32,
as the configuration states): the yardstick of the tolerance.
``operands`` rounds the weights and each layer's input to a lower type
first (float8): a reading that has to come out as not correct.
"""

from __future__ import annotations

import functools
import math
from typing import FrozenSet, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

ABLATIONS = ("state_bf16", "m_after_gate", "m_no_D", "lambda_layer",
             "pair_far", "window_minus", "window_plus", "cross_stale")


def highest():
    """The reference's matmul precision: on a TPU a float32 matmul runs
    in lower precision unless this is set."""
    return jax.default_matmul_precision("highest")


def layer_kinds(n: int) -> str:
    """``S`` Mamba-1, ``W`` window attention, ``F`` full attention, ``G``
    gated memory unit, ``X`` cross attention, by the layer's index."""
    h = n // 2
    return "".join(
        ("S" if l <= h else "G") if l % 2 == 0 else
        ("W" if l < h else "F" if l == h + 1 else "X") for l in range(n))


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


class Spec(NamedTuple):
    eps: float
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    d_inner: int
    state: int
    kernel: int
    dt_rank: int
    q_block: int = 0
    ffn_block: int = 0
    ablate: FrozenSet[str] = frozenset()


def spec(cfg: Mapping, ablate: FrozenSet[str] = frozenset(),
         q_block: int = 0, ffn_block: int = 0) -> Spec:
    """The layers' one Spec from the configuration's keys (the published
    ones and the four Mamba-1 constants it states as assumed)."""
    unknown = set(ablate) - set(ABLATIONS)
    if unknown:
        raise ValueError(f"unknown ablation {sorted(unknown)}")
    d = cfg["hidden_size"]
    rank = cfg.get("mamba_dt_rank", "auto")
    return Spec(
        eps=float(cfg["layer_norm_eps"]), heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        head_dim=d // cfg["num_attention_heads"],
        window=cfg["sliding_window"],
        d_inner=cfg.get("mamba_expand", 2) * d,
        state=cfg.get("mamba_d_state", 16),
        kernel=cfg.get("mamba_d_conv", 4),
        dt_rank=-(-d // 16) if rank == "auto" else int(rank),
        q_block=q_block, ffn_block=ffn_block, ablate=frozenset(ablate))


def _ln(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _f32_dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


# ------------------------------------------------------------- Mamba-1
def _mamba(a, w, s: Spec, dtype):
    """-> (out [S, d], the memory m [S, C] float32, the last state [C,
    N])."""
    S = a.shape[0]
    C, N, K, R = s.d_inner, s.state, s.kernel, s.dt_rank
    f32 = jnp.float32
    xz = a @ w["w_in"].astype(dtype)
    u, z = xz[:, :C], xz[:, C:]
    up = jnp.concatenate([jnp.zeros((K - 1, C), u.dtype), u])
    acc = w["conv_b"].astype(f32)[None]
    for j in range(K):
        acc = acc + w["conv_w"][:, j].astype(f32)[None] \
            * up[j:j + S].astype(f32)
    u = jax.nn.silu(acc).astype(dtype)
    rbc = u @ w["w_x"].astype(dtype)
    dt = jax.nn.softplus(
        (rbc[:, :R] @ w["w_dt"].astype(dtype)).astype(f32)
        + w["dt_bias"].astype(f32))
    bm, cm = rbc[:, R:R + N].astype(f32), rbc[:, R + N:].astype(f32)
    A = -jnp.exp(w["A_log"].astype(f32))                    # [C, N]
    x = u.astype(f32)

    def token(h, row):
        xt, dtt, bt, ct = row                   # [C], [C], [N], [N]
        h = jnp.exp(dtt[:, None] * A) * h \
            + (dtt * xt)[:, None] * bt[None, :]
        if "state_bf16" in s.ablate:
            # (not a pair of casts: the TPU compiler keeps the excess
            # precision of float32 -> bfloat16 -> float32)
            h = jax.lax.reduce_precision(h, exponent_bits=8,
                                         mantissa_bits=7)
        return h, jnp.sum(h * ct[None, :], -1)

    last, y = jax.lax.scan(token, jnp.zeros((C, N), f32), (x, dt, bm, cm))
    D = w["D"].astype(f32)[None]
    gate = jax.nn.silu(z.astype(f32))
    m = y if "m_no_D" in s.ablate else y + D * x
    y = y + D * x
    if "m_after_gate" in s.ablate:
        m = m * gate
    return _f32_dot((y * gate).astype(dtype), w["w_out"].astype(dtype)), \
        m, last


# ----------------------------------------------------------- attention
def _attention(a, w, s: Spec, dtype, lam_init, window, kv=None,
               stale: bool = False):
    """-> (out [S, d], (k, v) [S, KV, D])."""
    S = a.shape[0]
    H, KV, D = s.heads, s.kv_heads, s.head_dim
    f32 = jnp.float32
    q = (a @ w["wq"].astype(dtype) + w["bq"].astype(dtype)).reshape(S, H, D)
    if kv is None:
        kv = ((a @ w["wk"].astype(dtype) + w["bk"].astype(dtype))
              .reshape(S, KV, D),
              (a @ w["wv"].astype(dtype) + w["bv"].astype(dtype))
              .reshape(S, KV, D))
    k, v = kv
    nd = H // 2                                 # differential heads
    i = np.arange(nd)
    if "pair_far" in s.ablate:
        q1, q2 = i, i + nd
        j = i // 2
        k1, k2 = j, j + KV // 2
    else:
        q1, q2 = 2 * i, 2 * i + 1
        k1, k2 = 2 * (i // 2), 2 * (i // 2) + 1
    vp = jnp.concatenate([v[:, k1], v[:, k2]], -1)          # [S, nd, 2D]
    lam = (jnp.exp(jnp.sum(w["lq1"].astype(f32) * w["lk1"].astype(f32)))
           - jnp.exp(jnp.sum(w["lq2"].astype(f32) * w["lk2"].astype(f32)))
           + lam_init)
    qb = min(s.q_block or S, S)
    nb = -(-S // qb)
    pad = ((0, nb * qb - S), (0, 0), (0, 0))
    qa, qc = jnp.pad(q[:, q1], pad), jnp.pad(q[:, q2], pad)
    ka, kc = k[:, k1], k[:, k2]
    ii, jj = jnp.arange(qb)[:, None], jnp.arange(S)[None, :]

    def soft(qs, ks, seen):
        sc = jnp.einsum("qhd,khd->hqk", qs, ks).astype(f32) * D ** -0.5
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
        return jnp.einsum("hqk,khw->qhw", p.astype(v.dtype), vp).astype(f32)

    def block(b):
        q0 = b * qb
        pos = q0 + ii
        seen = (jj < pos) | ((jj == pos) & (pos == 0)) if stale \
            else jj <= pos
        if window is not None:
            seen &= jj > pos - window
        a1 = soft(jax.lax.dynamic_slice_in_dim(qa, q0, qb, 0), ka, seen)
        a2 = soft(jax.lax.dynamic_slice_in_dim(qc, q0, qb, 0), kc, seen)
        o = a1 - lam * a2
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + s.eps)
        return o * w["subln"].astype(f32) * (1.0 - lam_init)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(nb * qb, nd * 2 * D)[:S]
    return _f32_dot(o.astype(dtype), w["wo"].astype(dtype)) \
        + w["bo"].astype(f32), kv


# ----------------------------------------------------------------- FFN
def _ffn(b, w, s: Spec, dtype):
    width = w["wg"].shape[1]
    blk = min(s.ffn_block or width, width)
    while width % blk:
        blk -= 1

    def some(acc, c0):
        wg = jax.lax.dynamic_slice_in_dim(w["wg"], c0, blk, 1).astype(dtype)
        wu = jax.lax.dynamic_slice_in_dim(w["wu"], c0, blk, 1).astype(dtype)
        wd = jax.lax.dynamic_slice_in_dim(w["wd"], c0, blk, 0).astype(dtype)
        return acc + _f32_dot(jax.nn.silu(b @ wg) * (b @ wu), wd), None

    acc, _ = jax.lax.scan(some, jnp.zeros(b.shape, jnp.float32),
                          jnp.arange(0, width, blk))
    return acc


# --------------------------------------------------------------- layer
@functools.partial(jax.jit, static_argnames=("kind", "spec", "dtype",
                                             "operands"))
def layer(x, w, lam_init, m, kv, *, kind: str, spec: Spec, dtype,
          operands=None):
    """One layer of kind ``kind`` over x [S, hidden] (one sequence) ->
    (x, the memory it makes or None, the (k, v) it makes or None, the
    state it leaves or None)."""
    s = spec
    if operands is not None:
        w = {k: v.astype(operands).astype(v.dtype) for k, v in w.items()}
        x = x.astype(operands).astype(dtype)
    a = _ln(x, w["ln1"], w["ln1_b"], s.eps)
    mem = own = last = None
    if kind == "S":
        y, mem, last = _mamba(a, w, s, dtype)
    elif kind == "G":
        y = _f32_dot((jax.nn.silu((a @ w["w_a"].astype(dtype))
                                  .astype(jnp.float32)) * m).astype(dtype),
                     w["w_b"].astype(dtype))
    elif kind == "X":
        y, _ = _attention(a, w, s, dtype, lam_init, None, kv,
                          stale="cross_stale" in s.ablate)
    else:
        win = None
        if kind == "W":
            win = s.window - ("window_minus" in s.ablate) \
                + ("window_plus" in s.ablate)
        y, own = _attention(a, w, s, dtype, lam_init, win)
    x = x + y.astype(dtype)
    b = _ln(x, w["ln2"], w["ln2_b"], s.eps)
    x = x + _ffn(b, w, s, dtype).astype(dtype)
    return x, mem, own, last


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "block"))
def head_logits(x, norm_w, norm_b, embed, *, eps, dtype, block: int = 0):
    """``LN_f(x) E^T`` in float32, ``block`` rows of the vocabulary at a
    time (each cast from the resident embedding)."""
    h = _ln(x, norm_w.astype(dtype), norm_b.astype(dtype), eps)
    V = embed.shape[0]
    blk = min(block or V, V)
    while V % blk:
        blk -= 1

    def some(r0):
        e = jax.lax.dynamic_slice_in_dim(embed, r0, blk, 0).astype(dtype)
        return (h @ e.T).astype(jnp.float32)

    out = jax.lax.map(some, jnp.arange(0, V, blk))      # [V / blk, S, blk]
    return out.transpose(1, 0, 2).reshape(x.shape[0], V)


def hidden_states(ids, embed, layers, cfg: Mapping, dtype,
                  ablate: FrozenSet[str] = frozenset(), operands=None,
                  q_block: int = 0, ffn_block: int = 0,
                  state_of: int = -1):
    """ids [S] -> (x [S, hidden] before the last norm, the state [C, N]
    layer ``state_of`` holds after the last position, or None)."""
    sp = spec(cfg, ablate, q_block, ffn_block)
    n = len(layers)
    kinds = layer_kinds(n)
    x = embed[ids].astype(dtype)
    m = kv = state = None
    for l, (kind, w) in enumerate(zip(kinds, layers)):
        li = l + 1 if "lambda_layer" in sp.ablate else l
        x, mem, own, last = layer(
            x, w, jnp.float32(lambda_init(li)),
            m if kind == "G" else None, kv if kind == "X" else None,
            kind=kind, spec=sp, dtype=dtype, operands=operands)
        if l == n // 2:
            m = mem
        if l == n // 2 + 1:
            kv = own
        if l == state_of:
            state = last
    return x, state


def logits(ids, w: Mapping, cfg: Mapping, dtype=jnp.float32, **kw):
    """float32 logits [S, vocabulary] of one sequence: the whole
    forward (``w``: embed, layers, norm, norm_b)."""
    x, _ = hidden_states(ids, w["embed"], w["layers"], cfg, dtype, **kw)
    return head_logits(x, w["norm"], w["norm_b"], w["embed"],
                       eps=float(cfg["layer_norm_eps"]), dtype=dtype)
