"""Plain reference of the Mellum decoder family (JetBrains
Mellum2-12B-A2.5B): its TRAINING loss and gradients in straightforward
``jax.numpy`` — no kernel, no sort, no grouped GEMM, dense masks, a
Python loop over the held experts — float32 under
``jax.default_matmul_precision("highest")`` (`highest`), or in another
``dtype`` as the yardstick of a precision's noise.  It imports nothing
from ``paddle_tpu``: the rotary tables (YaRN included) and the
load-balance term are its own copies of the published description.

Equations (``config.json`` of JetBrains/Mellum2-12B-A2.5B-Instruct;
what the file does not say is ``assumed`` in the configuration), rows
x [T, hidden], RMSNorm in float32:

* ``h = RMSNorm(x)``; ``q = h Wq`` [T, n_q, D], ``k = h Wk``,
  ``v = h Wv`` [T, n_kv, D]; rotate-half RoPE over all D dims at the
  absolute position — sliding layers the default table, full layers YaRN
  inverse frequencies, cos and sin times ``attention_factor``;
  ``a = softmax(q k^T / sqrt(D)) v`` with key j visible to query i iff
  ``j <= i`` and, on a sliding layer, ``i - j < window``; ``x += a Wo``.
* ``h2 = RMSNorm(x)``; ``g = softmax_f32(h2 Wr)`` over ALL experts;
  ``e = top_k(g)``; ``w = g[e] / sum g[e]``;
  ``x += sum_j w_j Wd^{e_j} (silu(h2 Wg^{e_j}) * h2 Wu^{e_j})`` over the
  chosen experts that lie in ``held = (first, count)``, ``w`` normalised
  over all k chosen: one chip's addend of the expert-parallel layer.
* ``loss = mean_t CE(RMSNorm(x) W_head, label) + c_aux sum_layers
  L_aux``, ``L_aux = E sum_e P_e F_e``, ``P_e = mean_t g_t[e]``,
  ``F_e = sum_j mean_t [e_tj = e]``.

Memory: attention runs over ``q_block`` queries and one KV head at a
time, the experts ``expert_block`` at a time over all tokens, the head
over ``head_block`` rows at a time (each a divisor of what it cuts, else
the whole), the blocks IN TURN (``lax.map`` / ``lax.scan``), each block
and each layer under its own ``jax.checkpoint``, so that
``value_and_grad`` at 16,384 tokens fits on a chip beside the trainer's
state.
"""

from __future__ import annotations

import contextlib
import math
from typing import Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SLIDING = "sliding_attention"


def highest():
    """Float32 matmuls as float32 (a TPU's default is lower)."""
    return jax.default_matmul_precision("highest")


# ------------------------------------------------------------------ rope
def inv_freq(rp: Mapping, head_dim: int) -> Tuple[np.ndarray, float]:
    """(inverse frequencies [D/2] float64, attention factor) of one
    layer kind's ``rope_parameters`` entry; ``yarn`` as HF's
    ``_compute_yarn_parameters``: interpolated and extrapolated
    frequencies blended by a linear ramp between the correction dims of
    ``beta_fast`` and ``beta_slow`` at the original length."""
    base = float(rp["rope_theta"])
    pos = base ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    if rp.get("rope_type", "default") == "default":
        return 1.0 / pos, 1.0
    if rp["rope_type"] != "yarn":
        raise NotImplementedError(rp["rope_type"])
    factor = float(rp["factor"])
    orig = float(rp["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (head_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(float(rp.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction_dim(float(rp.get("beta_slow", 1)))),
               head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)
    af = rp.get("attention_factor")
    if af is None:
        af = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return inv, float(af)


def rope_tables(rp: Mapping, head_dim: int, n: int):
    """(cos, sin) float32 [n, D/2], the attention factor folded in."""
    inv, af = inv_freq(rp, head_dim)
    f = np.outer(np.arange(n, dtype=np.float64), inv)
    return (jnp.asarray(np.cos(f) * af, jnp.float32),
            jnp.asarray(np.sin(f) * af, jnp.float32))


def rope(x, cos, sin):
    """Rotate-half on x [S, heads, D]."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c, s = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


# ---------------------------------------------------------------- layers
def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    return y.astype(x.dtype) * w.astype(x.dtype)


def attention(h, w, cos, sin, *, nq, nkv, d, window, q_block):
    """One sequence h [S, hidden] -> [S, hidden]: dense masked softmax
    attention, ``q_block`` queries of one KV head's group at a time."""
    S = h.shape[0]
    rep = nq // nkv
    q = rope((h @ w["wq"]).reshape(S, nq, d), cos, sin)
    k = rope((h @ w["wk"]).reshape(S, nkv, d), cos, sin)
    v = (h @ w["wv"]).reshape(S, nkv, d)
    j = jnp.arange(S)[None, :]

    @jax.checkpoint
    def block(qb, kg, vg, i0):
        i = i0 + jnp.arange(qb.shape[0])[:, None]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        s = jnp.einsum("qrd,kd->rqk", qb, kg).astype(jnp.float32) \
            * d ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("rqk,kd->qrd", p.astype(vg.dtype), vg)

    # one (KV head, query block) at a time, IN TURN (`lax.map`: blocks
    # written side by side would all be live at once)
    qb = q_block if q_block and S % q_block == 0 else S
    nb = S // qb
    qr = q.reshape(nb, qb, nkv, rep, d).transpose(2, 0, 1, 3, 4) \
        .reshape(nkv * nb, qb, rep, d)
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    out = jax.lax.map(
        lambda n: block(qr[n], kt[n // nb], vt[n // nb], (n % nb) * qb),
        jnp.arange(nkv * nb))
    a = out.reshape(nkv, nb, qb, rep, d).transpose(1, 2, 0, 3, 4) \
        .reshape(S, nq * d)
    return a @ w["wo"]


def route(h2, wr, top_k: int):
    """(gates [T, E] float32, chosen experts [T, k], their weights
    [T, k] float32 normalised over the k chosen)."""
    g = jax.nn.softmax(h2.astype(jnp.float32) @ wr.astype(jnp.float32), -1)
    topv, topi = jax.lax.top_k(g, top_k)
    return g, topi, topv / jnp.sum(topv, -1, keepdims=True)


def load_balance(g, topi):
    """E x sum_e P_e F_e over all E outputs, F_e over ALL k choices."""
    E = g.shape[-1]
    chosen = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32), 1)
    return E * jnp.sum(jnp.mean(g, 0) * jnp.mean(chosen, 0))


def routed_ffn(h2, w, *, top_k, held, expert_block):
    """h2 [T, hidden] -> (this chip's addend [T, hidden], L_aux): every
    held expert on every token, weighted by the token's weight for it
    (0 where it was not chosen)."""
    first, count = held
    g, topi, wts = route(h2, w["wr"], top_k)
    # [T, count]: token t's weight for held expert first + e
    mine = jnp.sum(jnp.where(
        topi[..., None] == first + jnp.arange(count), wts[..., None], 0.0),
        1)

    @jax.checkpoint
    def block(x, m, wg, wu, wd):
        y = jnp.zeros(x.shape, jnp.float32)
        for e in range(wg.shape[0]):
            out = (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
            y = y + m[:, e, None] * out.astype(jnp.float32)
        return y

    # `expert_block` experts at a time, in turn
    eb = expert_block if expert_block and count % expert_block == 0 \
        else count
    blocks = lambda a: a.reshape((count // eb, eb) + a.shape[1:])  # noqa: E731
    y, _ = jax.lax.scan(
        lambda y, b: (y + block(h2, *b), None),
        jnp.zeros(h2.shape, jnp.float32),
        (blocks(mine.T).transpose(0, 2, 1), blocks(w["wg"]),
         blocks(w["wu"]), blocks(w["wd"])))
    return y.astype(h2.dtype), load_balance(g, topi)


def layer(x, w, cos, sin, *, nq, nkv, d, eps, window, top_k, held,
          q_block, expert_block):
    """x [B, S, hidden] -> (x, L_aux): attention a sequence at a time,
    the routed FFN (and its statistics) over all B x S tokens."""
    h = rms_norm(x, w["ln1"], eps)
    a = jnp.stack([attention(h[b], w, cos, sin, nq=nq, nkv=nkv, d=d,
                             window=window, q_block=q_block)
                   for b in range(x.shape[0])])
    x = x + a
    h2 = rms_norm(x, w["ln2"], eps)
    y, aux = routed_ffn(h2.reshape(-1, h2.shape[-1]), w, top_k=top_k,
                        held=held, expert_block=expert_block)
    return x + y.reshape(x.shape), aux


def head_loss_sum(x, norm_w, head_w, labels, *, eps, head_block):
    """Sum over the tokens of CE(RMSNorm(x) W_head, label); x
    [T, hidden], logits in float32, ``head_block`` rows at a time."""
    hn = rms_norm(x, norm_w, eps)

    @jax.checkpoint
    def block(hb, lb):
        logits = (hb @ head_w).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, -1)
        return (lse - jnp.take_along_axis(logits, lb[:, None], -1)[:, 0]
                ).sum()

    T = x.shape[0]
    hb = head_block if head_block and T % head_block == 0 else T
    return jax.lax.map(lambda b: block(*b),
                       (hn.reshape(T // hb, hb, -1),
                        labels.reshape(T // hb, hb))).sum()


# ------------------------------------------------------------------ loss
def model_kw(c: Mapping, seq: int, check: Optional[Mapping] = None):
    """The static arguments of `loss` from a configuration as run: the
    router covers ``published.num_experts`` outputs, ``experts_held``
    of them are held."""
    check = check or {}
    kinds = tuple(c["layer_types"][:c["num_hidden_layers"]])
    tables = {k: rope_tables(c["rope_parameters"][k], c["head_dim"], seq)
              for k in sorted(set(kinds))}
    return dict(
        kinds=kinds, tables=tables, nq=c["num_attention_heads"],
        nkv=c["num_key_value_heads"], d=c["head_dim"],
        eps=c["rms_norm_eps"], sliding_window=c["sliding_window"],
        top_k=c["num_experts_per_tok"], held=tuple(c["experts_held"]),
        c_aux=c["router_aux_loss_coef"],
        q_block=check.get("q_block"), expert_block=check.get("expert_block"),
        head_block=check.get("head_block"))


def loss(x, layers: Sequence[Dict], norm_w, head_w, labels, *, kinds, tables,
         nq, nkv, d, eps, sliding_window, top_k, held, c_aux, q_block=None,
         expert_block=None, head_block=None, dtype=jnp.float32):
    """(loss, sum of the layers' L_aux) from the EMBEDDED inputs x
    [B, S, hidden] (so that a caller can differentiate at them), labels
    [B, S]; every weight and x cast to ``dtype``."""
    cast = lambda a: a.astype(dtype)  # noqa: E731
    x = cast(x)
    aux = jnp.zeros((), jnp.float32)
    for kind, w in zip(kinds, layers):
        cos, sin = tables[kind]
        step = jax.checkpoint(lambda x_, w_, cos=cos, sin=sin, kind=kind:
                              layer(x_, w_, cos, sin, nq=nq, nkv=nkv, d=d,
                                    eps=eps,
                                    window=sliding_window
                                    if kind == SLIDING else None,
                                    top_k=top_k, held=held, q_block=q_block,
                                    expert_block=expert_block))
        x, a = step(x, {k: cast(v) for k, v in w.items()})
        aux = aux + a
    total = head_loss_sum(x.reshape(-1, x.shape[-1]), cast(norm_w),
                          cast(head_w), labels.reshape(-1), eps=eps,
                          head_block=head_block)
    ce = total / labels.size
    return ce + c_aux * aux, aux


def value_and_grads(embed, layers, norm_w, head_w, ids, labels, *,
                    expert: int, dtype=jnp.float32, **kw):
    """((loss, aux), gradients of the loss at: the embedded inputs
    [B, S, hidden]; layer 0's router weight; held expert ``expert``'s
    down projection in the LAST layer) — `jax.value_and_grad` of `loss`
    at the weights given."""
    def f(x, wr0, wd_last):
        ls = [dict(w) for w in layers]
        ls[0]["wr"] = wr0
        ls[-1]["wd"] = ls[-1]["wd"].at[expert].set(
            wd_last.astype(ls[-1]["wd"].dtype))
        return loss(x, ls, norm_w, head_w, labels, dtype=dtype, **kw)

    x = jnp.take(embed, ids, 0)
    return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
        x, layers[0]["wr"], layers[-1]["wd"][expert])


@contextlib.contextmanager
def precision(dtype):
    """`highest` for float32, nothing for a lower precision's run."""
    with (highest() if dtype == jnp.float32 else contextlib.nullcontext()):
        yield
