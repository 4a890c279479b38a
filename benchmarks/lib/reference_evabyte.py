"""Plain reference of the EvaByte decoder (``EvaByte/EvaByte``): every
layer chunk-summary (EVA) attention, Zheng et al., arXiv:2302.04542, in
the form the release's ``config.json`` names (``attention_class`` eva,
``chunk_size``, ``window_size``, ``num_pred_heads``).

Straightforward ``jax.numpy``: float32 under ``default_matmul_precision
("highest")``, no cache, no kernels, no batching; every chunk of the
sequence is pooled and a mask says who sees what.  Heads and query
blocks run one after another, and the SwiGLU in column blocks, only so
that a 20 k sample fits beside the engine.  Written from the published
config and the paper, independent of ``paddle_tpu/models/evabyte.py``.
Head ``h`` of width ``d``, ``s = d^-1/2``, window ``W``, chunk ``c``,
positions from 0:

1. ``x`` is the residual stream in float32.  ``u = RMSNorm(x; gain
   1 + g, eps)``; ``q, k, v = u Wq, u Wk, u Wv`` (no bias); RoPE
   (``rope_theta``, rotate-half) on ``q`` and ``k`` at absolute
   positions.
2. Chunk ``j`` holds tokens ``P_j = [c j, c j + c)``.  ``a_m = softmax
   over m in P_j of (s phi_h . k_m)``; ``k~_j = sum_m a_m k_m + mu_h``;
   ``v~_j = sum_m a_m v_m``.
3. Query ``t`` in window ``w = t // W`` sees the pooled rows of every
   chunk of windows ``< w`` (``j < (W / c) w``) and the exact rows ``W w
   <= m <= t``: ``o_t = softmax_s(q_t . [k~_j ..., k_m ...]) [v~_j ...,
   v_m ...]``, ONE normaliser over the union.
4. ``x = x + o Wo``; ``x = x + SwiGLU(RMSNorm(x))``: the adds in float32.
5. Final RMSNorm; ``logits = float32(u W_head)``, ``num_pred_heads``
   heads of ``vocab_size`` side by side, head 0 the next byte.

What the config leaves open is under ``assumed`` in the configuration
file.  ``ablate`` plants ONE fault (`ABLATIONS`): the negative controls
of ``tools/evabyte_limit.py`` and the tests, never the reference.

One departure, stated, as in ``reference_llama``: with
``dtype=bfloat16`` the matmul operands are the serving type at the
default precision (the residual stream stays float32, as equation 1
says); that is the yardstick of the tolerance, not the reference.
"""

from __future__ import annotations

import functools
from typing import FrozenSet, Mapping, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: one layer's weights: [in, out] matrices, g the norms' offsets,
#: phi / mu [heads, head_dim]
LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "phi", "mu", "ln2",
              "wg", "wu", "wd")
#: the planted faults: no pooled rows at all; mean pooling (phi = 0);
#: no offset on the pooled key; a sliding window of W in place of the
#: tumbling one; a window's pooled rows visible chunk by chunk as they
#: close and not from the window's close; the residual stream rounded
#: to bfloat16 after every add; the norms' gain g in place of 1 + g
ABLATIONS = ("summaries", "phi", "mu", "tumbling", "close", "fp32_skip_add",
             "unit_offset")


class LayerSpec(NamedTuple):
    heads: int
    d: int
    eps: float
    window: int
    chunk: int
    q_block: int
    head_block: int
    ffn_block: int
    ablate: FrozenSet[str]


def rope_tables(theta: float, head_dim: int, n: int):
    inv = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                          / head_dim)
    f = np.outer(np.arange(n, dtype=np.float64), inv)
    return jnp.asarray(np.cos(f), jnp.float32), \
        jnp.asarray(np.sin(f), jnp.float32)


def _rope(x, cos, sin):
    """x [S, h, D], rotate-half."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c, s = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _norm(x, g, spec: LayerSpec, dtype):
    """RMSNorm of the float32 stream with gain 1 + g, in float32; the
    result in the matmuls' type."""
    g = g.astype(jnp.float32)
    gain = g if "unit_offset" in spec.ablate else 1.0 + g
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + spec.eps)
    return (y * gain).astype(dtype)


def _add(x, y, spec: LayerSpec):
    x = x + y.astype(jnp.float32)
    if "fp32_skip_add" in spec.ablate:
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _pool(k, v, phi, mu, spec: LayerSpec):
    """Equation 2 over every whole chunk of k, v [S, h, D]: (k~, v~)
    [S / c, h, D], in float32 and back to the operands' type."""
    S, h, D = k.shape
    c, f32 = spec.chunk, jnp.float32
    kc = k.reshape(S // c, c, h, D).astype(f32)
    vc = v.reshape(S // c, c, h, D).astype(f32)
    if "phi" in spec.ablate:
        phi = jnp.zeros_like(phi)
    a = jax.nn.softmax(
        jnp.einsum("nchd,hd->nch", kc, phi.astype(f32)) / np.sqrt(D), 1)
    kt = jnp.einsum("nch,nchd->nhd", a, kc)
    if "mu" not in spec.ablate:
        kt = kt + mu.astype(f32)
    return kt.astype(k.dtype), jnp.einsum("nch,nchd->nhd", a,
                                          vc).astype(v.dtype)


def _attention(q, k, v, kt, vt, spec: LayerSpec):
    """Equation 3: q, k, v [S, h, D], pooled kt, vt [S / c, h, D] ->
    [S, h, D].  Blocks of `q_block` queries inside one window, one
    after another, for memory only."""
    S, h, D = q.shape
    W, c = spec.window, spec.chunk
    qb = min(spec.q_block or W, W)
    if W % qb or S % qb:
        raise ValueError(f"{S} positions / a window of {W} are not whole "
                         f"query blocks of {qb}")
    sliding = "tumbling" in spec.ablate
    span = 2 * W if sliding else W      # exact keys a block can meet
    kp = jnp.pad(k, ((W, W), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((W, W), (0, 0), (0, 0)))
    j_end = (jnp.arange(S // c) + 1) * c    # a chunk's first position after

    def block(b):
        q0 = b * qb
        w0 = q0 // W * W
        t = (q0 + jnp.arange(qb))[:, None]
        k0 = w0 - W if sliding else w0
        m = (k0 + jnp.arange(span))[None, :]
        exact = (m <= t) & ((m > t - W) & (m >= 0) if sliding else m >= w0)
        pooled = jnp.broadcast_to(j_end[None, :] <= w0, (qb, S // c))
        if "close" in spec.ablate:
            pooled = j_end[None, :] <= t + 1
        if "summaries" in spec.ablate:
            pooled = jnp.zeros_like(pooled)
        qh = jax.lax.dynamic_slice_in_dim(q, q0, qb, 0)
        kh = jax.lax.dynamic_slice_in_dim(kp, k0 + W, span, 0)
        vh = jax.lax.dynamic_slice_in_dim(vp, k0 + W, span, 0)
        s = jnp.concatenate([
            jnp.where(pooled, jnp.einsum("qhd,nhd->hqn", qh, kt), -jnp.inf),
            jnp.where(exact, jnp.einsum("qhd,khd->hqk", qh, kh), -jnp.inf)],
            -1).astype(jnp.float32) / np.sqrt(D)
        p = jax.nn.softmax(s, -1).astype(q.dtype)
        return jnp.einsum("hqk,khd->qhd", p, jnp.concatenate([vt, vh], 0))

    return jax.lax.map(block, jnp.arange(S // qb)).reshape(S, h, D)


def _attention_half(x, w, cos, sin, spec: LayerSpec, dtype, cast):
    """Equations 1-3 and the first add of 4, `head_block` heads at a
    time: each block's o Wo is added up in float32.  `cast` brings a
    block's weights to the matmuls' type, block by block."""
    S = x.shape[0]
    H, D = spec.heads, spec.d
    hb = spec.head_block or H
    u = _norm(x, w["ln1"], spec, dtype)

    def cols(m):                    # [hidden, H * D] -> [blocks, hidden, hb * D]
        return jnp.moveaxis(m.reshape(m.shape[0], H // hb, hb * D), 1, 0)

    def heads(acc, args):
        wq, wk, wv, wo, phi, mu = map(cast, args)
        q = _rope((u @ wq).reshape(S, hb, D), cos, sin)
        k = _rope((u @ wk).reshape(S, hb, D), cos, sin)
        v = (u @ wv).reshape(S, hb, D)
        kt, vt = _pool(k, v, phi, mu, spec)
        o = _attention(q, k, v, kt, vt, spec)
        return acc + jnp.dot(o.reshape(S, hb * D), wo,
                             preferred_element_type=jnp.float32), None

    y, _ = jax.lax.scan(heads, jnp.zeros_like(x), (
        cols(w["wq"]), cols(w["wk"]), cols(w["wv"]),
        w["wo"].reshape(H // hb, hb * D, -1),
        w["phi"].reshape(H // hb, hb, D), w["mu"].reshape(H // hb, hb, D)))
    return _add(x, y, spec)


def _ffn_half(x, w, spec: LayerSpec, dtype, cast):
    """The second add of equation 4, `ffn_block` columns at a time."""
    u = _norm(x, w["ln2"], spec, dtype)
    I = w["wg"].shape[1]
    fb = spec.ffn_block or I
    if I % fb:
        raise ValueError(f"{I} SwiGLU columns are not whole blocks of {fb}")

    def block(acc, args):
        wg, wu, wd = map(cast, args)
        return acc + jnp.dot(jax.nn.silu(u @ wg) * (u @ wu), wd,
                             preferred_element_type=jnp.float32), None

    y, _ = jax.lax.scan(block, jnp.zeros_like(x), (
        jnp.moveaxis(w["wg"].reshape(-1, I // fb, fb), 1, 0),
        jnp.moveaxis(w["wu"].reshape(-1, I // fb, fb), 1, 0),
        w["wd"].reshape(I // fb, fb, -1)))
    return _add(x, y, spec)


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "operands"))
def layer(x, w, cos, sin, *, spec: LayerSpec, dtype, operands=None):
    """One decoder layer over the float32 stream x [S, hidden] of one
    sequence."""
    def cast(m):
        # `operands`: a LOWER precision than the configuration states,
        # for the reading that has to come out as not correct: the
        # matrices rounded to it (float8), then as dtype
        if operands is not None:
            m = m.astype(operands)
        return m.astype(dtype)

    x = _attention_half(x, w, cos, sin, spec, dtype, cast)
    return _ffn_half(x, w, spec, dtype, cast)


@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def head_logits(x, norm_g, head_w, *, spec: LayerSpec, dtype):
    """Equation 5 over rows x [n, hidden]: float32 [n, heads x vocab]."""
    return jnp.dot(_norm(x, norm_g, spec, dtype), head_w.astype(dtype),
                   preferred_element_type=jnp.float32)


def layer_spec(cfg: Mapping, q_block: int = 0, head_block: int = 0,
               ffn_block: int = 0,
               ablate: FrozenSet[str] = frozenset()) -> LayerSpec:
    unknown = set(ablate) - set(ABLATIONS)
    if unknown:
        raise ValueError(f"unknown ablation {sorted(unknown)}")
    return LayerSpec(
        heads=cfg["num_attention_heads"],
        d=cfg["hidden_size"] // cfg["num_attention_heads"],
        eps=cfg["rms_norm_eps"], window=cfg["window_size"],
        chunk=cfg["chunk_size"], q_block=q_block, head_block=head_block,
        ffn_block=ffn_block, ablate=frozenset(ablate))


def hidden_states(ids, embed, layers: Sequence[Mapping], cfg: Mapping,
                  dtype=jnp.float32, q_block: int = 0, head_block: int = 0,
                  ffn_block: int = 0,
                  ablate: FrozenSet[str] = frozenset(), operands=None):
    """Embedding and every decoder layer over ids [S] (one sequence, S
    whole chunks and whole query blocks): the float32 stream [S, hidden]."""
    spec = layer_spec(cfg, q_block, head_block, ffn_block, ablate)
    cos, sin = rope_tables(float(cfg["rope_theta"]), spec.d, ids.shape[0])
    x = jnp.take(embed, ids, axis=0).astype(jnp.float32)
    for w in layers:
        x = layer(x, {k: w[k] for k in LAYER_KEYS}, cos, sin, spec=spec,
                  dtype=dtype, operands=operands)
    return x


def logits(ids, weights: Mapping, cfg: Mapping, dtype=jnp.float32,
           **blocks):
    """The whole forward over ids [S]: float32 [S, num_pred_heads,
    vocab_size]."""
    x = hidden_states(ids, weights["embed"], weights["layers"], cfg, dtype,
                      **blocks)
    out = head_logits(x, weights["norm"], weights["head"],
                      spec=layer_spec(cfg), dtype=dtype)
    return out.reshape(ids.shape[0], cfg["num_pred_heads"],
                       cfg["vocab_size"])


def highest():
    """The reference's matmul precision: on a TPU a float32 matmul runs
    in lower precision unless this is set."""
    return jax.default_matmul_precision("highest")
