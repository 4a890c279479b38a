"""Plain reference of a dense GQA decoder (Mistral / Llama layout).

Straightforward ``jax.numpy``: float32, ``default_matmul_precision
("highest")``, full causal attention, no kernels, no cache, no batching
tricks.  It follows the published description (pre-norm residual
blocks, RMSNorm, rotary embedding in the rotate-half convention,
grouped-query attention, SwiGLU feed-forward, untied head).  Weights
come in whatever type the system holds and are upcast one layer at a
time, so the whole float32 model never exists.

One departure, stated: with ``dtype=bfloat16`` the same code runs in
the serving type at the default precision.  That is not the reference:
it measures how far a correct bfloat16 evaluation lies from the
float32 one, which is the yardstick of the tolerance.
"""

from __future__ import annotations

import functools
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: names of one layer's weights, [in, out] matrices as the program
#: stores them
LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")


def rope_tables(head_dim: int, n: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                           / head_dim))
    f = np.outer(np.arange(n, dtype=np.float64), inv)
    return (jnp.asarray(np.cos(f), jnp.float32),
            jnp.asarray(np.sin(f), jnp.float32))


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y.astype(x.dtype) * w.astype(x.dtype)


def _rope(x, cos, sin):
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(x, w, cos, sin, *, nq, nkv, d, eps, dtype, head_block,
           remat_attention=False):
    w = {k: v.astype(dtype) for k, v in w.items()}
    B, S, _ = x.shape
    h = _rms(x, w["ln1"], eps)
    q = _rope((h @ w["wq"]).reshape(B, S, nq, d), cos, sin)
    k = _rope((h @ w["wk"]).reshape(B, S, nkv, d), cos, sin)
    v = (h @ w["wv"]).reshape(B, S, nkv, d)
    rep = nq // nkv
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def attend(qb, kb, vb):
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb).astype(jnp.float32)
        s = jnp.where(causal, s / np.sqrt(d), -jnp.inf)
        p = jax.nn.softmax(s, -1).astype(qb.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vb)

    hb = head_block or nq
    if remat_attention:
        # under a backward: one block's scores live at a time, the
        # blocks strictly one after another
        def blocks(a):
            return jnp.moveaxis(a.reshape(B, S, nq // hb, hb, d), 2, 0)
        o = jax.lax.map(lambda qkv: jax.checkpoint(attend)(*qkv),
                        (blocks(q), blocks(k), blocks(v)))
        o = jnp.moveaxis(o, 0, 2).reshape(B, S, nq, d)
    else:
        o = jnp.concatenate([attend(q[:, :, i:i + hb], k[:, :, i:i + hb],
                                    v[:, :, i:i + hb])
                             for i in range(0, nq, hb)], 2)
    x = x + o.reshape(B, S, nq * d) @ w["wo"]
    h = _rms(x, w["ln2"], eps)
    return x + (jax.nn.silu(h @ w["wg"]) * (h @ w["wu"])) @ w["wd"]


_LAYER_STATIC = ("nq", "nkv", "d", "eps", "dtype", "head_block")


@functools.partial(jax.jit, static_argnames=_LAYER_STATIC)
def layer(x, w, cos, sin, *, nq, nkv, d, eps, dtype, head_block=0):
    """One decoder layer over x [B, S, H]; ``w`` maps LAYER_KEYS to
    arrays.  ``head_block`` > 0 runs attention over that many query
    heads at a time, so that [B, heads, S, S] scores fit."""
    return _layer(x, w, cos, sin, nq=nq, nkv=nkv, d=d, eps=eps,
                  dtype=dtype, head_block=head_block)


@functools.partial(jax.jit, static_argnames=_LAYER_STATIC)
def layer_input_grad(x, w, cos, sin, dy, *, nq, nkv, d, eps, dtype,
                     head_block=0):
    """The gradient at a layer's input x given the gradient ``dy`` at
    its output: plain reverse mode through the same layer, the weights
    held fixed."""
    _, pull = jax.vjp(lambda x_: _layer(
        x_, w, cos, sin, nq=nq, nkv=nkv, d=d, eps=eps, dtype=dtype,
        head_block=head_block, remat_attention=True), x)
    return pull(dy.astype(x.dtype))[0]


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def head_logits(x, norm_w, head_w, *, eps, dtype):
    return (_rms(x, norm_w.astype(dtype), eps)
            @ head_w.astype(dtype)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def head_loss_sum(x, norm_w, head_w, labels, *, eps, dtype):
    """Sum of the next-token cross-entropy over x [B, S, H]."""
    logits = head_logits(x, norm_w, head_w, eps=eps, dtype=dtype)
    lse = jax.scipy.special.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return (lse - picked).sum()


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def head_loss_input_grad(x, norm_w, head_w, labels, *, eps, dtype):
    """The gradient of ``head_loss_sum`` at x."""
    return jax.grad(lambda x_: head_loss_sum(
        x_, norm_w, head_w, labels, eps=eps, dtype=dtype))(x)


def hidden_states(ids, embed, layers: Sequence[Mapping], cfg: Mapping,
                  dtype=jnp.float32, head_block: int = 0):
    """Embedding and every decoder layer over ids [B, S]."""
    S = ids.shape[1]
    cos, sin = rope_tables(cfg["head_dim"], S, cfg["rope_theta"])
    x = jnp.take(embed, ids, axis=0).astype(dtype)
    for w in layers:
        x = layer(x, {k: w[k] for k in LAYER_KEYS}, cos, sin,
                  nq=cfg["num_attention_heads"],
                  nkv=cfg["num_key_value_heads"], d=cfg["head_dim"],
                  eps=cfg["rms_norm_eps"], dtype=dtype,
                  head_block=head_block)
    return x


def highest():
    """The reference's matmul precision: on a TPU a float32 matmul runs
    in lower precision unless this is set."""
    return jax.default_matmul_precision("highest")
