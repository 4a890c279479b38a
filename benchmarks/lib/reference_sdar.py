"""Plain reference of the SDAR-MoE decoder (JetLM SDAR-30B-A3B-Chat):
the forward under the BLOCK-CAUSAL mask and the transfer rule of its
generation by diffusion over blocks.

Straightforward ``jax.numpy``: float32 under ``default_matmul_precision
("highest")``, no cache, no kernels, no batching; every expert in a
loop (cast to the compute type a few at a time: a layer's 128 experts
are 2.4 GB in float32); attention in query blocks only so that a 3 k
sample fits beside the engine.  Written from the published
``config.json`` and the equations of ISSUE 60; independent of
``paddle_tpu/models/sdar.py``, of ``paddle_tpu/ops`` and of the engine.
With ``h = RMSNorm(x; ln1)``:

1. ``q = h Wq`` [n_q, D], ``k = h Wk``, ``v = h Wv`` [n_kv, D], no bias.
2. ``q = RMSNorm_D(q; q_norm)``, ``k = RMSNorm_D(k; k_norm)`` over each
   head's D dims, one gain vector [D] for all heads; THEN rotate-half
   RoPE at the row's absolute position, ``1 / theta^(2i/D)``.
3. Softmax attention at scale D^-0.5, GQA, under the mask M: the row at
   position i sees key j iff ``j <= B floor(i / B) + B - 1`` — up to the
   END of its block of B positions.  B = 1 is the causal mask.
4. ``x += concat(o) Wo``; ``h2 = RMSNorm(x; ln2)``; ``p = softmax(h2
   Wr)`` over ALL experts in float32, top-k, renormalised
   (``norm_topk_prob``); ``x += sum_e w_e Expert_e(h2)``, SwiGLU experts,
   no shared expert.

Then the final RMSNorm and an untied head.

The transfer rule (``low_confidence_static``, greedy), `transfer`: of a
block's logits [B, V], ``x0 = argmax``, ``c = max softmax_f32``; among
the rows still masked the k with the largest c (all that are left, if
fewer) take their x0; ties go to the lower position; no other row
moves.  `generate` runs it whole, one forward over ALL ids a pass (no
cache): the first ``B floor(P / B)`` prompt tokens are context, the
remaining ``P mod B`` open the first block as given tokens; a block is
denoised in ``ceil((B - g) S / B)`` passes and committed.

``ablate`` plants one fault ("causal": the plain causal mask; "qk_norm":
no q / k norm; "renorm": no renormalisation of the top-k): the tests'
and the limit tool's negative controls, never the reference.  With
``dtype=bfloat16`` the same code runs in the serving type at the default
precision: the yardstick of the tolerance, not the reference.
``operands=float8`` rounds the weights and each layer's input to a lower
precision than the configuration states: the reading that has to come
out as not correct.
"""

from __future__ import annotations

import functools
from typing import FrozenSet, Mapping, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: one layer's weights, [in, out] matrices; gains [D] for q_norm / k_norm;
#: the router [hidden, E]; the expert stacks [E, in, out]
LAYER_KEYS = ("ln1", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "ln2",
              "router", "eg", "eu", "ed")


class LayerSpec(NamedTuple):
    nq: int
    nkv: int
    d: int
    eps: float
    theta: float
    block: int                      # the mask's block length (1: causal)
    qk_norm: bool
    top_k: int
    renorm: bool
    q_block: int                    # queries at a time (0: all)
    expert_block: int               # experts cast at a time (0: all)


def _cast(a, dtype, operands):
    """`a` in the compute type, rounded through `operands` (float8)
    first where a lower precision is being read."""
    if operands is not None:
        a = a.astype(operands)
    return a.astype(dtype)


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y.astype(x.dtype) * w.astype(x.dtype)


def rope_tables(theta: float, d: int, n: int):
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    f = np.outer(np.arange(n, dtype=np.float64), inv)
    return jnp.asarray(np.cos(f), jnp.float32), \
        jnp.asarray(np.sin(f), jnp.float32)


def _rope(x, cos, sin):
    """x [S, h, D]; rotate-half: dims (i, i + D / 2) are a pair."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c, s = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v, block: int, q_block: int):
    """q [S, nkv, rep, D], k / v [S, nkv, D] -> [S, nkv, rep, D] under
    the block-causal mask, `q_block` queries at a time (memory only)."""
    S, nkv, rep, D = q.shape
    qb = min(q_block or S, S)
    nb = -(-S // qb)
    Sp = nb * qb
    qp = jnp.pad(q, ((0, Sp - S), (0, 0), (0, 0), (0, 0)))
    j = jnp.arange(S)[None, :]

    def rows(b):
        i = (b * qb + jnp.arange(qb))[:, None]
        seen = j <= (i // block + 1) * block - 1
        qh = jax.lax.dynamic_slice_in_dim(qp, b * qb, qb, 0)
        s = jnp.einsum("qgrd,kgd->grqk", qh, k).astype(jnp.float32)
        s = jnp.where(seen, s / np.sqrt(D), -jnp.inf)
        p = jax.nn.softmax(s, -1).astype(qh.dtype)
        return jnp.einsum("grqk,kgd->qgrd", p, v)

    o = jax.lax.map(rows, jnp.arange(nb))           # [nb, qb, nkv, rep, D]
    return o.reshape(Sp, nkv, rep, D)[:S]


def routing(h2, router, top_k: int, renorm: bool):
    """(weights [S, k], experts [S, k]) over ALL of the router's
    outputs, in float32 whatever the layer's type."""
    p = jax.nn.softmax(h2.astype(jnp.float32)
                       @ router.astype(jnp.float32), -1)
    w, e = jax.lax.top_k(p, top_k)
    if renorm:
        w = w / jnp.sum(w, -1, keepdims=True)
    return w, e


def _experts(h2, w, spec: LayerSpec, dtype, operands):
    """sum_e w_e Expert_e(h2), one expert at a time; the stacks are
    cast to `dtype` `expert_block` experts at a time."""
    wts, ids = routing(h2, w["router"], spec.top_k, spec.renorm)
    E = w["eg"].shape[0]
    eb = spec.expert_block or E

    def group(carry, g):
        stacks = [_cast(jax.lax.dynamic_slice_in_dim(w[k], g * eb, eb, 0),
                        dtype, operands) for k in ("eg", "eu", "ed")]

        def one(acc, ew):
            e, (eg, eu, ed) = ew
            mine = jnp.sum(jnp.where(ids == g * eb + e, wts, 0.0), -1)
            y = (jax.nn.silu(h2 @ eg) * (h2 @ eu)) @ ed
            return acc + y * mine[:, None].astype(y.dtype), None

        return jax.lax.scan(one, carry, (jnp.arange(eb), stacks))[0], None

    out, _ = jax.lax.scan(group, jnp.zeros_like(h2), jnp.arange(E // eb))
    return out, ids


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "operands"))
def layer(x, w, cos, sin, *, spec: LayerSpec, dtype, operands=None):
    """One decoder layer over x [S, H] (one sequence): (x, the experts
    [S, k] it routed to)."""
    S = x.shape[0]
    nq, nkv, d = spec.nq, spec.nkv, spec.d
    if operands is not None:
        x = _cast(x, dtype, operands)
    small = {k: _cast(w[k], dtype, operands) for k in LAYER_KEYS
             if k not in ("eg", "eu", "ed")}
    h = _rms(x, small["ln1"], spec.eps)
    q = (h @ small["wq"]).reshape(S, nq, d)
    k = (h @ small["wk"]).reshape(S, nkv, d)
    v = (h @ small["wv"]).reshape(S, nkv, d)
    if spec.qk_norm:
        q, k = _rms(q, small["q_norm"], spec.eps), \
            _rms(k, small["k_norm"], spec.eps)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    o = _attention(q.reshape(S, nkv, nq // nkv, d), k, v, spec.block,
                   spec.q_block)
    x = x + o.reshape(S, nq * d) @ small["wo"]
    h2 = _rms(x, small["ln2"], spec.eps)
    routed, ids = _experts(h2, dict(w, router=small["router"]), spec,
                           dtype, operands)
    return x + routed, ids


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "vocab_block"))
def head_logits(x, norm_w, head_w, *, eps, dtype, vocab_block: int = 0):
    """[rows, V] float32; the head is cast `vocab_block` columns at a
    time (0: whole — 1.2 GB in float32 at the published vocabulary)."""
    h = _rms(x, norm_w.astype(dtype), eps)
    V = head_w.shape[1]
    vb = vocab_block or V
    if V % vb:
        raise ValueError(f"vocab_block {vb} does not divide {V}")

    def cols(b):
        w = jax.lax.dynamic_slice_in_dim(head_w, b * vb, vb, 1)
        return (h @ w.astype(dtype)).astype(jnp.float32)

    out = jax.lax.map(cols, jnp.arange(V // vb))        # [nb, rows, vb]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)


def layer_specs(cfg: Mapping, q_block: int = 0, expert_block: int = 0,
                ablate: FrozenSet[str] = frozenset()) -> Sequence[LayerSpec]:
    """One LayerSpec a layer from the configuration's published keys and
    its ``block_length``."""
    spec = LayerSpec(
        nq=cfg["num_attention_heads"], nkv=cfg["num_key_value_heads"],
        d=cfg["head_dim"], eps=cfg["rms_norm_eps"],
        theta=float(cfg["rope_theta"]),
        block=1 if "causal" in ablate else int(cfg["block_length"]),
        qk_norm="qk_norm" not in ablate,
        top_k=cfg["num_experts_per_tok"],
        renorm=bool(cfg["norm_topk_prob"]) and "renorm" not in ablate,
        q_block=q_block, expert_block=expert_block)
    return [spec] * cfg["num_hidden_layers"]


def hidden_states(ids, embed, layers: Sequence[Mapping], cfg: Mapping,
                  dtype=jnp.float32, q_block: int = 0, expert_block: int = 0,
                  ablate: FrozenSet[str] = frozenset(), operands=None):
    """Embedding and every decoder layer over ids [S] (one sequence,
    mask tokens and all): (x [S, H], the experts [layers, S, k])."""
    S = ids.shape[0]
    cos, sin = rope_tables(float(cfg["rope_theta"]), cfg["head_dim"], S)
    x = jnp.take(embed, ids, axis=0).astype(dtype)
    routed = []
    for w, spec in zip(layers, layer_specs(cfg, q_block, expert_block,
                                           ablate)):
        x, ids_l = layer(x, {k: w[k] for k in LAYER_KEYS}, cos, sin,
                         spec=spec, dtype=dtype, operands=operands)
        routed.append(ids_l)
    return x, routed


def logits(ids, weights: Mapping, cfg: Mapping, dtype=jnp.float32,
           rows=slice(None), vocab_block: int = 0, **kw):
    """The forward over ids [S] under the block-causal mask: the rows
    `rows` of [S, V] float32."""
    x, _ = hidden_states(ids, weights["embed"], weights["layers"], cfg,
                         dtype, **kw)
    return head_logits(x[rows], weights["norm"], weights["head"],
                       eps=cfg["rms_norm_eps"], dtype=dtype,
                       vocab_block=vocab_block)


# ------------------------------------------------------------ the rule
def confidence(logits: np.ndarray):
    """(x0 [B] the argmax, the first on ties; c [B] = max softmax_f32)."""
    z = np.asarray(logits, np.float32)
    top = z.max(-1, keepdims=True)
    return z.argmax(-1), (1.0 / np.exp(z - top).sum(-1, dtype=np.float32))


def transfer(logits: np.ndarray, masked: np.ndarray, k: int):
    """The transfer rule as a pure function of a block's logits [B, V],
    which rows are still masked [B] and how many a pass unmasks: (x0
    [B], chosen [B] bool — the rows that take their x0 — and the
    confidences [B])."""
    x0, c = confidence(logits)
    order = sorted((i for i in range(len(c)) if masked[i]),
                   key=lambda i: (-c[i], i))
    chosen = np.zeros(len(c), bool)
    chosen[order[:k]] = True
    return x0, chosen, c


def block_passes(block: int, steps: int, given: int = 0) -> int:
    """Launches a block of `given` given tokens costs: its denoise
    passes and one commit."""
    return -(-(block - given) * steps // block) + 1


def generate(prompt, max_new: int, weights: Mapping, cfg: Mapping,
             eos=None, dtype=jnp.float32, **kw):
    """The generation rule whole, one full forward a denoise pass (the
    commit pass computes nothing a forward without a cache needs): (the
    tokens generated, [(block tokens going in, logits [B, V], block
    after)] of every denoise pass)."""
    B, S_ = int(cfg["block_length"]), int(cfg["denoising_steps"])
    mask = int(cfg["mask_token_id"])
    prompt = [int(t) for t in prompt]
    g = len(prompt) % B
    ctx, out, passes = prompt[:len(prompt) - g], [], []
    given = prompt[len(prompt) - g:]
    while len(out) < max_new:
        blk = np.asarray(given + [mask] * (B - len(given)), np.int64)
        for _ in range(block_passes(B, S_, len(given)) - 1):
            ids = np.asarray(ctx + blk.tolist(), np.int32)
            z = np.asarray(logits(jnp.asarray(ids), weights, cfg, dtype,
                                  rows=slice(len(ctx), len(ctx) + B), **kw))
            x0, chosen, _ = transfer(z, blk == mask, B // S_)
            after = np.where(chosen, x0, blk)
            passes.append((blk.copy(), z, after.copy()))
            blk = after
        ctx += blk.tolist()
        for t in blk.tolist()[len(given):]:
            out.append(t)
            if len(out) == max_new or (eos is not None and t == eos):
                return out, passes
        given = []
    return out, passes


def highest():
    """The reference's matmul precision: on a TPU a float32 matmul runs
    in lower precision unless this is set."""
    return jax.default_matmul_precision("highest")
