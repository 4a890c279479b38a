"""Plain reference of the Ouro looped decoder (``ByteDance/Ouro-2.6B``,
``model_type`` ``ouro``; Ouro / LoopLM, arXiv:2510.25741): the layer
list run ``total_ut_steps`` times a token with ONE set of weights.

Straightforward ``jax.numpy``: float32 under ``default_matmul_precision
("highest")``, no cache, no kernels, no batching: one sequence, every
pass over the whole of it, a causal mask.  Queries run in blocks and the
SwiGLU in column blocks, and a layer's weights are cast as it is met,
only so that a 1.5 k sample fits beside the engine.  Written from the
published config and the release's description, independent of
``paddle_tpu/models/ouro.py``.  ``H`` hidden, 16 heads of ``d`` = 128,
``s = d^-1/2``, ``U`` passes, positions from 0:

1. ``x_0 = E[tok]``; the embedding enters before the first pass only.
2. Pass ``u`` applies layers ``l`` in order with the same weights in
   every pass.  Layer (all RMSNorm, plain gain): ``a = RMSNorm(x; g1)``;
   ``q, k, v = a Wq, a Wk, a Wv`` (no bias); RoPE (rotate-half,
   ``rope_theta``) on ``q`` and ``k`` at the absolute position, the
   same in every pass; ``o_t = softmax_s(q_t . K_{<=t}) V_{<=t}`` over
   the keys and values THIS pass of THIS layer made; ``x = x +
   RMSNorm(o Wo; g2)``; ``m = RMSNorm(x; g3)``; ``x = x +
   RMSNorm((silu(m Wg) * (m Wu)) Wd; g4)``.
3. ``h_u = RMSNorm(x; g_f)`` ends every pass and the next pass starts
   from ``h_u``.  ``lambda_u = sigmoid(h_u . w_e + b_e)``; ``p(u) =
   lambda_u prod_{j<u} (1 - lambda_j)``, the last pass takes the rest.
   A token leaves at the first pass whose cumulative ``p`` reaches
   ``early_exit_threshold``; at the published 1 that is the last pass:
   ``logits = h_{U-1} W_head``.

What the config leaves open is under ``assumed`` in the configuration
file.  Departures from the release, stated: none known; the release's
``modeling_ouro.py`` names the two output norms ``input_layernorm_2``
and ``post_attention_layernorm_2`` and indexes its cache by ``pass x
layers + layer``, which is equation 2's "THIS pass of THIS layer".

``ablate`` plants ONE fault (`ABLATIONS`), and `shared_slot_states` is
the fault of a cache that keeps one slot a layer: the negative controls
of ``tools/ouro_limit.py`` and the tests, never the reference.

One departure, stated, as in ``reference_llama``: with
``dtype=bfloat16`` the same code runs in the serving type at the
default precision; that is the yardstick of the tolerance, not the
reference.
"""

from __future__ import annotations

import functools
from typing import FrozenSet, List, Mapping, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: one layer's weights: [in, out] matrices and the four gains
LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln1_out", "ln2", "wg", "wu",
              "wd", "ln2_out")
#: the planted faults: one pass only; one pass too few; pass u reading
#: pass u - 1's keys and values; no norm between the passes (the last
#: norm before the head only); the two output norms dropped; the
#: embedding added again at the start of every later pass
ABLATIONS = ("passes_1", "passes_3", "prev_pass_rows", "loop_norm",
             "post_norms", "reinject_embed")


class LayerSpec(NamedTuple):
    heads: int
    d: int
    eps: float
    q_block: int
    ffn_block: int
    ablate: FrozenSet[str]


def rope_tables(theta: float, head_dim: int, n: int):
    inv = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                          / head_dim)
    f = np.outer(np.arange(n, dtype=np.float64), inv)
    return jnp.asarray(np.cos(f), jnp.float32), \
        jnp.asarray(np.sin(f), jnp.float32)


def _rope(x, cos, sin):
    """x [n, h, D], rotate-half."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c, s = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _norm(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _attention(q, k, v, start, spec: LayerSpec):
    """q [n, h, D] at positions start + 0..n-1 over keys and values
    [S, h, D] at positions 0..S-1, causal.  Blocks of ``q_block``
    queries one after another, for memory only."""
    n, h, D = q.shape
    qb = spec.q_block if spec.q_block and n % spec.q_block == 0 else n
    kpos = jnp.arange(k.shape[0])[None, :]

    def block(b):
        qh = jax.lax.dynamic_slice_in_dim(q, b * qb, qb, 0)
        qpos = (start + b * qb + jnp.arange(qb))[:, None]
        s = jnp.einsum("qhd,khd->hqk", qh, k).astype(jnp.float32) \
            / np.sqrt(D)
        p = jax.nn.softmax(jnp.where(kpos <= qpos, s, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p.astype(q.dtype), v)

    return jax.lax.map(block, jnp.arange(n // qb)).reshape(n, h, D)


@functools.partial(jax.jit,
                   static_argnames=("spec", "dtype", "operands", "mode"))
def layer(x, w, cos, sin, rows=None, start=0, *, spec: LayerSpec, dtype,
          operands=None, mode: str = "own"):
    """One application of one layer to x [n, hidden], the rows of one
    sequence at positions start + 0..n-1 (``cos`` / ``sin`` theirs).
    Returns (x, (k, v)).

    ``mode`` "own" is equation 2: attention over the keys and values
    this call made, which it returns.  The two others are faults:
    "read" attends over ``rows`` (another pass's) and returns its own;
    "write" puts its own into ``rows`` [S, h, D] at ``start`` and
    attends over all of them up to each query's position — a cache with
    ONE slot a layer — and returns the rows."""
    def cast(m):
        # `operands`: a LOWER precision than the configuration states,
        # for the reading that has to come out as not correct
        if operands is not None:
            m = m.astype(operands)
        return m.astype(dtype)

    n = x.shape[0]
    H, D = spec.heads, spec.d
    post = "post_norms" not in spec.ablate
    a = _norm(x, w["ln1"], spec.eps)
    q = _rope((a @ cast(w["wq"])).reshape(n, H, D), cos, sin)
    k = _rope((a @ cast(w["wk"])).reshape(n, H, D), cos, sin)
    v = (a @ cast(w["wv"])).reshape(n, H, D)
    own = (k, v)
    if mode == "read":
        k, v = rows
    elif mode == "write":
        k = jax.lax.dynamic_update_slice_in_dim(rows[0], k, start, 0)
        v = jax.lax.dynamic_update_slice_in_dim(rows[1], v, start, 0)
        own = (k, v)
    y = _attention(q, k, v, start, spec).reshape(n, H * D) @ cast(w["wo"])
    x = x + (_norm(y, w["ln1_out"], spec.eps) if post else y)
    m = _norm(x, w["ln2"], spec.eps)
    F = w["wg"].shape[1]
    fb = spec.ffn_block or F
    if F % fb:
        raise ValueError(f"{F} SwiGLU columns are not whole blocks of {fb}")

    def block(acc, args):
        wg, wu, wd = map(cast, args)
        return acc + jnp.dot(jax.nn.silu(m @ wg) * (m @ wu), wd,
                             preferred_element_type=jnp.float32), None

    y, _ = jax.lax.scan(block, jnp.zeros(x.shape, jnp.float32), (
        jnp.moveaxis(w["wg"].reshape(-1, F // fb, fb), 1, 0),
        jnp.moveaxis(w["wu"].reshape(-1, F // fb, fb), 1, 0),
        w["wd"].reshape(F // fb, fb, -1)))
    y = y.astype(x.dtype)
    return x + (_norm(y, w["ln2_out"], spec.eps) if post else y), own


def layer_spec(cfg: Mapping, q_block: int = 0, ffn_block: int = 0,
               ablate: FrozenSet[str] = frozenset()) -> LayerSpec:
    unknown = set(ablate) - set(ABLATIONS)
    if unknown:
        raise ValueError(f"unknown ablation {sorted(unknown)}")
    return LayerSpec(heads=cfg["num_attention_heads"], d=cfg["head_dim"],
                     eps=cfg["rms_norm_eps"], q_block=q_block,
                     ffn_block=ffn_block, ablate=frozenset(ablate))


def _pick(w: Mapping) -> dict:
    return {k: w[k] for k in LAYER_KEYS}


def pass_states(ids, weights: Mapping, cfg: Mapping, dtype=jnp.float32,
                q_block: int = 0, ffn_block: int = 0,
                ablate: FrozenSet[str] = frozenset(),
                operands=None) -> List[jax.Array]:
    """Equations 1-3 over ids [S] (one sequence): ``h_u`` [S, hidden] of
    every pass, in ``dtype``."""
    spec = layer_spec(cfg, q_block, ffn_block, ablate)
    U = int(cfg["total_ut_steps"])
    U = 1 if "passes_1" in spec.ablate else \
        U - 1 if "passes_3" in spec.ablate else U
    cos, sin = rope_tables(float(cfg["rope_theta"]), spec.d, ids.shape[0])
    e = jnp.take(weights["embed"], ids, axis=0).astype(dtype)
    x, hs, before = e, [], None
    for u in range(U):
        if u and "reinject_embed" in spec.ablate:
            x = x + e
        made = []
        for i, w in enumerate(weights["layers"]):
            read = before is not None
            x, kv = layer(x, _pick(w), cos, sin,
                          before[i] if read else None, spec=spec,
                          dtype=dtype, operands=operands,
                          mode="read" if read else "own")
            made.append(kv)
        if "prev_pass_rows" in spec.ablate:
            before = made
        if "loop_norm" not in spec.ablate or u == U - 1:
            x = _norm(x, weights["norm"], spec.eps)
        hs.append(x)
    return hs


def shared_slot_states(ids, weights: Mapping, cfg: Mapping,
                       launches: Sequence[int], dtype=jnp.float32,
                       ffn_block: int = 0):
    """THE FAULT of a cache that keeps one slot a layer, as a serving
    engine would show it (the paper's "reuse the last pass's rows in
    decode" is this, and changes the logits): the sequence arrives in
    ``launches`` (rows each: the prompt's chunks, then one token at a
    time), every pass of a launch writes its rows over the pass
    before's, and a later launch finds the LAST pass's rows of the
    earlier ones.  The last pass's ``h`` [S, hidden]."""
    spec = layer_spec(cfg, 0, ffn_block)
    U, S = int(cfg["total_ut_steps"]), ids.shape[0]
    if sum(launches) != S:
        raise ValueError(f"launches {sum(launches)} rows, ids {S}")
    cos, sin = rope_tables(float(cfg["rope_theta"]), spec.d, S)
    empty = jnp.zeros((S, spec.heads, spec.d), dtype)
    slots = [(empty, empty) for _ in weights["layers"]]
    out, start = [], 0
    for n in launches:
        at = slice(start, start + n)
        x = jnp.take(weights["embed"], ids[at], axis=0).astype(dtype)
        for _ in range(U):
            for i, w in enumerate(weights["layers"]):
                x, slots[i] = layer(x, _pick(w), cos[at], sin[at], slots[i],
                                    start, spec=spec, dtype=dtype,
                                    mode="write")
            x = _norm(x, weights["norm"], spec.eps)
        out.append(x)
        start += n
    return jnp.concatenate(out, 0)


@functools.partial(jax.jit, static_argnames=("dtype",))
def head_logits(h, head_w, *, dtype):
    """``h W_head`` over normed rows h [n, hidden]: float32 [n, vocab]."""
    return jnp.dot(h.astype(dtype), head_w.astype(dtype),
                   preferred_element_type=jnp.float32)


def exit_distribution(hs: Sequence[jax.Array], gate_w, gate_b):
    """Equation 3's ``p`` [S, U] from the passes' states."""
    f32 = jnp.float32
    lam = [jax.nn.sigmoid(h.astype(f32) @ gate_w.astype(f32)
                          + gate_b.astype(f32)) for h in hs]
    p, stay = [], jnp.ones_like(lam[0])
    for lm in lam[:-1]:
        p.append(lm * stay)
        stay = stay * (1.0 - lm)
    return jnp.stack(p + [stay], -1)


def logits(ids, weights: Mapping, cfg: Mapping, dtype=jnp.float32,
           **blocks):
    """The whole forward over ids [S]: float32 [S, vocab] of the state
    each token leaves with, the passes' states and ``p``."""
    hs = pass_states(ids, weights, cfg, dtype, **blocks)
    p = exit_distribution(hs, weights["gate_w"], weights["gate_b"])
    u = np.full(ids.shape[0], len(hs) - 1)
    if cfg["early_exit_threshold"] < 1:
        reached = np.asarray(jnp.cumsum(p, -1)) >= cfg["early_exit_threshold"]
        reached[:, -1] = True
        u = reached.argmax(-1)
    h = jnp.stack(hs, 1)[jnp.arange(ids.shape[0]), u]
    return head_logits(h, weights["head"], dtype=dtype), hs, p


def highest():
    """The reference's matmul precision: on a TPU a float32 matmul runs
    in lower precision unless this is set."""
    return jax.default_matmul_precision("highest")
