"""Text generation (ref capability: PaddleNLP GenerationMixin —
model.generate with greedy_search / sampling decode strategies,
paddlenlp/generation/utils.py).

TPU-first mechanism: autoregressive decoding runs the model on a FIXED
[B, prompt+max_new_tokens] buffer every step and reads the logits at the
current position. Causal attention makes positions > t irrelevant to the
step-t logits, so the pad tail is harmless — and the constant shape means
ONE compiled executable serves every step (no per-length recompiles, the
XLA analog of the reference's static decode graph). The serving-grade
O(1)-per-step path is the paged/masked decode attention kernel set
(ops/paged_attention.py, incubate.nn.functional.masked_multihead_attention)
used by the inference Predictor; this module is the framework-level
`generate()` every CausalLM model family shares.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import observability as _obs
from . import resilience as _res
from .core.tensor import Tensor
from .core import autograd as ag
from .framework.random import next_key

__all__ = ["generate"]


def _finalize_tokens(out_tokens, out_scores, B, max_new_tokens,
                     pad_token_id):
    """Stack + right-pad the per-step token/score lists to the full
    [B, max_new_tokens] width (early eos or deadline expiry leaves the
    lists short; an expiry before the first token leaves them empty)."""
    if out_tokens:
        gen = jnp.stack(out_tokens, 1)
        sc = jnp.stack(out_scores, 1)
    else:
        gen = jnp.zeros((B, 0), jnp.int32)
        sc = jnp.zeros((B, 0), jnp.float32)
    if gen.shape[1] < max_new_tokens:
        padw = max_new_tokens - gen.shape[1]
        gen = jnp.concatenate(
            [gen, jnp.full((B, padw), pad_token_id, jnp.int32)], 1)
        sc = jnp.concatenate([sc, jnp.zeros((B, padw), sc.dtype)], 1)
    return Tensor(gen), Tensor(sc)


def _timeout_result(kind, dl, completed, partial):
    """Typed deadline-expiry return (resilience.TimeoutResult): counts
    the miss and carries whatever tokens were produced in time."""
    _res.deadline_miss()
    return _res.TimeoutResult(kind=kind, budget_s=dl.budget_s,
                              elapsed_s=dl.elapsed_s,
                              completed=completed, partial=partial)

# serving metrics (ISSUE 1): prefill vs decode token throughput, request
# batch sizes, and decode-loop program-cache hit rate. Durations are host
# wall-clock around the dispatching section; PJRT dispatch is async, so a
# section's time includes device wait only where the code forces a fetch
# (documented in docs/OBSERVABILITY.md).
_SRV_REQS = _obs.registry().counter(
    "pt_serving_requests_total", "generate-family calls", labels=("path",))
_SRV_PREFILL_TOK = _obs.registry().counter(
    "pt_serving_prefill_tokens_total", "prompt tokens prefilled")
_SRV_DECODE_TOK = _obs.registry().counter(
    "pt_serving_decode_tokens_total", "tokens produced by decode steps")
_SRV_PREFILL_S = _obs.registry().histogram(
    "pt_serving_prefill_seconds", "prefill section wall time",
    labels=("path",))
_SRV_DECODE_S = _obs.registry().histogram(
    "pt_serving_decode_seconds", "decode section wall time",
    labels=("path",))
_SRV_BATCH = _obs.registry().histogram(
    "pt_serving_batch_size", "request batch size",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512))
_JIT_CACHE = _obs.registry().counter(
    "pt_jit_cache_events_total", "compiled-program cache lookups",
    labels=("cache", "event"))


def _logits_fn(model, ids_arr):
    """One forward on the padded buffer → [B, S, V] raw logits array."""
    out = model(Tensor(ids_arr))
    if isinstance(out, tuple):
        out = out[-1]
    return out._data


def _sample_token(logits, strategy, top_k, top_p, temperature):
    """logits [B, V] → token ids [B]."""
    if strategy == "greedy_search" or (temperature is not None
                                       and temperature <= 0.0):
        # temperature 0 degenerates to greedy (the usual convention),
        # never a silent fall-through to temperature-1 sampling
        return jnp.argmax(logits, -1).astype(jnp.int32)
    return jax.random.categorical(
        next_key(), _filter_logits(logits, top_k, top_p, temperature),
        -1).astype(jnp.int32)


def _filter_logits(logits, top_k, top_p, temperature):
    """The temperature/top-k/top-p part of _sample_token, key-free (shared
    by the host-loop and compiled samplers); keeps the smallest prefix with
    cumulative prob >= top_p."""
    if temperature is not None and temperature != 1.0:
        logits = logits / temperature
    if top_k:
        kth = jnp.sort(logits, -1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p and top_p < 1.0:
        sorted_logits = jnp.sort(logits, -1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, -1)
        cum = jnp.cumsum(probs, -1)
        cutoff_idx = jnp.sum(cum < top_p, -1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None], -1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def _refuse_block_diffusion(model, what: str) -> None:
    """A model that generates by diffusion over blocks (`models.sdar`:
    its config has a ``block_length``) is never decoded one causal
    token at a time: `serving.ServingEngine` runs its rule."""
    block = getattr(getattr(model, "config", None), "block_length", None)
    if block is not None:
        raise NotImplementedError(
            f"{what}: this model generates by diffusion over blocks of "
            f"{block} under a block-causal mask; one causal token a step "
            f"is another model. serving.ServingEngine runs its rule")


def generate(model, input_ids, max_new_tokens: int = 20,
             decode_strategy: str = "sampling", top_k: Optional[int] = None,
             top_p: Optional[float] = None, temperature: float = 1.0,
             eos_token_id: Optional[int] = None, pad_token_id: int = 0,
             deadline_s: Optional[float] = None):
    """ref: PaddleNLP model.generate(...). Returns (generated_ids, scores):
    generated_ids [B, max_new_tokens] holds ONLY the new tokens (prompt
    excluded, PaddleNLP convention), padded with pad_token_id after eos;
    scores [B, max_new_tokens] are the chosen tokens' log-probs.

    ``deadline_s`` bounds the request wall-clock: the decode loop stops
    at the first step past the budget and the call returns a falsy
    resilience.TimeoutResult whose .partial carries the (padded) tokens
    produced in time — a typed outcome, never an unbounded hang.
    """
    _refuse_block_diffusion(model, "generate")
    if decode_strategy not in ("greedy_search", "sampling"):
        raise ValueError(f"decode_strategy {decode_strategy!r}: expected "
                         "'greedy_search' or 'sampling'")
    ids = input_ids._data if isinstance(input_ids, Tensor) \
        else jnp.asarray(input_ids)
    ids = ids.astype(jnp.int32)
    B, S0 = ids.shape
    total = S0 + max_new_tokens
    buf = jnp.concatenate(
        [ids, jnp.full((B, max_new_tokens), pad_token_id, jnp.int32)], 1)
    finished = jnp.zeros((B,), bool)
    out_tokens = []
    out_scores = []
    dl = _res.Deadline(deadline_s) if deadline_s else None
    timed_out = False
    was_training = getattr(model, "training", False)
    if hasattr(model, "eval"):
        model.eval()
    try:
        with ag.no_grad():
            for t in range(S0 - 1, total - 1):
                if dl is not None and dl.expired():
                    timed_out = True
                    break
                logits = _logits_fn(model, buf)[:, t]
                tok = _sample_token(logits, decode_strategy, top_k, top_p,
                                    temperature)
                logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
                score = jnp.take_along_axis(logp, tok[:, None], -1)[:, 0]
                if eos_token_id is not None:
                    tok = jnp.where(finished, pad_token_id, tok)
                    score = jnp.where(finished, 0.0, score)
                    finished = finished | (tok == eos_token_id)
                buf = buf.at[:, t + 1].set(tok)
                out_tokens.append(tok)
                out_scores.append(score)
                if eos_token_id is not None and bool(jnp.all(finished)):
                    break
    finally:
        if was_training and hasattr(model, "train"):
            model.train()
    partial = _finalize_tokens(out_tokens, out_scores, B, max_new_tokens,
                               pad_token_id)
    if timed_out:
        return _timeout_result("generate", dl, len(out_tokens), partial)
    return partial


# ---------------------------------------------------------------------------
# KV-cache decoding (serving-grade O(1)-per-step path; ref capability:
# PaddleNLP use_cache generation over the masked/block decode attention
# kernels — paddle/phi/kernels/fusion/gpu/masked_multihead_attention)
# ---------------------------------------------------------------------------
def _llama_decode_params(model, weight_only_int8: bool = False,
                         weight_only_quant=None):
    """Extract the cached-decode weight tree from a Llama-family CausalLM
    (LlamaForCausalLM, Qwen2ForCausalLM — same GQA backbone; Qwen2 adds
    q/k/v biases, carried as optional leaves).

    ``weight_only_int8``: store every 2-D matmul weight as (int8 values,
    per-output-channel f32 scale) — ops/quant.weight_quantize — halving
    the HBM weight reads that bound decode; the body dequantizes in VMEM
    (ref: paddle/nn/quant weight-only deploy path).
    ``weight_only_quant``: 'int8' (same as the bool) or 'int4' (packed
    nibbles, quarter the weight reads; decode contracts even/odd rows so
    the unpack fuses — see _int4_halves).

    Layout of the float leaves: ``wq`` / ``wk`` / ``wv`` are stored
    [heads, head_dim, in] (`_heads_w`; read through `_mm_heads`),
    because their output is split into heads for a kernel and the TPU
    compiler then reads the weight in that form — from a stored
    [in, out] it transposed all three once a layer of every step.
    ``wo``, the FFN's matrices and the head produce 2-D outputs, are
    read as stored and stay [in, out] (`_mm_w`). Every family's twin
    below does the same for its head-split keys (the latent family:
    ``wqb`` or its one-stage ``wq``, and ``wkvb``). The stored leaf is a
    second buffer beside the module's parameter: 2 bytes x in x out a
    key a layer. Quantized pairs keep [K, N]."""
    algo, enabled = _woq_algo(weight_only_int8, weight_only_quant)
    cfg = model.config
    inner = getattr(model, "llama", None)
    if inner is None:
        inner = getattr(model, "qwen2", None)
    if inner is None:
        raise NotImplementedError(
            "KV-cache generation: expected a Llama-family model "
            "(model.llama / model.qwen2)")
    if getattr(cfg, "fuse_attention_qkv", False) or \
            getattr(cfg, "fuse_attention_ffn", False):
        raise NotImplementedError(
            "use_cache generation supports the unfused Llama layout; the "
            "fused qkv/ffn packs are pretrain perf knobs")
    layers = []
    for lyr in inner.layers:
        a, m = lyr.self_attn, lyr.mlp
        d = dict(
            ln1=lyr.input_layernorm.weight._data,
            wq=a.q_proj.weight._data, wk=a.k_proj.weight._data,
            wv=a.v_proj.weight._data, wo=a.o_proj.weight._data,
            ln2=lyr.post_attention_layernorm.weight._data,
            wg=m.gate_proj.weight._data, wu=m.up_proj.weight._data,
            wd=m.down_proj.weight._data)
        if getattr(a.q_proj, "bias", None) is not None:
            d["bq"] = a.q_proj.bias._data
            d["bk"] = a.k_proj.bias._data
            d["bv"] = a.v_proj.bias._data
        for k in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
            _q8(d, k, enabled, algo)
        _heads_w(d, cfg.head_dim, "wq", "wk", "wv")
        layers.append(d)
    head = model.lm_head.weight._data if model.lm_head is not None else None
    p = dict(cfg=cfg, family="llama",
             embed=inner.embed_tokens.weight._data,
             layers=layers, norm=inner.norm.weight._data, head=head,
             cos=inner.rope_cos._data, sin=inner.rope_sin._data)
    if enabled and head is not None:
        _q8(p, "head", True, algo)
        p["head"] = None
    return p


def _gpt_decode_params(model):
    """GPT family: fused qkv (+bias), LayerNorms with biases, GELU MLP,
    learned positions, no rope."""
    gpt = model.gpt
    layers = []
    for blk in gpt.h:
        layers.append(dict(
            ln1w=blk.ln_1.weight._data, ln1b=blk.ln_1.bias._data,
            wqkv=blk.attn.qkv.weight._data, bqkv=blk.attn.qkv.bias._data,
            wo=blk.attn.proj.weight._data, bo=blk.attn.proj.bias._data,
            ln2w=blk.ln_2.weight._data, ln2b=blk.ln_2.bias._data,
            wi=blk.mlp.fc_in.weight._data, bi=blk.mlp.fc_in.bias._data,
            wf=blk.mlp.fc_out.weight._data, bf=blk.mlp.fc_out.bias._data))
    head = model.lm_head.weight._data if model.lm_head is not None else None
    return dict(cfg=model.config, family="gpt",
                embed=gpt.embed_tokens.weight._data,
                pos=gpt.embed_positions.weight._data,
                layers=layers, normw=gpt.ln_f.weight._data,
                normb=gpt.ln_f.bias._data, head=head)


def _woq_algo(weight_only_int8, weight_only_quant):
    """Normalize the two public quant knobs to (algo, enabled)."""
    if weight_only_quant not in (None, "int8", "int4"):
        raise ValueError(
            f"weight_only_quant {weight_only_quant!r}: expected "
            "'int8' or 'int4'")
    if weight_only_quant:
        if weight_only_int8 and weight_only_quant != "int8":
            raise ValueError(
                "conflicting quant knobs: weight_only_int8=True with "
                f"weight_only_quant={weight_only_quant!r} — drop the "
                "bool or make them agree")
        return "weight_only_" + weight_only_quant, True
    return "weight_only_int8", bool(weight_only_int8)


def _q8(d, key, enabled: bool = True, algo: str = "weight_only_int8"):
    """Quantize d[key] in place to (int8 or packed-int4 values,
    per-out-channel f32 scale) — the weight-only deploy transform shared
    by every decode family. int8 stores key_q [K, N]; int4 stores key_q4
    [K/2, N] (two nibbles per byte — consumers split the contraction
    into even/odd rows so the unpack stays an elementwise chain XLA
    fuses into the dot operand loads, never a materialized bf16 weight).
    3-D expert stacks [E, K, N] quantize per expert (vmapped absmax)
    with scales [E, N]; None entries and disabled calls are no-ops."""
    if not enabled or d.get(key) is None:
        return
    from .ops.quant import weight_quantize
    import functools
    w = d.pop(key)
    qfn = functools.partial(weight_quantize, algo=algo)
    if w.ndim == 3:
        qw, sc = jax.vmap(qfn)(w)
    else:
        qw, sc = qfn(w)
    d[key + ("_q4" if algo == "weight_only_int4" else "_q")] = qw
    d[key + "_s"] = sc.astype(jnp.float32)


def _heads_w(d, head_dim: int, *keys):
    """Store the float leaves d[key] [in, heads * head_dim] as [heads,
    head_dim, in], in place: the store side of `_mm_heads`, called once
    at load by every `_*_decode_params` twin for the projections whose
    output is split into heads for a kernel (``wq`` / ``wk`` / ``wv``;
    the latent family's ``wqb`` or one-stage ``wq``, and ``wkvb``). For
    such an output the TPU compiler reads the weight as [heads, D, in]
    with ``in`` minor; a stored [in, heads * D] cannot be bitcast to
    that, and a weight is an argument of the step, so the transposition
    ran once a layer of every step. The 2-D [out, in] has the same
    bytes and compiles copy-free too, but at Mistral's depth the
    compiler then placed the step's activations elsewhere and the step
    was 1.7 ms SLOWER on the chip; under the 3-D form the compiled step
    is the parent's without the copies (PERF.md section 6, PR 48). The
    stored leaf is a second buffer beside the module's parameter. A
    quantized pair (``key_q`` / ``key_q4``, ``key_s``) has no float leaf
    under ``key`` and keeps [K, N]: its kernel and its
    per-output-channel scales are written for that."""
    for key in keys:
        if d.get(key) is not None:
            w = d[key]
            d[key] = w.T.reshape(-1, head_dim, w.shape[0])


def _mlp_params(lyr, weight_only_int8: bool = False,
                algo: str = "weight_only_int8"):
    """Per-layer FFN weights: (weight dict, static routing knobs or None).
    Dense SwiGLU (llama layout) or routed MoE (dropless per-token routing —
    serving never drops tokens; the capacity factor is a training
    regularizer, ref fused MoE serving kernels). Static knobs must stay out
    of the weight tree: it rides through jit as arguments.

    ``weight_only_int8`` quantizes the dense ffn, the per-expert stacks
    (per-expert out-channel scales) and the shared expert with ``algo``
    ('weight_only_int8' or 'weight_only_int4' — the 3-D expert stacks
    pack per expert via the vmapped weight_quantize and read back
    through _dq's plane-interleave); the ROUTER gate stays fp — it is
    tiny and routing decisions are precision-sensitive (a flipped top-k
    is a different program, not a rounding error)."""
    m = lyr.mlp
    from .incubate.moe import MoELayer
    if isinstance(m, MoELayer):
        if m.activation != "swiglu":
            raise NotImplementedError(
                "cached MoE decode supports swiglu experts (the LM configs)")
        if not m.dropless:
            import warnings
            warnings.warn(
                "cached/compiled MoE decode always routes DROPLESS (no "
                "capacity drops — serving never discards tokens); this "
                "model trains in capacity mode, so cached decode can "
                "diverge from generate() near capacity overflow. Exactness "
                "vs the buffer path holds for moe_dropless=True models.",
                stacklevel=3)
        mo = dict(gate=m.gate_weight._data,
                  wge=m.w_gate._data if m.w_gate is not None else None,
                  wup=m.w_up._data, wdn=m.w_down._data)
        for k in ("wge", "wup", "wdn"):
            _q8(mo, k, weight_only_int8, algo)
        if m.e_score_correction_bias is not None:
            mo["bias"] = m.e_score_correction_bias._data
        if m.shared_up is not None:
            sh = dict(sg=m.shared_gate.weight._data,
                      su=m.shared_up.weight._data,
                      sd=m.shared_down.weight._data)
            for k in ("sg", "su", "sd"):
                _q8(sh, k, weight_only_int8, algo)
            mo["shared"] = sh
        st = dict(top_k=m.top_k, renorm=m.renormalize)
        if m.experts_held is not None or m.routed_scale != 1.0:
            # one chip's share of an expert-parallel layer (and the
            # routed scaling factor): static, like the knobs above
            st.update(held=m.experts_held, scale=m.routed_scale)
        if m.score != "softmax":
            st["score"] = m.score
        if m.route_group is not None:
            st["group"] = m.route_group
        return dict(moe=mo), st
    d = dict(wg=m.gate_proj.weight._data, wu=m.up_proj.weight._data,
             wd=m.down_proj.weight._data)
    for k in ("wg", "wu", "wd"):
        _q8(d, k, weight_only_int8, algo)
    return d, None


def _moe_decode_params(model, weight_only_int8: bool = False,
                       algo: str = "weight_only_int8"):
    """MoEForCausalLM (Qwen2-MoE/DeepSeekMoE pattern): llama attention
    backbone, per-layer dense-or-routed FFN. ``weight_only_int8`` cuts
    the HBM weight reads (the expert stacks are the bulk of them) with
    ``algo`` — 'weight_only_int4' packs the 3-D expert stacks two
    nibbles per byte for quarter-width reads — see _llama_decode_params.

    SDARMoeForCausalLM (`models.sdar`) is this family with three
    additions: each layer's ``q_norm`` / ``k_norm`` leaves (a per-head
    RMSNorm of q and k before the rotary turn), routed layers that HOLD
    every expert and say so (``held`` None, ``scale`` 1: the uncut
    layer's numerics, and the routed layers' counts are taken), and rope
    tables made by ``p["rope_fn"](positions)`` once the caller knows how
    many it serves. That it generates by diffusion over blocks is read
    from its ``cfg`` (``block_length``) by whoever generates."""
    inner = model.model
    cfg = model.config
    sdar = hasattr(cfg, "block_length")
    layers = []
    moe_static = []
    for lyr in inner.layers:
        a = lyr.self_attn
        d = dict(
            ln1=lyr.input_layernorm.weight._data,
            wq=a.q_proj.weight._data, wk=a.k_proj.weight._data,
            wv=a.v_proj.weight._data, wo=a.o_proj.weight._data,
            ln2=lyr.post_attention_layernorm.weight._data)
        if sdar:
            d.update(q_norm=a.q_norm.weight._data,
                     k_norm=a.k_norm.weight._data)
        for k in ("wq", "wk", "wv", "wo"):
            _q8(d, k, weight_only_int8, algo)
        _heads_w(d, cfg.head_dim, "wq", "wk", "wv")
        mlp_w, mlp_st = _mlp_params(lyr, weight_only_int8, algo)
        if sdar:
            mlp_st.update(held=None, scale=1.0)
        d.update(mlp_w)
        layers.append(d)
        moe_static.append(mlp_st)
    head = model.lm_head.weight._data if model.lm_head is not None else None
    p = dict(cfg=cfg, family="moe",
             embed=inner.embed_tokens.weight._data,
             layers=layers, norm=inner.norm.weight._data, head=head,
             moe_static=tuple(moe_static))
    if sdar:
        p["rope_fn"] = lambda n: dict(zip(("cos", "sin"),
                                          inner.rope_tables(n)))
    else:
        p.update(cos=inner.rope_cos._data, sin=inner.rope_sin._data)
    if weight_only_int8 and head is not None:
        _q8(p, "head", True, algo)
        p["head"] = None
    return p


def _laguna_decode_params(model):
    """LagunaForCausalLM: a llama-layout weight tree plus, OUTSIDE it
    like ``moe_static``, one STATIC record a layer (``attn_static``):
    query heads, window or None, which rope table (a key suffix of the
    tree's cos / sin).  The serving engine's llama body reads the
    record where other families read ``cfg``; the head gate is there
    where a layer has a ``wgate`` leaf.

    Partial rotary: the engine's rope kernels pair dims (i, i + D/2)
    over the whole head; a rotary width r < D pairs (i, i + r/2).  The
    SAME column permutation is applied here to ``wq`` and ``wk`` inside
    every head — rotary first halves, then pass-through dims, in each
    half of the head — which leaves every q.k unchanged, and the table
    carries identity (cos 1, sin 0) for the pass-through dims
    (`models.laguna.rope_table(kernel_layout=True)`).  V is untouched.

    The rope tables are built by ``p["rope_fn"](positions)`` once the
    caller knows how many positions it serves."""
    from .models.laguna import FULL, SLIDING, rope_inv_freq
    inner, cfg = model.model, model.config
    D = cfg.head_dim
    suffix = {FULL: "", SLIDING: "_local"}  # of a kind's cos / sin keys
    layers, attn_static, moe_static = [], [], []
    for i, lyr in enumerate(inner.layers):
        a = lyr.self_attn
        wq, wk = a.q_proj.weight._data, a.k_proj.weight._data
        r = rope_inv_freq(cfg.rope_parameters[a.kind], D)[2]
        if r < D:
            half, rest = r // 2, (D - r) // 2
            perm = np.concatenate([
                np.arange(half), r + np.arange(rest),
                half + np.arange(half), r + rest + np.arange(rest)])
            wq = wq.reshape(-1, a.heads, D)[..., perm].reshape(wq.shape)
            wk = wk.reshape(-1, cfg.num_key_value_heads, D)[
                ..., perm].reshape(wk.shape)
        d = dict(ln1=lyr.input_layernorm.weight._data, wq=wq, wk=wk,
                 wv=a.v_proj.weight._data, wgate=a.g_proj.weight._data,
                 wo=a.o_proj.weight._data,
                 ln2=lyr.post_attention_layernorm.weight._data)
        _heads_w(d, D, "wq", "wk", "wv")
        mlp_w, mlp_st = _mlp_params(lyr)
        d.update(mlp_w)
        layers.append(d)
        moe_static.append(mlp_st)
        attn_static.append(dict(heads=a.heads, window=a.window,
                                rope=suffix[a.kind]))

    def rope_fn(positions: int):
        out = {}
        for kind, (cos, sin) in inner.rope_tables(
                positions, kernel_layout=True).items():
            out["cos" + suffix[kind]], out["sin" + suffix[kind]] = cos, sin
        return out

    return dict(cfg=cfg, family="laguna",
                embed=inner.embed_tokens.weight._data, layers=layers,
                norm=inner.norm.weight._data,
                head=model.lm_head.weight._data,
                moe_static=tuple(moe_static),
                attn_static=tuple(attn_static), rope_fn=rope_fn)


def _eva_decode_params(model):
    """EvaByteForCausalLM: a llama-layout weight tree whose norm gains
    are ``1 + g`` in float32 (``norm_add_unit_offset``, folded here once)
    and whose layers carry the chunk pooling's per-head ``phi`` and
    ``mu`` [heads, head_dim]; ``head`` is the byte heads side by side."""
    from .models.evabyte import rope_table
    inner, cfg = model.model, model.config

    def gain(norm):
        return 1.0 + norm.weight._data.astype(jnp.float32)

    layers = []
    for lyr in inner.layers:
        a, m = lyr.self_attn, lyr.mlp
        layers.append(dict(
            ln1=gain(lyr.input_layernorm),
            wq=a.q_proj.weight._data, wk=a.k_proj.weight._data,
            wv=a.v_proj.weight._data, wo=a.o_proj.weight._data,
            phi=a.adaptive_phi._data, mu=a.adaptive_mu_k._data,
            ln2=gain(lyr.post_attention_layernorm),
            wg=m.gate_proj.weight._data, wu=m.up_proj.weight._data,
            wd=m.down_proj.weight._data))
        _heads_w(layers[-1], cfg.head_dim, "wq", "wk", "wv")
    return dict(
        cfg=cfg, family="eva", embed=inner.embed_tokens.weight._data,
        layers=layers, norm=gain(inner.norm),
        head=model.lm_head.weight._data,
        rope_fn=lambda n: dict(zip(("cos", "sin"), rope_table(
            cfg.rope_theta, cfg.head_dim, n))))


def _looped_decode_params(model):
    """OuroForCausalLM: a llama-layout weight tree run ``total_ut_steps``
    times a token. Each layer carries four gains (``ln1_out`` and
    ``ln2_out`` norm a sublayer's OUTPUT before the add); ``norm`` closes
    every pass; ``gate_w`` [hidden] and ``gate_b`` [] are the exit gate."""
    from .models.evabyte import rope_table
    inner, cfg = model.model, model.config
    layers = []
    for lyr in inner.layers:
        a, m = lyr.self_attn, lyr.mlp
        layers.append(dict(
            ln1=lyr.input_layernorm.weight._data,
            wq=a.q_proj.weight._data, wk=a.k_proj.weight._data,
            wv=a.v_proj.weight._data, wo=a.o_proj.weight._data,
            ln1_out=lyr.input_layernorm_2.weight._data,
            ln2=lyr.post_attention_layernorm.weight._data,
            wg=m.gate_proj.weight._data, wu=m.up_proj.weight._data,
            wd=m.down_proj.weight._data,
            ln2_out=lyr.post_attention_layernorm_2.weight._data))
        _heads_w(layers[-1], cfg.head_dim, "wq", "wk", "wv")
    gate = inner.early_exit_gate
    return dict(
        cfg=cfg, family="looped", embed=inner.embed_tokens.weight._data,
        layers=layers, norm=inner.norm.weight._data,
        gate_w=gate.weight._data[:, 0], gate_b=gate.bias._data[0],
        head=model.lm_head.weight._data,
        rope_fn=lambda n: dict(zip(("cos", "sin"), rope_table(
            cfg.rope_theta, cfg.head_dim, n))))


def _hybrid_decode_params(model):
    """NemotronHForCausalLM: ONE mixer a block, of the kind its letter of
    ``pattern`` names (static, outside the tree): ``M`` a Mamba-2
    state-space layer, ``*`` attention without rotary, ``E`` a latent
    routed FFN in `_ffn_apply`'s layout.  ``attn_static`` has one record
    for each ATTENTION block (the only ones with pages); ``moe_static``
    one for each ``E`` block."""
    from .models.nemotron_h import arrays
    inner, cfg = model.model, model.config
    layers, moe_static = [], []
    for blk in inner.layers:
        mix = blk.mixer
        d = dict(norm=blk.norm.weight._data)
        if blk.kind == "E":
            d["moe"] = arrays(mix.weights())
            moe_static.append(mix.static())
        else:
            d.update(arrays(mix.weights()))
        if blk.kind == "*":
            _heads_w(d, cfg.head_dim, "wq", "wk", "wv")
        layers.append(d)
    n_attn = cfg.hybrid_override_pattern.count("*")
    return dict(
        cfg=cfg, family="hybrid", pattern=cfg.hybrid_override_pattern,
        embed=inner.embed_tokens.weight._data, layers=layers,
        norm=inner.norm_f.weight._data, head=model.lm_head.weight._data,
        moe_static=tuple(moe_static),
        attn_static=(dict(heads=cfg.num_attention_heads, window=None,
                          rope=""),) * n_attn)


def _falcon_h1_decode_params(model):
    """FalconH1ForCausalLM on the hybrid body: TWO blocks a layer, the
    first with TWO mixers on its one norm — ``[M*]``: a Mamba-2
    state-space mixer and a rotary GQA mixer, both fed the block's
    normed input, their outputs summed into the residual — then ``D``, a
    dense SwiGLU FFN.  ``attn_static`` has one record for each layer
    (every layer has pages AND a state slot).  ``mults`` holds the
    fourteen muP multipliers (`models.falcon_h1.MULTIPLIERS`), static
    scalars the body applies where the model's equations put them; no
    stored weight is scaled."""
    from .models.falcon_h1 import arrays
    inner, cfg = model.model, model.config
    layers = []
    for lyr in inner.layers:
        d = dict(norm=lyr.input_layernorm.weight._data)
        d.update(arrays(lyr.mamba.weights()))
        d.update(arrays(lyr.self_attn.weights()))
        _heads_w(d, cfg.head_dim, "wq", "wk", "wv")
        layers.append(d)
        layers.append(dict(norm=lyr.pre_ff_layernorm.weight._data,
                           **arrays(lyr.feed_forward.weights())))
    return dict(
        cfg=cfg, family="hybrid", pattern=cfg.pattern,
        embed=inner.embed_tokens.weight._data, layers=layers,
        norm=inner.final_layernorm.weight._data,
        head=model.lm_head.weight._data, moe_static=(),
        mults=dict(cfg.multipliers),
        attn_static=(dict(heads=cfg.num_attention_heads, window=None,
                          rope=""),) * cfg.num_hidden_layers,
        rope_fn=lambda n: dict(zip(("cos", "sin"), cfg.rope_table(n))))


def _phi4flash_decode_params(model):
    """Phi4FlashForCausalLM on the hybrid body: TWO blocks a layer, a
    mixer then a dense SwiGLU FFN ``D``, each on its own LayerNorm WITH
    bias (``norm`` / ``norm_b``).  The mixers (`models.phi4flash.
    Phi4FlashConfig.pattern`): ``S`` Mamba-1, ``A_log`` stored turned,
    [N, C], as its pool has the channels along the lanes; ``*``
    differential attention with pages of its own — ``attn_static`` has
    one record for each, the first-half layers' with the window; ``G<j>``
    a gated unit and ``X<j>`` a cross-attention mixer, which own NO
    memory and read block j's scan output / pages.  ``diff`` holds each
    attention mixer's layer index (its ``lambda_init``), by block.  The
    head is the embedding (``head`` None)."""
    from .models.phi4flash import arrays
    inner, cfg = model.model, model.config
    layers, diff, attn_static = [], {}, []
    for l, lyr in enumerate(inner.layers):
        d = dict(norm=lyr.input_layernorm.weight._data,
                 norm_b=lyr.input_layernorm.bias._data,
                 **arrays(lyr.mixer.weights()))
        if lyr.kind == "S":
            d["a_log_t"] = d.pop("A_log").T
        if lyr.kind in "WFX":
            _heads_w(d, cfg.head_dim, "wq", "wk", "wv")
            diff[len(layers)] = l
        if lyr.kind in "WF":
            attn_static.append(dict(
                heads=cfg.num_attention_heads, rope="",
                window=cfg.sliding_window if lyr.kind == "W" else None))
        layers.append(d)
        layers.append(dict(norm=lyr.post_attention_layernorm.weight._data,
                           norm_b=lyr.post_attention_layernorm.bias._data,
                           **arrays(lyr.mlp.weights())))
    return dict(
        cfg=cfg, family="hybrid", pattern=cfg.pattern,
        embed=inner.embed_tokens.weight._data, layers=layers,
        norm=inner.final_layernorm.weight._data,
        norm_b=inner.final_layernorm.bias._data, head=None, moe_static=(),
        diff=diff, attn_static=tuple(attn_static))


def _bailing_decode_params(model):
    """BailingHybridForCausalLM on the hybrid body: TWO blocks a layer,
    of the kinds ``pattern`` spells (static, outside the tree): ``K`` a
    KDA linear-attention mixer (its three convolutions' weights side by
    side, as its tail pool holds their rows), ``L`` a gated
    latent-attention mixer, ``D`` a dense SwiGLU FFN, ``E`` a routed FFN
    in `_ffn_apply`'s layout.  ``attn_static`` has one record for each
    ``L`` block (the only ones with pages); ``moe_static`` one for each
    ``E`` block.

    The published rope turns INTERLEAVED pairs; the engine's turns
    halves.  The same column order is applied here to the rope columns
    of ``wq`` (inside every head) and of ``wkva``, which leaves every
    q.k unchanged (`_laguna_decode_params` does the like)."""
    from .models.bailing_hybrid import arrays, interleaved_to_halves
    inner, cfg = model.model, model.config
    nh, dn, dr, r = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.kv_lora_rank)
    order = interleaved_to_halves(dr)
    layers, moe_static = [], []
    for blk in inner.layers:
        mix = blk.mixer
        d = dict(norm=blk.norm.weight._data)
        if blk.kind == "E":
            d["moe"] = arrays(mix.weights())
            moe_static.append(mix.static())
        else:
            d.update(arrays(mix.weights()))
        if blk.kind == "K":
            d["conv_w"] = jnp.concatenate(
                [d.pop(k) for k in ("q_conv", "k_conv", "v_conv")])
        if blk.kind == "L":
            wq = d["wq"].reshape(-1, nh, dn + dr)
            d["wq"] = jnp.concatenate(
                [wq[..., :dn], wq[..., dn:][..., order]],
                -1).reshape(d["wq"].shape)
            d["wkva"] = jnp.concatenate(
                [d["wkva"][:, :r], d["wkva"][:, r:][:, order]], -1)
            _heads_w(d, dn + dr, "wq")
            _heads_w(d, dn + cfg.v_head_dim, "wkvb")
        layers.append(d)
    return dict(
        cfg=cfg, family="hybrid", pattern=cfg.pattern,
        embed=inner.embed_tokens.weight._data, layers=layers,
        norm=inner.norm.weight._data, head=model.lm_head.weight._data,
        moe_static=tuple(moe_static),
        attn_static=(dict(heads=nh, window=None, rope=""),)
        * cfg.pattern.count("L"),
        rope_fn=lambda n: dict(zip(("cos", "sin"), cfg.rope_table(n))))


def _lfm2_decode_params(model):
    """Lfm2MoeForCausalLM on the hybrid body: TWO blocks a layer, of the
    kinds ``pattern`` spells (static, outside the tree): ``C`` a gated
    short convolution (``w_in`` whose output splits B | C | z, the
    depthwise ``conv_w`` [hidden, K], ``w_out``) whose memory of a
    sequence is the last K - 1 rows of B * z and nothing else; ``*``
    rotary GQA with ``q_norm`` / ``k_norm`` leaves (`_gqa_mixer`); ``D``
    a dense SwiGLU FFN, ``E`` a routed FFN in `_ffn_apply`'s layout —
    sigmoid scores, the ``bias`` that picks, EVERY expert held and said
    so (``held`` None, ``scale``: the routed layers' counts are taken).
    ``attn_static`` has one record for each ``*`` block (the only ones
    with pages); ``moe_static`` one for each ``E`` block.  The head is
    the embedding (``head`` None)."""
    from .models.lfm2 import arrays
    inner, cfg = model.model, model.config
    layers, moe_static = [], []
    for blk in inner.layers:
        d = dict(norm=blk.norm.weight._data)
        if blk.kind in "DE":
            mlp_w, st = _mlp_params(blk)
            d.update(mlp_w)
            if st is not None:
                st.update(held=None, scale=cfg.routed_scaling_factor)
                moe_static.append(st)
        else:
            d.update(arrays(blk.mixer.weights()))
        if blk.kind == "*":
            _heads_w(d, cfg.head_dim, "wq", "wk", "wv")
        layers.append(d)
    return dict(
        cfg=cfg, family="hybrid", pattern=cfg.pattern,
        embed=inner.embed_tokens.weight._data, layers=layers,
        norm=inner.embedding_norm.weight._data, head=None,
        moe_static=tuple(moe_static),
        attn_static=(dict(heads=cfg.num_attention_heads, window=None,
                          rope=""),) * cfg.pattern.count("*"),
        rope_fn=lambda n: dict(zip(("cos", "sin"), cfg.rope_table(n))))


def _mla_decode_params(model, weight_only_int8: bool = False,
                       algo: str = "weight_only_int8"):
    """DeepSeekV2ForCausalLM: multi-head latent attention with the
    ABSORBED decode formulation — the KV cache stores only the normalized
    latent [r] + shared rope key [dr] per token, and kv_b is folded into
    the query/output projections (DeepSeek-V2 matrix absorption; ref
    capability: PaddleNLP deepseek_v2 fused MLA decode).

    ``algo`` applies to every quantized leaf: 'weight_only_int4' packs
    the attention projections (kv_b reads whole through
    ops.quant.int4_dequantize; the rest through _mm_w's split
    contraction) AND the FFN/expert stacks — 3-D packed stacks read
    whole through _dq's plane-interleave dequant (density win: the
    stored stack is quarter-width)."""
    inner = model.model
    cfg = model.config
    if cfg.hc_mult > 1 and weight_only_int8:
        raise NotImplementedError(
            "weight-only quantisation is not wired for a hyper-connected "
            "residual (hc_mult > 1: the Xing family)")
    from .ops.references import mhc_pack
    layers = []
    moe_static = []
    for lyr in inner.layers:
        a = lyr.self_attn
        d = dict(
            ln1=lyr.input_layernorm.weight._data,
            wkva=a.kv_a_proj_with_mqa.weight._data,
            gkv=a.kv_a_layernorm.weight._data,
            wkvb=a.kv_b_proj.weight._data,
            wo=a.o_proj.weight._data,
            ln2=lyr.post_attention_layernorm.weight._data)
        if cfg.q_lora_rank:
            d["wqa"] = a.q_a_proj.weight._data
            d["gq"] = a.q_a_layernorm.weight._data
            d["wqb"] = a.q_b_proj.weight._data
        else:
            d["wq"] = a.q_proj.weight._data
        for k in ("wkva", "wkvb", "wo", "wqa", "wqb", "wq"):
            if k in d:
                _q8(d, k, weight_only_int8, algo)
        _heads_w(d, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
                 "wqb", "wq")
        _heads_w(d, cfg.qk_nope_head_dim + cfg.v_head_dim, "wkvb")
        mlp_w, mlp_st = _mlp_params(lyr, weight_only_int8, algo)
        d.update(mlp_w)
        if cfg.hc_mult > 1:
            # a residual of hc_mult streams: each sublayer's mixing
            # weights as the step's kernels read them (`phi` turned, a
            # group of coefficients a sublane tile: `ops.pallas_mhc`)
            for key, hc in (("hc1", lyr.hc_attn), ("hc2", lyr.hc_ffn)):
                d[key] = dict(zip(("phi_t", "ab"), mhc_pack(
                    hc.phi._data, hc.b._data, hc.a._data, cfg.hc_mult)))
        layers.append(d)
        moe_static.append(mlp_st)
    head = model.lm_head.weight._data if model.lm_head is not None else None
    p = dict(cfg=cfg, family="mla",
             embed=inner.embed_tokens.weight._data,
             layers=layers, norm=inner.norm.weight._data, head=head,
             cos=inner.rope_cos._data, sin=inner.rope_sin._data,
             moe_static=tuple(moe_static))
    if cfg.rope_positions < cfg.max_position_embeddings:
        # the model's own table stops at rope_positions; a serving
        # engine builds one to the positions it serves
        p["rope_fn"] = lambda n: dict(zip(("cos", "sin"),
                                          cfg.rope_table(n)))
    if weight_only_int8 and head is not None:
        _q8(p, "head", True, algo)
        p["head"] = None
    return p


def _decode_params(model, weight_only_int8: bool = False,
                   weight_only_quant=None):
    """Family dispatch for the cached/compiled decode paths. int4 covers
    the llama, MoE and MLA families end-to-end: 2-D projections contract
    through _mm_w's even/odd split (or read whole through
    int4_dequantize — the MLA kv_b), and the 3-D MoE expert stacks pack
    per expert and read back through _dq's plane-interleave. The GPT
    family stays fp (its fused-qkv + bias layout is not wired through
    the quant matmul helper)."""
    algo, enabled = _woq_algo(weight_only_int8, weight_only_quant)
    if getattr(model, "gpt", None) is not None:
        if enabled:
            raise NotImplementedError(
                "weight_only_int8 decode covers the llama/MoE/MLA "
                "families; the GPT family is fp (its fused-qkv + bias "
                "layout is not wired through the quant matmul helper)")
        return _gpt_decode_params(model)
    inner = getattr(model, "model", None)
    if inner is not None:
        from .models.deepseek import DeepSeekV2Model
        from .models.evabyte import EvaByteModel
        from .models.laguna import LagunaModel
        from .models.moe_llm import MoEModel
        from .models.bailing_hybrid import BailingHybridModel
        from .models.falcon_h1 import FalconH1Model
        from .models.lfm2 import Lfm2MoeModel
        from .models.nemotron_h import NemotronHModel
        from .models.ouro import OuroModel
        from .models.phi4flash import Phi4FlashModel
        from .models.sdar import SDARMoeModel
        if isinstance(inner, (NemotronHModel, BailingHybridModel,
                              FalconH1Model, Phi4FlashModel,
                              Lfm2MoeModel)):
            if enabled:
                raise NotImplementedError(
                    "weight-only quantisation is not wired for the "
                    "Nemotron-H, Ling (bailing_hybrid), Falcon-H1, "
                    "Phi-4-flash and LFM2 families")
            if isinstance(inner, Lfm2MoeModel):
                return _lfm2_decode_params(model)
            if isinstance(inner, Phi4FlashModel):
                return _phi4flash_decode_params(model)
            if isinstance(inner, BailingHybridModel):
                return _bailing_decode_params(model)
            if isinstance(inner, FalconH1Model):
                return _falcon_h1_decode_params(model)
            return _hybrid_decode_params(model)
        if isinstance(inner, OuroModel):
            if enabled:
                raise NotImplementedError(
                    "weight-only quantisation is not wired for the "
                    "Ouro family")
            return _looped_decode_params(model)
        if isinstance(inner, EvaByteModel):
            if enabled:
                raise NotImplementedError(
                    "weight-only quantisation is not wired for the "
                    "EvaByte family")
            return _eva_decode_params(model)
        if isinstance(inner, LagunaModel):
            if enabled:
                raise NotImplementedError(
                    "weight-only quantisation is not wired for the "
                    "Laguna family")
            return _laguna_decode_params(model)
        if isinstance(inner, DeepSeekV2Model):
            return _mla_decode_params(model, enabled, algo)
        if isinstance(inner, (MoEModel, SDARMoeModel)):
            return _moe_decode_params(model, enabled, algo)
    return _llama_decode_params(model, weight_only_int8,
                                weight_only_quant)


def _llama_weights(p):
    """The traced-argument slice of _llama_decode_params: weights enter
    jit as ARGUMENTS, never as closures — a closed-over device array is
    embedded in the lowered module as a literal constant, and at 8B-shard
    scale (~0.5 GB) that makes XLA chew through the weights at compile
    time."""
    return {k: v for k, v in p.items()
            if k not in ("cfg", "family", "moe_static", "attn_static",
                         "rope_fn", "pattern", "mults", "diff")}


def _dq(d, key, dtype):
    """Read an optionally-quantized weight entry WHOLE (for consumers
    that reshape/slice it, e.g. the MLA kv_b or 3-D expert stacks, where
    _mm_w's fused matmul shape doesn't apply): int8 layouts dequantize
    in VMEM — the HBM read stays int8 and XLA fuses the scale multiply
    into the consuming einsum. 3-D stacks carry per-(expert, out-channel)
    scales [E, N]. 2-D int4 (_q4) entries unpack through the
    ops.quant.int4_dequantize Pallas kernel (the HBM read stays packed;
    the MLA absorbed kv_b rides this); 3-D packed stacks [E, K/2, N]
    interleave their sign-extended nibble planes back to source-row
    order (the same row order weight_dequantize writes) and scale per
    (expert, out-channel) — int4's recorded win here is DENSITY (the
    stored stack is quarter-width), not speed: the per-expert einsum
    consumers materialize the planes either way."""
    if key + "_q4" in d:
        q4, s = d[key + "_q4"], d[key + "_s"]
        if q4.ndim == 3:
            from .ops.quant import int4_planes
            lo, hi = int4_planes(q4)                    # [E, K/2, N]
            E, K2, N = q4.shape
            w = jnp.stack([lo, hi], axis=2).reshape(E, K2 * 2, N)
            return (w.astype(jnp.float32)
                    * s[:, None, :].astype(jnp.float32)).astype(dtype)
        from .ops.quant import int4_dequantize
        return int4_dequantize(q4, s).astype(dtype)
    if key + "_q" in d:
        q, s = d[key + "_q"], d[key + "_s"].astype(dtype)
        if q.ndim == 3:
            return q.astype(dtype) * s[:, None, :]
        return q.astype(dtype) * s
    return d[key]


def _int4_halves(q4, s):
    """Sign-extended nibble planes of a packed int4 weight, scaled:
    (lo, hi) each [K/2, N] — h @ W == h[..., 0::2] @ lo + h[..., 1::2]
    @ hi. Pure elementwise on the packed bytes, so XLA fuses the unpack
    into the dot operand loads (the same fusion that makes int8
    weight-only decode win); nothing bf16-sized ever hits HBM."""
    from .ops.quant import int4_planes
    lo, hi = int4_planes(q4)
    return lo.astype(s.dtype) * s, hi.astype(s.dtype) * s


def _mm_w(h, L, key):
    """Quant-aware matmul against a stored weight: weight-only int8
    layouts hold (key_q int8, key_s per-channel f32) and dequantize in
    VMEM right before the matmul (the HBM read is int8 — half the bf16
    bytes that bound decode); fp layouts hold the key directly. The ONE
    place both layouts' matmul goes through. Packed-int4 layouts
    (key_q4) contract even/odd input rows against the nibble planes so
    the unpack fuses into the dot operand loads (_int4_halves).
    Every weight here is [K, N] = [in, out]; a projection whose output
    is split into heads goes through `_mm_heads`, whose float leaf is
    stored [heads, head_dim, in]."""
    if key + "_q4" in L:
        # in-kernel unpack for ANY N: packed int4 is the only weight HBM
        # traffic (XLA cannot fuse the shift chain into the MXU feed, so
        # a host-side plane split materializes bf16 planes and runs at
        # bf16 speed — measured r5). Non-128-aligned N (the vocab-16032
        # head) is zero-padded inside the kernel launch and sliced back.
        from .ops.quant import weight_only_linear
        return weight_only_linear(h, L[key + "_q4"], L[key + "_s"],
                                  algo="weight_only_int4")
    return h @ _dq(L, key, h.dtype)


def _mm_heads(h, L, key):
    """h @ W [..., heads * D] of a projection whose output is split into
    heads: a float leaf is stored [heads, D, in] (`_heads_w`) and
    contracted on its last axis — the same operands, contraction and
    accumulation as `h @ W`, read the way the compiled dot reads them; a
    quantized pair kept [K, N] and goes through `_mm_w`. The ONE reader
    of those leaves."""
    if key in L:
        y = jax.lax.dot_general(h, L[key], (((h.ndim - 1,), (2,)), ((), ())))
        return y.reshape(*h.shape[:-1], -1)
    return _mm_w(h, L, key)


def _kvb_heads(L, nh: int, dtype):
    """The latent family's kv_b as [heads, dn + dv, r] — head a's W^K
    rows then its W^V rows, ``r`` minor — for the absorbed form's two
    einsums: the float leaf as stored (`_heads_w`), or a quantized pair
    read whole ([r, out], `_dq`) and turned."""
    if "wkvb" in L:
        return L["wkvb"]
    w = _dq(L, "wkvb", dtype)
    return w.reshape(w.shape[0], nh, -1).transpose(1, 2, 0)


def _ffn_apply(L, h2, st=None, stats=None, live=None):
    """Per-layer FFN on [B, S, H]: dense SwiGLU (fp or weight-only int8)
    or routed-MoE (dropless per-token top-k — numerics match
    MoELayer._dropless exactly so the cached path exact-matches a
    moe_dropless buffer model). ``st`` holds the layer's STATIC routing
    knobs (top_k, renorm; held, scale where the layer is one chip's
    share of an expert-parallel one; score, group where the router is
    not a softmax top-k; act "relu2" where the experts are two matrices)
    from _mlp_params; for a DENSE layer it may hold the two multipliers
    ``mlp_gate`` (on the gate's pre-activation) and ``mlp_down`` (on the
    output). ``L["moe"]`` may hold a correction ``bias`` [E]
    of the choice and the latent projections ``lat_dn`` / ``lat_up``
    around the experts (Nemotron-H). A routed layer
    appends its `moe.routing_stats` to the list ``stats``, counted over
    the rows that ``live`` [B * S] marks (all, if None)."""
    if "moe" not in L:
        with jax.named_scope("ffn"):
            if st is not None:  # a dense FFN's multipliers (Falcon-H1)
                return _mm_w(
                    jax.nn.silu(_mm_w(h2, L, "wg") * st["mlp_gate"])
                    * _mm_w(h2, L, "wu"), L, "wd") * st["mlp_down"]
            return _mm_w(jax.nn.silu(_mm_w(h2, L, "wg"))
                         * _mm_w(h2, L, "wu"), L, "wd")
    mo = L["moe"]
    B, S, H = h2.shape
    T = B * S
    xt = h2.reshape(T, H)
    from .incubate.moe import (dense_expert_ffn, dropless_expert_ffn,
                               routing_stats)
    # decode steps (tiny T): every-expert dense compute beats the
    # sort+grouped-GEMM path (128-row tile padding) and is bitwise-equal
    ffn = dense_expert_ffn if T <= 32 else dropless_expert_ffn
    dt = h2.dtype
    share = {k: st[k] for k in ("held", "scale", "group") if k in st}
    act = st.get("act", "swiglu")
    if "bias" in mo:
        # a per-expert correction of the CHOICE (`moe._route`)
        share["bias"] = mo["bias"].astype(jnp.float32)
    # stable scopes in the ops' metadata, for whoever reads a trace
    with jax.named_scope("routed_ffn"):
        gates = xt.astype(jnp.float32) @ mo["gate"].astype(jnp.float32)
        gates = jax.nn.sigmoid(gates) if st.get("score") == "sigmoid" \
            else jax.nn.softmax(gates, axis=-1)
        if "lat_dn" in mo:
            # experts in a latent: the router read the full width, the
            # experts read (and write) the down-projected row
            with jax.named_scope("latent_proj"):
                xt = xt @ _dq(mo, "lat_dn", dt)
        y, topi = ffn(xt, gates,
                      _dq(mo, "wge", dt) if act == "swiglu" else None,
                      _dq(mo, "wup", dt), _dq(mo, "wdn", dt),
                      top_k=st["top_k"], renormalize=st["renorm"],
                      activation=act, **share)
        if stats is not None:
            stats.append(routing_stats(topi, st.get("held"),
                                       gates.shape[-1], live))
        if "lat_up" in mo:
            with jax.named_scope("latent_proj"):
                y = y.astype(dt) @ _dq(mo, "lat_up", dt)
        y = y.reshape(B, S, H).astype(h2.dtype)
    if "shared" in mo:
        sh = mo["shared"]
        with jax.named_scope("shared_expert"):
            if "sg" in sh or "sg_q" in sh or "sg_q4" in sh:
                s = jax.nn.silu(h2 @ _dq(sh, "sg", dt)) \
                    * (h2 @ _dq(sh, "su", dt))
            else:       # two matrices: relu(x U)^2 V
                s = jnp.square(jax.nn.relu(h2 @ _dq(sh, "su", dt)))
            y = y + s @ _dq(sh, "sd", dt)
    return y


def _llama_cached_step_body(cfg, max_len: int, moe_static=None):
    """Un-jitted (weights, ids_step, caches, start_pos) ->
    (last_logits, caches) body — jitted per-call-width by
    _make_cached_step for the host-loop path, traced inside one
    scan by generate_compiled."""
    Hh, KV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    eps = cfg.rms_norm_eps
    from .models.llama import apply_rope
    from .flags import flag, flags_guard
    # prefill routes through sdpa, whose kernel choice reads
    # FLAGS_flash_impl at trace time — pin it at construction so the
    # program matches _DECODE_LOOP_CACHE's key (same lazy-trace hazard
    # as the mla impl flag, review r5)
    flash_impl = flag("FLAGS_flash_impl")

    def rms(h, w):
        var = jnp.mean(jnp.square(h.astype(jnp.float32)), -1, keepdims=True)
        return (h * jax.lax.rsqrt(var + eps).astype(h.dtype)) * w

    def step(w, ids, caches, start):
        B, S = ids.shape
        x = w["embed"][ids]
        cos = jax.lax.dynamic_slice_in_dim(w["cos"], start, S, 0)
        sin = jax.lax.dynamic_slice_in_dim(w["sin"], start, S, 0)
        new_caches = []
        pos_k = jnp.arange(max_len)
        q_pos = start + jnp.arange(S)
        # key j visible to query i iff j <= start + i
        vis = pos_k[None, :] <= q_pos[:, None]            # [S, max_len]
        sts = moe_static or (None,) * len(w["layers"])
        for L, (ck, cv), st in zip(w["layers"], caches, sts):
            h = rms(x, L["ln1"])
            q, k, v = (_mm_heads(h, L, w)
                       for w in ("wq", "wk", "wv"))
            if "bq" in L:                      # Qwen2 qkv biases
                q, k, v = q + L["bq"], k + L["bk"], v + L["bv"]
            q = q.reshape(B, S, Hh, D)
            k = k.reshape(B, S, KV, D)
            v = v.reshape(B, S, KV, D)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            ck = jax.lax.dynamic_update_slice(ck, k, (0, start, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v, (0, start, 0, 0))
            new_caches.append((ck, cv))
            rep = Hh // KV
            if S > 1 and isinstance(start, int) and start == 0:
                # prefill-from-zero: the cache holds nothing but this
                # window, so attend causally over the fresh k/v through
                # the flash route — the dense path below materializes
                # [*, S, max_len] f32 scores, which both OOMs long
                # contexts and wastes the (max_len - S) masked columns
                # (same routing as the buffer-model forward)
                from .ops.flash_attention import sdpa_prefill
                kr = jnp.repeat(k, rep, 2) if rep > 1 else k
                vr = jnp.repeat(v, rep, 2) if rep > 1 else v
                # trace-time pin of the kernel route for this compiled
                # step; re-applied on every retrace by construction.
                # sdpa_prefill pads non-128-multiple prompts through the
                # segment-id flash kernel instead of the dense fallback.
                with flags_guard(flash_impl=flash_impl):  # paddlelint: disable=PT005
                    o = sdpa_prefill(q, kr, vr,
                                     causal=True).reshape(B, S, Hh * D)
            elif rep > 1:
                # GQA WITHOUT materializing jnp.repeat of the cache: the
                # repeat wrote+read rep x the KV bytes per step — at the
                # MoE serving shape (16q/4kv, 8 layers) that was ~0.8 GB
                # of pure overhead against 1.5 GB of weights, the bulk of
                # the missing moe_decode roofline (VERDICT r4 item 2).
                # Group q as [B,S,KV,rep,D] and batch the dot over the kv
                # head so each cache byte is read exactly once.
                qg = q.reshape(B, S, KV, rep, D)
                scores = jnp.einsum("bsgrd,btgd->bgrst", qg, ck) \
                    * (D ** -0.5)
                scores = jnp.where(vis[None, None, None],
                                   scores.astype(jnp.float32), -1e30)
                aw = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
                o = jnp.einsum("bgrst,btgd->bsgrd", aw, cv).reshape(
                    B, S, Hh * D)
            else:
                scores = jnp.einsum("bshd,bthd->bhst", q, ck) * (D ** -0.5)
                scores = jnp.where(vis[None, None],
                                   scores.astype(jnp.float32), -1e30)
                aw = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
                o = jnp.einsum("bhst,bthd->bshd", aw, cv).reshape(
                    B, S, Hh * D)
            x = x + _mm_w(o, L, "wo")
            h2 = rms(x, L["ln2"])
            x = x + _ffn_apply(L, h2, st)
        x = rms(x, w["norm"])
        last = x[:, -1]
        if "head_q" in w or "head_q4" in w:
            logits = _mm_w(last, w, "head")
        else:
            logits = last @ (w["head"] if w["head"] is not None
                             else w["embed"].T)
        return logits, new_caches

    return step


def _gpt_cached_step_body(cfg, max_len: int):
    """GPT analog of _llama_cached_step_body: learned positions, LN with
    bias, fused qkv, GELU MLP; MHA cache (KV heads == q heads)."""
    nh, hd = cfg.num_attention_heads, cfg.head_dim
    eps = cfg.layer_norm_eps
    from .flags import flag, flags_guard
    flash_impl = flag("FLAGS_flash_impl")   # see _llama_cached_step_body

    def ln(h, wt, b):
        h32 = h.astype(jnp.float32)
        mu = jnp.mean(h32, -1, keepdims=True)
        var = jnp.var(h32, -1, keepdims=True)
        return (((h32 - mu) * jax.lax.rsqrt(var + eps))
                .astype(h.dtype) * wt + b)

    def step(w, ids, caches, start):
        B, S = ids.shape
        x = w["embed"][ids] + jax.lax.dynamic_slice_in_dim(
            w["pos"], start, S, 0)[None]
        pos_k = jnp.arange(max_len)
        q_pos = start + jnp.arange(S)
        vis = pos_k[None, :] <= q_pos[:, None]            # [S, max_len]
        new_caches = []
        for L, (ck, cv) in zip(w["layers"], caches):
            h = ln(x, L["ln1w"], L["ln1b"])
            qkv = h @ L["wqkv"] + L["bqkv"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, S, nh, hd)
            k = k.reshape(B, S, nh, hd)
            v = v.reshape(B, S, nh, hd)
            ck = jax.lax.dynamic_update_slice(ck, k, (0, start, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v, (0, start, 0, 0))
            new_caches.append((ck, cv))
            if S > 1 and isinstance(start, int) and start == 0:
                # flash prefill — see _llama_cached_step_body
                from .ops.flash_attention import sdpa_prefill
                # trace-time pin, re-applied on every retrace
                with flags_guard(flash_impl=flash_impl):  # paddlelint: disable=PT005
                    o = sdpa_prefill(q, k, v, causal=True).reshape(B, S, -1)
            else:
                scores = jnp.einsum("bshd,bthd->bhst", q, ck) \
                    * (hd ** -0.5)
                scores = jnp.where(vis[None, None],
                                   scores.astype(jnp.float32), -1e30)
                aw = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
                o = jnp.einsum("bhst,bthd->bshd", aw, cv).reshape(B, S, -1)
            x = x + (o @ L["wo"] + L["bo"])
            h2 = ln(x, L["ln2w"], L["ln2b"])
            x = x + (jax.nn.gelu(h2 @ L["wi"] + L["bi"],
                                 approximate=True) @ L["wf"] + L["bf"])
        x = ln(x, w["normw"], w["normb"])
        last = x[:, -1]
        logits = last @ (w["head"] if w["head"] is not None
                         else w["embed"].T)
        return logits, new_caches

    return step


def _mla_cached_step_body(cfg, max_len: int, moe_static=None):
    """DeepSeek-V2 MLA cached decode with matrix absorption: the cache per
    token is (normalized latent [r], rope key [dr]) — kv_lora_rank + dr
    floats instead of nh*(dn+dv). kv_b is folded into the score (q_nope @
    W_k absorbed onto the latent) and the output (attention over latents,
    W_v applied after). Ref: DeepSeek-V2 inference optimization; PaddleNLP
    deepseek_v2 decode (SURVEY §2.4)."""
    nh = cfg.num_attention_heads
    dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                  cfg.v_head_dim)
    r = cfg.kv_lora_rank
    eps = cfg.rms_norm_eps
    from .models.llama import apply_rope
    # the impl flag is pinned at BODY-CONSTRUCTION time: jax.jit traces
    # lazily at first call, and _DECODE_LOOP_CACHE keys on the flag as
    # read when the loop is built — a trace-time read could cache the
    # other impl's program under this key (review r5)
    from .flags import flag, flags_guard
    impl = flag("FLAGS_mla_decode_impl")
    flash_impl = flag("FLAGS_flash_impl")   # see _llama_cached_step_body

    def rms(h, w):
        var = jnp.mean(jnp.square(h.astype(jnp.float32)), -1, keepdims=True)
        return (h * jax.lax.rsqrt(var + eps).astype(h.dtype)) * w

    def step(w, ids, caches, start):
        B, S = ids.shape
        x = w["embed"][ids]
        cos = jax.lax.dynamic_slice_in_dim(w["cos"], start, S, 0)
        sin = jax.lax.dynamic_slice_in_dim(w["sin"], start, S, 0)
        pos_k = jnp.arange(max_len)
        q_pos = start + jnp.arange(S)
        vis = pos_k[None, :] <= q_pos[:, None]            # [S, max_len]
        scale = cfg.softmax_scale
        use_fused = False
        if S == 1 and impl != "xla":
            from .ops import pallas_mla
            use_fused = (impl == "fused"
                         or pallas_mla.mla_kernel_eligible(nh, r, dr))
        new_caches = []
        sts = moe_static or (None,) * len(w["layers"])
        for L, (c_lat, c_pe), st in zip(w["layers"], caches, sts):
            h = rms(x, L["ln1"])
            if "wqa" in L or "wqa_q" in L or "wqa_q4" in L:
                q = _mm_heads(rms(_mm_w(h, L, "wqa"), L["gq"]), L, "wqb")
            else:
                q = _mm_heads(h, L, "wq")
            q = q.reshape(B, S, nh, dn + dr)
            q_nope, q_pe = q[..., :dn], q[..., dn:]
            q_pe = apply_rope(q_pe, cos, sin)

            kv_a = _mm_w(h, L, "wkva")                    # [B, S, r+dr]
            lat = rms(kv_a[..., :r], L["gkv"])            # normalized latent
            k_pe = apply_rope(kv_a[..., r:][:, :, None, :], cos, sin)[:, :, 0]

            c_lat = jax.lax.dynamic_update_slice(c_lat, lat, (0, start, 0))
            c_pe = jax.lax.dynamic_update_slice(c_pe, k_pe, (0, start, 0))
            new_caches.append((c_lat, c_pe))

            if S > 1 and isinstance(start, int) and start == 0:
                # prefill-from-zero in the NON-absorbed form (k/v heads
                # materialized once — reassociation of the same math) so
                # the flash route applies; the absorbed dense path below
                # materializes [B,nh,S,max_len] f32 scores, which OOMs
                # long-context prefill (matches models/deepseek.py
                # forward, incl. the padded-head route for dv != dn+dr)
                from .ops.flash_attention import sdpa_padded_heads
                kv = _mm_heads(lat, L, "wkvb").reshape(B, S, nh, dn + dv)
                k_h = jnp.concatenate(
                    [kv[..., :dn],
                     jnp.broadcast_to(k_pe[:, :, None, :], (B, S, nh, dr))],
                    -1)
                q_h = jnp.concatenate([q_nope, q_pe], -1)
                # trace-time pin, re-applied on every retrace
                with flags_guard(flash_impl=flash_impl):  # paddlelint: disable=PT005
                    o_v = sdpa_padded_heads(q_h, k_h, kv[..., dn:],
                                            causal=True, scale=scale)
                x = x + _mm_w(o_v.reshape(B, S, nh * dv), L, "wo")
                h2 = rms(x, L["ln2"])
                x = x + _ffn_apply(L, h2, st)
                continue
            wkb = _kvb_heads(L, nh, x.dtype)
            w_k, w_v = wkb[:, :dn], wkb[:, dn:]
            # absorb W_k onto the query: score = q_eff . latent + q_pe . k_pe
            q_eff = jnp.einsum("bsnd,ndr->bsnr", q_nope, w_k)
            if use_fused:
                # single-read fused decode: each latent-cache byte feeds
                # the score AND the output from one VMEM tile (the XLA
                # einsum pair below reads c_lat twice across the softmax
                # barrier — the measured 0.09 roofline residual)
                lens = jnp.full((B,), start + 1, jnp.int32)
                o_lat = pallas_mla.mla_decode_attention(
                    q_eff[:, 0], q_pe[:, 0], c_lat, c_pe, lens,
                    scale=scale)[:, None]
            else:
                scores = (jnp.einsum("bsnr,btr->bnst", q_eff, c_lat)
                          + jnp.einsum("bsnd,btd->bnst", q_pe, c_pe)) * scale
                scores = jnp.where(vis[None, None],
                                   scores.astype(jnp.float32), -1e30)
                aw = jax.nn.softmax(scores, axis=-1).astype(c_lat.dtype)
                o_lat = jnp.einsum("bnst,btr->bsnr", aw, c_lat)
            o = jnp.einsum("bsnr,nvr->bsnv", o_lat, w_v)
            x = x + _mm_w(o.reshape(B, S, nh * dv), L, "wo")
            h2 = rms(x, L["ln2"])
            x = x + _ffn_apply(L, h2, st)
        x = rms(x, w["norm"])
        last = x[:, -1]
        if "head_q" in w or "head_q4" in w:
            logits = _mm_w(last, w, "head")
        else:
            logits = last @ (w["head"] if w["head"] is not None
                             else w["embed"].T)
        return logits, new_caches

    return step


def _cached_step_body(p, max_len: int):
    if p["family"] == "laguna":
        raise NotImplementedError(
            "the Laguna family (per-layer heads, windows, two rope "
            "tables) decodes through serving.ServingEngine; the "
            "contiguous-cache generate_cached / generate_compiled "
            "bodies read one head count and one table")
    if p["family"] == "eva":
        raise NotImplementedError(
            "the EvaByte family (chunk-summary attention: pooled rows "
            "beside a tumbling window) decodes through "
            "serving.ServingEngine; the contiguous-cache bodies keep "
            "one row a token")
    if p["family"] == "looped":
        raise NotImplementedError(
            "the Ouro family (the layer list run total_ut_steps times, a "
            "cache row for every pass of every layer) decodes through "
            "serving.ServingEngine; the contiguous-cache bodies keep "
            "one row a layer")
    if p["family"] == "hybrid":
        raise NotImplementedError(
            "a hybrid family (Nemotron-H, Ling, Falcon-H1, Phi-4-flash: "
            "mixers whose memory of a sequence is a slot of recurrent "
            "state; LFM2: a convolution's last rows) beside the attention "
            "mixers' pages decodes through serving.ServingEngine; the "
            "contiguous-cache bodies keep K/V rows only")
    if p["family"] == "gpt":
        return _gpt_cached_step_body(p["cfg"], max_len)
    if p["family"] == "mla" and p["cfg"].hc_mult > 1:
        raise NotImplementedError(
            "the Xing family (a residual of hc_mult streams mixed by "
            "hyper-connections) runs through ServingEngine's unified step "
            "only; generate_cached / generate_compiled have no such body")
    if p["family"] == "mla":
        return _mla_cached_step_body(p["cfg"], max_len,
                                     p.get("moe_static"))
    return _llama_cached_step_body(p["cfg"], max_len, p.get("moe_static"))


def _init_caches(p, B: int, total: int):
    """Family-shaped zero KV caches for one sequence batch."""
    cfg = p["cfg"]
    dt = p["embed"].dtype
    n_layers = len(p["layers"])
    if p["family"] == "gpt":
        nh, hd = cfg.num_attention_heads, cfg.head_dim
        return [(jnp.zeros((B, total, nh, hd), dt),
                 jnp.zeros((B, total, nh, hd), dt))
                for _ in range(n_layers)]
    if p["family"] == "mla":
        return [(jnp.zeros((B, total, cfg.kv_lora_rank), dt),
                 jnp.zeros((B, total, cfg.qk_rope_head_dim), dt))
                for _ in range(n_layers)]
    KV, D = cfg.num_key_value_heads, cfg.head_dim
    return [(jnp.zeros((B, total, KV, D), dt),
             jnp.zeros((B, total, KV, D), dt))
            for _ in range(n_layers)]


def _make_cached_step(p, max_len: int):
    """Jitted cached step: one compile per distinct step width (prefill
    S0, decode 1). Weights ride as jit arguments (see _llama_weights).
    A multi-token call at start=0 pins start STATICALLY so the body can
    take the flash prefill route (O(S) memory) instead of the dense
    [S, max_len] score path; decode keeps start traced (no retrace per
    position)."""
    w = _llama_weights(p)
    body = _cached_step_body(p, max_len)
    jit_dec = jax.jit(body)
    jit_pre = jax.jit(lambda w, ids, caches: body(w, ids, caches, 0))

    def call(ids, caches, start):
        if ids.shape[1] > 1 and isinstance(start, int) and start == 0:
            return jit_pre(w, ids, caches)
        return jit_dec(w, ids, caches, start)
    return call


def generate_cached(model, input_ids, max_new_tokens: int = 20,
                    decode_strategy: str = "sampling",
                    top_k: Optional[int] = None, top_p: Optional[float] = None,
                    temperature: float = 1.0,
                    eos_token_id: Optional[int] = None, pad_token_id: int = 0,
                    weight_only_int8: bool = False,
                    weight_only_quant=None,
                    deadline_s: Optional[float] = None):
    """KV-cache generation for LlamaForCausalLM-family models: prefill once
    over the prompt, then O(1) work per new token (the compiled-decode
    analog of the reference's masked_multihead_attention loop).
    ``deadline_s``: per-request wall-clock budget — see generate().

    Numerics note: matches the buffer path exactly under f32 matmul
    precision; under the TPU bf16 default the two paths may argmax-flip
    near-tied logits (same situation as the reference's fp16 decode
    kernels vs the fp32 training graph). MoE models: decode always routes
    DROPLESS (serving never discards tokens), so exactness vs generate()
    holds for moe_dropless=True models; capacity-mode models get a
    warning (drops are a training-time regularizer).
    """
    _refuse_block_diffusion(model, "generate_cached")
    if decode_strategy not in ("greedy_search", "sampling"):
        raise ValueError(f"decode_strategy {decode_strategy!r}: expected "
                         "'greedy_search' or 'sampling'")
    p = _decode_params(model, weight_only_int8, weight_only_quant)
    cfg = p["cfg"]
    ids = input_ids._data if isinstance(input_ids, Tensor) \
        else jnp.asarray(input_ids)
    ids = ids.astype(jnp.int32)
    B, S0 = ids.shape
    total = S0 + max_new_tokens
    if total > cfg.max_position_embeddings:
        raise ValueError(f"{total} tokens exceed max_position_embeddings")
    caches = _init_caches(p, B, total)
    step = _make_cached_step(p, total)
    finished = jnp.zeros((B,), bool)
    out_tokens, out_scores = [], []
    dl = _res.Deadline(deadline_s) if deadline_s else None
    timed_out = False
    mx = _obs.enabled()
    if mx:
        _SRV_REQS.labels(path="cached").inc()
        _SRV_BATCH.observe(B)
        _SRV_PREFILL_TOK.inc(B * S0)
    import time as _time
    with ag.no_grad():
        t0 = _time.perf_counter() if mx else 0.0
        logits, caches = step(ids, caches, 0)          # prefill
        if mx:
            _SRV_PREFILL_S.labels(path="cached").observe(
                _time.perf_counter() - t0)
            t0 = _time.perf_counter()
        pos = S0
        while pos < total:
            tok = _sample_token(logits, decode_strategy, top_k, top_p,
                                temperature)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            score = jnp.take_along_axis(logp, tok[:, None], -1)[:, 0]
            if eos_token_id is not None:
                tok = jnp.where(finished, pad_token_id, tok)
                score = jnp.where(finished, 0.0, score)
                finished = finished | (tok == eos_token_id)
            out_tokens.append(tok)
            out_scores.append(score)
            if pos == total - 1 or (eos_token_id is not None
                                    and bool(jnp.all(finished))):
                break
            if dl is not None and dl.expired():
                timed_out = True
                break
            logits, caches = step(tok[:, None], caches, pos)
            pos += 1
    if mx:
        _SRV_DECODE_S.labels(path="cached").observe(
            _time.perf_counter() - t0)
        _SRV_DECODE_TOK.inc(B * len(out_tokens))
    partial = _finalize_tokens(out_tokens, out_scores, B, max_new_tokens,
                               pad_token_id)
    if timed_out:
        return _timeout_result("generate_cached", dl, len(out_tokens),
                               partial)
    return partial


def _make_decode_loop(p, S0: int, max_new_tokens: int,
                      decode_strategy: str, top_k, top_p,
                      temperature: float, eos_token_id, pad_token_id):
    """Compile prefill + the ENTIRE decode loop into one XLA program:
    a lax.scan over max_new_tokens cached decode steps. No host round-trip
    per token — the host-loop path pays dispatch+transfer latency
    every token; this is the serving-grade path
    (the XLA analog of the reference's fused decode loop over
    masked_multihead_attention, paddle/phi/kernels/fusion/gpu/
    masked_multihead_attention.cu). Fixed trip count (no early-eos exit)
    keeps the loop compiled; finished rows emit pad_token_id."""
    total = S0 + max_new_tokens
    cfg = p["cfg"]
    body = _cached_step_body(p, total)

    def run(w, ids, key):
        B = ids.shape[0]
        caches = _init_caches(p, B, total)
        logits, caches = body(w, ids, caches, 0)         # prefill
        finished = jnp.zeros((B,), bool)

        def scan_step(carry, i):
            logits, caches, finished, key = carry
            if decode_strategy == "greedy_search" or (
                    temperature is not None and temperature <= 0.0):
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
            else:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(
                    sub, _filter_logits(logits, top_k, top_p, temperature),
                    -1).astype(jnp.int32)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            score = jnp.take_along_axis(logp, tok[:, None], -1)[:, 0]
            if eos_token_id is not None:
                tok = jnp.where(finished, pad_token_id, tok)
                score = jnp.where(finished, 0.0, score)
                finished = finished | (tok == eos_token_id)
            logits, caches = body(w, tok[:, None], caches, S0 + i)
            return (logits, caches, finished, key), (tok, score)

        (_, _, _, _), (toks, scores) = jax.lax.scan(
            scan_step, (logits, caches, finished, key),
            jnp.arange(max_new_tokens))
        return toks.T, scores.T                          # [B, max_new]

    cfg_key = (p["family"], cfg.num_hidden_layers, cfg.hidden_size,
               cfg.num_attention_heads,
               getattr(cfg, "num_key_value_heads", 0),
               getattr(cfg, "head_dim", 0), cfg.vocab_size,
               getattr(cfg, "intermediate_size", 0),
               getattr(cfg, "rms_norm_eps", 0.0),
               getattr(cfg, "layer_norm_eps", 0.0),  # eps bakes into the body
               # MoE / MLA program-shaping knobs
               getattr(cfg, "num_experts", 0), getattr(cfg, "top_k", 0),
               getattr(cfg, "moe_intermediate_size", 0),
               getattr(cfg, "shared_expert_intermediate_size", 0),
               getattr(cfg, "first_k_dense_replace", 0),
               getattr(cfg, "kv_lora_rank", 0),
               getattr(cfg, "q_lora_rank", 0) or 0,
               getattr(cfg, "qk_nope_head_dim", 0),
               getattr(cfg, "qk_rope_head_dim", 0),
               getattr(cfg, "v_head_dim", 0))
    from .flags import flag
    prog_key = (cfg_key, S0, max_new_tokens, decode_strategy, top_k,
                top_p, temperature, eos_token_id, pad_token_id,
                # trace-time flags that shape the step body: a flipped
                # impl flag must MISS, not return the other impl's
                # compiled program (gmm routes the MoE prefill experts)
                flag("FLAGS_mla_decode_impl"), flag("FLAGS_gmm_impl"),
                flag("FLAGS_flash_impl"))
    jitted = _DECODE_LOOP_CACHE.get(prog_key)
    if _obs.enabled():
        _JIT_CACHE.labels(cache="decode_loop",
                          event="hit" if jitted is not None
                          else "miss").inc()
    if jitted is None:
        if len(_DECODE_LOOP_CACHE) >= 32:
            _DECODE_LOOP_CACHE.pop(next(iter(_DECODE_LOOP_CACHE)))
            if _obs.enabled():
                _JIT_CACHE.labels(cache="decode_loop",
                                  event="evict").inc()
        jitted = jax.jit(run)
        _DECODE_LOOP_CACHE[prog_key] = jitted
    weights = _llama_weights(p)
    return lambda ids, key: jitted(weights, ids, key)


# compiled decode loops keyed on everything that shapes the program: the
# weights ride as ARGUMENTS, so one executable serves every same-config
# model and every generate_compiled call with the same lengths/strategy —
# and the weights are re-read per call (no stale-closure capture after a
# training step updates the model)
_DECODE_LOOP_CACHE: dict = {}


def generate_compiled(model, input_ids, max_new_tokens: int = 20,
                      decode_strategy: str = "sampling",
                      top_k: Optional[int] = None,
                      top_p: Optional[float] = None, temperature: float = 1.0,
                      eos_token_id: Optional[int] = None,
                      pad_token_id: int = 0,
                      weight_only_int8: bool = False,
                      weight_only_quant=None,
                      deadline_s: Optional[float] = None):
    """KV-cache generation with the whole decode loop compiled (see
    _make_decode_loop). Same contract (and defaults) as
    generate_cached; sampling draws from the framework RNG stream once
    per call (the per-step keys are split on-device).

    ``deadline_s``: the scan-fused loop is one atomic XLA program, so
    the deadline is enforced at the dispatch boundaries — an expired
    budget before launch short-circuits to a TimeoutResult (partial
    None), and a launch that finishes past the budget returns a
    TimeoutResult whose .partial holds the full output."""
    _refuse_block_diffusion(model, "generate_compiled")
    if decode_strategy not in ("greedy_search", "sampling"):
        raise ValueError(f"decode_strategy {decode_strategy!r}: expected "
                         "'greedy_search' or 'sampling'")
    p = _decode_params(model, weight_only_int8, weight_only_quant)
    ids = input_ids._data if isinstance(input_ids, Tensor) \
        else jnp.asarray(input_ids)
    ids = ids.astype(jnp.int32)
    B, S0 = ids.shape
    if S0 + max_new_tokens > p["cfg"].max_position_embeddings:
        raise ValueError(f"{S0 + max_new_tokens} tokens exceed "
                         "max_position_embeddings")
    dl = _res.Deadline(deadline_s) if deadline_s else None
    if dl is not None and dl.expired():
        return _timeout_result("generate_compiled", dl, 0, None)
    run = _make_decode_loop(p, S0, max_new_tokens, decode_strategy,
                            top_k, top_p, temperature, eos_token_id,
                            pad_token_id)
    mx = _obs.enabled()
    if mx:
        _SRV_REQS.labels(path="compiled").inc()
        _SRV_BATCH.observe(B)
        _SRV_PREFILL_TOK.inc(B * S0)
    import time as _time
    t0 = _time.perf_counter() if mx else 0.0
    with ag.no_grad():
        gen, sc = run(ids, next_key())
    if mx:
        # one XLA program fuses prefill + decode; the whole call is
        # charged to the decode section
        _SRV_DECODE_S.labels(path="compiled").observe(
            _time.perf_counter() - t0)
        _SRV_DECODE_TOK.inc(B * max_new_tokens)
    out = (Tensor(gen), Tensor(sc))
    if dl is not None and dl.expired():
        return _timeout_result("generate_compiled", dl, max_new_tokens,
                               out)
    return out


# ---------------------------------------------------------------------------
# Beam search (ref: PaddleNLP GenerationMixin beam_search / group_beam_search,
# paddlenlp/generation/utils.py + BeamHypotheses in beam_utils) — with length
# penalty (score / len**length_penalty), repetition penalty (CTRL-style
# multiply/divide), and diverse groups (Hamming diversity: later groups pay
# diversity_rate per token already chosen this step by earlier groups).
# Fixed-shape: the model always sees [B*num_beams, S0+max_new_tokens].
# ---------------------------------------------------------------------------
def _repetition_penalize(logits, seen_tokens, penalty):
    """logits [R, V] (raw, pre-softmax); seen_tokens [R, T] int; CTRL
    penalty on the logits — seen tokens' negative logits are multiplied
    by `penalty`, positive ones divided — so the subsequent log_softmax
    still yields normalized log-probabilities (ref: paddlenlp
    RepetitionPenaltyLogitsProcessor.__call__)."""
    if penalty == 1.0:
        return logits
    R, V = logits.shape
    seen = jnp.zeros((R, V), bool).at[
        jnp.arange(R)[:, None], seen_tokens].set(True)
    penalized = jnp.where(logits < 0, logits * penalty, logits / penalty)
    return jnp.where(seen, penalized, logits)


def _beam_step(scores, finished, logp, num_beams, num_beam_groups,
               diversity_rate, pad_token_id, eos_token_id):
    """One beam-search selection. scores/finished [B, nb]; logp
    [B*nb, V] log-softmaxed. Returns (scores, tok, src_beam) [B, nb]."""
    B, nb = scores.shape
    V = logp.shape[-1]
    logp = logp.reshape(B, nb, V)
    # finished beams emit pad with frozen score
    frozen = jnp.full((V,), -jnp.inf).at[pad_token_id].set(0.0)
    logp = jnp.where(finished[..., None], frozen[None, None], logp)
    gs = nb // num_beam_groups
    parts = []
    chosen = jnp.zeros((B, V), jnp.float32)
    for g in range(num_beam_groups):
        lg = logp[:, g * gs:(g + 1) * gs]
        cand = scores[:, g * gs:(g + 1) * gs, None] + lg
        if g > 0 and diversity_rate:
            cand = cand - diversity_rate * chosen[:, None, :]
        top_s, top_i = jax.lax.top_k(cand.reshape(B, gs * V), gs)
        src = top_i // V + g * gs
        tok = (top_i % V).astype(jnp.int32)
        if num_beam_groups > 1:
            chosen = chosen.at[jnp.arange(B)[:, None], tok].add(1.0)
        parts.append((top_s, tok, src))
    new_scores = jnp.concatenate([p[0] for p in parts], 1)
    new_tok = jnp.concatenate([p[1] for p in parts], 1)
    new_src = jnp.concatenate([p[2] for p in parts], 1)
    return new_scores, new_tok, new_src


def _beam_engine(step_logits, reorder_state, ids, max_new_tokens,
                 num_beams, num_beam_groups, diversity_rate,
                 length_penalty, repetition_penalty, eos_token_id,
                 pad_token_id, num_return_sequences):
    """Shared beam loop. step_logits(t) -> [B*nb, V] logits at position
    t given current buffers; reorder_state(src_beam [B, nb], tok [B,nb],
    t) commits the beam permutation + chosen tokens."""
    B, S0 = ids.shape
    nb = num_beams
    if nb % num_beam_groups:
        raise ValueError(f"num_beams {nb} not divisible by "
                         f"num_beam_groups {num_beam_groups}")
    if num_return_sequences > nb:
        raise ValueError("num_return_sequences > num_beams")
    # beam 0 of each group starts live, the rest -inf (identical prompts
    # would otherwise fill the beam with duplicates)
    gs = nb // num_beam_groups
    init = np.full((B, nb), -1e9, np.float32)
    init[:, 0::gs] = 0.0
    scores = jnp.asarray(init)
    finished = jnp.zeros((B, nb), bool)
    toks = []  # committed tokens per step, [B, nb] AFTER reordering
    for t in range(S0 - 1, S0 + max_new_tokens - 1):
        logits = step_logits(t)
        logits = _repetition_penalize(
            logits.astype(jnp.float32),
            reorder_state.current_tokens(t), repetition_penalty)
        logp = jax.nn.log_softmax(logits, -1)
        scores, tok, src = _beam_step(scores, finished, logp, nb,
                                      num_beam_groups, diversity_rate,
                                      pad_token_id, eos_token_id)
        finished = jnp.take_along_axis(finished, src, 1)
        if eos_token_id is not None:
            finished = finished | (tok == eos_token_id)
        reorder_state.commit(src, tok, t)
        toks = [jnp.take_along_axis(x, src, 1) for x in toks]
        toks.append(tok)
        if eos_token_id is not None and bool(jnp.all(finished)):
            break
    gen = jnp.stack(toks, -1)                      # [B, nb, L]
    L = gen.shape[-1]
    if eos_token_id is not None:
        is_eos = gen == eos_token_id
        has = is_eos.any(-1)
        first = jnp.where(has, jnp.argmax(is_eos, -1) + 1, L)
    else:
        first = jnp.full(gen.shape[:2], L)
    lengths = first.astype(jnp.float32)
    final = scores / (lengths ** length_penalty) \
        if length_penalty != 0.0 else scores
    order = jnp.argsort(-final, axis=1)[:, :num_return_sequences]
    gen = jnp.take_along_axis(gen, order[..., None], 1)  # [B, nrs, L]
    best_sc = jnp.take_along_axis(final, order, 1)
    # mask everything after (and incl.) nothing — pad after eos
    pos = jnp.arange(L)[None, None, :]
    keep = pos < jnp.take_along_axis(first, order, 1)[..., None]
    gen = jnp.where(keep, gen, pad_token_id)
    if L < max_new_tokens:
        gen = jnp.concatenate(
            [gen, jnp.full(gen.shape[:2] + (max_new_tokens - L,),
                           pad_token_id, jnp.int32)], -1)
    gen = gen.reshape(B * num_return_sequences, max_new_tokens)
    return Tensor(gen), Tensor(best_sc.reshape(-1))


class _BufferBeamState:
    """Fixed-buffer model state for beam search: [B*nb, total] ids."""

    def __init__(self, model, ids, nb, max_new_tokens, pad_token_id):
        B, S0 = ids.shape
        self.B, self.nb, self.S0 = B, nb, S0
        total = S0 + max_new_tokens
        buf = jnp.concatenate(
            [ids, jnp.full((B, max_new_tokens), pad_token_id,
                           jnp.int32)], 1)
        self.buf = jnp.repeat(buf, nb, axis=0)     # [B*nb, total]
        self.model = model

    def logits_at(self, t):
        return _logits_fn(self.model, self.buf)[:, t]

    def current_tokens(self, t):
        return self.buf[:, :t + 1]  # pad tail excluded from penalties

    def commit(self, src, tok, t):
        B, nb = self.B, self.nb
        buf = self.buf.reshape(B, nb, -1)
        buf = jnp.take_along_axis(buf, src[..., None], 1)
        buf = buf.at[:, :, t + 1].set(tok)
        self.buf = buf.reshape(B * nb, -1)


def beam_search(model, input_ids, max_new_tokens: int = 20,
                num_beams: int = 4, num_beam_groups: int = 1,
                diversity_rate: float = 0.0, length_penalty: float = 0.0,
                repetition_penalty: float = 1.0,
                eos_token_id: Optional[int] = None, pad_token_id: int = 0,
                num_return_sequences: int = 1):
    """ref: PaddleNLP GenerationMixin.beam_search / group_beam_search.
    Returns (generated_ids [B*num_return_sequences, max_new_tokens],
    scores [B*num_return_sequences]) — sequences ranked by
    sum-logprob / len**length_penalty; tokens after eos are pad."""
    ids = input_ids._data if isinstance(input_ids, Tensor) \
        else jnp.asarray(input_ids)
    ids = ids.astype(jnp.int32)
    state = _BufferBeamState(model, ids, num_beams, max_new_tokens,
                             pad_token_id)
    was_training = getattr(model, "training", False)
    if hasattr(model, "eval"):
        model.eval()
    try:
        with ag.no_grad():
            return _beam_engine(state.logits_at, state, ids,
                                max_new_tokens, num_beams,
                                num_beam_groups, diversity_rate,
                                length_penalty, repetition_penalty,
                                eos_token_id, pad_token_id,
                                num_return_sequences)
    finally:
        if was_training and hasattr(model, "train"):
            model.train()


class _CachedBeamState:
    """KV-cache model state for beam search: caches gathered by the beam
    permutation every step (the reference's cache reorder on beam_idx)."""

    def __init__(self, model, ids, nb, max_new_tokens,
                 weight_only_int8=False, weight_only_quant=None):
        p = _decode_params(model, weight_only_int8, weight_only_quant)
        self.p = p
        cfg = p["cfg"]
        B, S0 = ids.shape
        self.B, self.nb, self.S0 = B, nb, S0
        total = S0 + max_new_tokens
        if total > cfg.max_position_embeddings:
            raise ValueError(
                f"{total} tokens exceed max_position_embeddings")
        self.caches = _init_caches(p, B * nb, total)
        self.step = _make_cached_step(p, total)
        self.buf = jnp.repeat(
            jnp.concatenate([ids, jnp.zeros((B, max_new_tokens),
                                            jnp.int32)], 1), nb, 0)
        self._logits = None
        self._pending = None  # (tok, t) decode deferred until needed

    def logits_at(self, t):
        # lazy: the engine may break on all-finished right after a
        # commit — deferring the decode forward here saves that call
        if self._logits is None:
            logits, self.caches = self.step(self.buf[:, :self.S0],
                                            self.caches, 0)
            self._logits = logits
        elif self._pending is not None:
            tok, tp = self._pending
            self._pending = None
            self._logits, self.caches = self.step(
                tok.reshape(-1, 1), self.caches, tp + 1)
        return self._logits

    def current_tokens(self, t):
        return self.buf[:, :t + 1]

    def commit(self, src, tok, t):
        B, nb = self.B, self.nb
        flat_src = (src + jnp.arange(B)[:, None] * nb).reshape(-1)
        self.caches = [(ck[flat_src], cv[flat_src])
                       for ck, cv in self.caches]
        buf = self.buf.reshape(B, nb, -1)
        buf = jnp.take_along_axis(buf, src[..., None], 1)
        buf = buf.at[:, :, t + 1].set(tok)
        self.buf = buf.reshape(B * nb, -1)
        self._pending = (tok, t)


def beam_search_cached(model, input_ids, max_new_tokens: int = 20,
                       num_beams: int = 4, num_beam_groups: int = 1,
                       diversity_rate: float = 0.0,
                       length_penalty: float = 0.0,
                       repetition_penalty: float = 1.0,
                       eos_token_id: Optional[int] = None,
                       pad_token_id: int = 0,
                       num_return_sequences: int = 1,
                       weight_only_int8: bool = False,
                       weight_only_quant=None):
    """KV-cache beam search for the Llama family (cache rows gathered by
    the beam permutation each step); same contract as beam_search."""
    ids = input_ids._data if isinstance(input_ids, Tensor) \
        else jnp.asarray(input_ids)
    ids = ids.astype(jnp.int32)
    state = _CachedBeamState(model, ids, num_beams, max_new_tokens,
                             weight_only_int8, weight_only_quant)
    with ag.no_grad():
        return _beam_engine(state.logits_at, state, ids, max_new_tokens,
                            num_beams, num_beam_groups, diversity_rate,
                            length_penalty, repetition_penalty,
                            eos_token_id, pad_token_id,
                            num_return_sequences)


__all__ += ["generate_cached", "generate_compiled", "beam_search",
            "beam_search_cached"]
