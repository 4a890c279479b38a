"""Serving-path benchmark: KV-cache decode throughput on the local chip
(VERDICT r2 item 7; ref capability: the reference's inference engine is a
perf product — paddle/fluid/inference/ + the masked/block decode attention
kernel set, paddle/phi/kernels/fusion/gpu/block_multi_head_attention*).

Measures, on the real device, generate_compiled (one-XLA-program
prefill + lax.scan decode loop) on the per-chip shard of the mp=8 x pp=4
partitioned Llama-3-8B — the same per-chip model the training bench
measures, so the two numbers compose the same way (multiply by chips,
subtract the collective terms accounted in docs/FLAGSHIP.md).

Writes docs/SERVING_BENCH.json and prints a summary. Roofline note: at
batch B with per-chip weight bytes W and per-sequence KV-cache bytes C(s),
one decode step must read >= W + B*C(s) from HBM; tokens/s/chip is
bounded by B * BW / (W + B*C(s)). The report records achieved vs that
bound.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

def _bw() -> float:
    """HBM bytes/s of the local chip; an unknown device is an error (a
    roofline share against an assumed peak would be a wrong figure)."""
    from paddle_tpu.device.peaks import require_peak
    return require_peak().hbm_bytes_per_s


def _tree_bytes(p) -> int:
    import jax
    skip = {"cfg", "family", "moe_static"}
    leaves = jax.tree_util.tree_leaves(
        {k: v for k, v in p.items() if k not in skip})
    return sum(v.size * v.dtype.itemsize for v in leaves
               if hasattr(v, "size"))


def _roofline(family, *, B, S0, new, n_layers, w_bytes, decode_tok_s,
              kv_heads=0, head_dim=0, kv_latent_dim=0):
    """Roofline fields for one bench row, derived from the shared
    `observability.costmodel` registry (ISSUE 11: every roofline in this
    report comes from `decode_step_budget`, never hand-inlined byte
    math). The average KV length over the decode phase is ~S0 + new/2.
    ``bytes_per_token_measured`` is the HBM traffic per token the
    achieved rate implies at full bandwidth (= model / roofline
    fraction) — the instrumented-HBM counterpart lives in the serving
    engine's `hbm_accounting()` ledger."""
    from paddle_tpu.observability import costmodel
    budget = costmodel.decode_step_budget(
        family, batch=B, context=S0 + new / 2, layers=n_layers,
        weight_bytes=w_bytes, kv_heads=kv_heads, head_dim=head_dim,
        kv_latent_dim=kv_latent_dim)
    bw = _bw()
    bound_tok_s = costmodel.roofline_tokens_per_s(budget, bw)
    return dict(
        roofline_tokens_per_s=round(bound_tok_s, 1),
        roofline_fraction=round(decode_tok_s / bound_tok_s, 3),
        bytes_per_token_model=round(budget["bytes_per_token"], 1),
        bytes_per_token_measured=round(bw / decode_tok_s, 1))


def _log(msg):
    print(f"[serving_bench +{time.time() - _T0:.0f}s] {msg}", file=sys.stderr,
          flush=True)


_T0 = time.time()


def _llama_bench_raw_model(total, dtype="bfloat16"):
    """The ONE llama bench config (decode rows, the long-prefill row and
    the serving-engine row must measure the same 8B mp=8 x pp=4 shard —
    only cache capacity and quant mode differ). Returns (cfg, model)."""
    import jax.numpy as jnp
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama3_8b_shard_config)
    import paddle_tpu as paddle
    cfg = llama3_8b_shard_config(mp=8, pp=4,
                                 max_position_embeddings=total)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    if dtype == "bfloat16":
        for prm in model.parameters():
            prm._data = prm._data.astype(jnp.bfloat16)
    return cfg, model


def _llama_bench_model(total, dtype="bfloat16", weight_only_int8=False,
                       weight_only_quant=None):
    from paddle_tpu.generation import _llama_decode_params
    cfg, model = _llama_bench_raw_model(total, dtype)
    return cfg, _llama_decode_params(
        model, weight_only_int8=weight_only_int8,
        weight_only_quant=weight_only_quant)


def bench_decode(B=8, S0=1024, new=512, dtype="bfloat16",
                 weight_only_int8=False, weight_only_quant=None):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.generation import _make_decode_loop

    total = S0 + new
    _log(f"init model B={B} S0={S0} new={new} int8={weight_only_int8}")
    cfg, p = _llama_bench_model(total, dtype, weight_only_int8,
                                weight_only_quant)
    _log("model built")
    w_bytes = _tree_bytes(p)
    KV, D = cfg.num_key_value_heads, cfg.head_dim
    cache_bytes_full = 2 * total * KV * D * 2 * len(p["layers"])  # bf16

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S0)), jnp.int32)

    run = _make_decode_loop(p, S0, new, "greedy_search", None, None,
                                  1.0, None, 0)
    key = jax.random.PRNGKey(0)
    _log("compiling decode loop")
    t0 = time.time()
    toks, _ = run(ids, key)
    jax.block_until_ready(toks)
    _log("decode loop compiled+run")
    compile_and_first = time.time() - t0
    reps = 3
    t0 = time.time()
    for _ in range(reps):
        toks, _ = run(ids, key)
    np.asarray(toks)
    dt = (time.time() - t0) / reps

    # split prefill from decode: a 1-token decode loop isolates prefill
    run_pf = _make_decode_loop(p, S0, 1, "greedy_search", None, None,
                                     1.0, None, 0)
    _log("compiling prefill-only loop")
    toks_pf, _ = run_pf(ids, key)
    np.asarray(toks_pf)
    _log("prefill-only compiled+run")
    t0 = time.time()
    for _ in range(reps):
        toks_pf, _ = run_pf(ids, key)
    np.asarray(toks_pf)
    t_prefill = (time.time() - t0) / reps

    t_decode = max(dt - t_prefill, 1e-9)
    decode_tok_s = B * new / t_decode
    per_token_ms = t_decode / new * 1e3
    prefill_tok_s = B * S0 / max(t_prefill, 1e-9)

    roof = _roofline("llama", B=B, S0=S0, new=new,
                     n_layers=len(p["layers"]), w_bytes=w_bytes,
                     decode_tok_s=decode_tok_s, kv_heads=KV, head_dim=D)
    wo_tag = ("int4" if weight_only_quant == "int4"
              else "int8" if (weight_only_int8 or weight_only_quant)
              else None)
    extra = {}
    if wo_tag == "int4":
        extra["int4_note"] = (
            "int4 decode runs AT OR SLIGHTLY BELOW int8 throughput "
            "(~5-10% behind on recorded runs — compare the decode_int8 "
            "row measured the same day) rather than beating it: the "
            "in-kernel nibble unpack is VPU-bound at int32 width "
            "(Mosaic has no int8 vector shifts), spending roughly what "
            "the halved HBM reads save. The win is the 2x smaller "
            "weight footprint (serving density / headroom)")
    return dict(
        **extra,
        config="llama3_8b_shard mp=8 pp=4 (8 layers, 4 q-heads/1 kv-head "
               "d128, ffn 1792, vocab 16032)"
               + (f" [weight-only {wo_tag}]" if wo_tag else ""),
        dtype=f"{wo_tag}-weights" if wo_tag else dtype,
        batch=B, prefill_len=S0, new_tokens=new,
        weight_bytes=int(w_bytes), kv_cache_bytes_full=int(cache_bytes_full),
        compile_plus_first_s=round(compile_and_first, 2),
        prefill_tokens_per_s=round(prefill_tok_s),
        decode_tokens_per_s_per_chip=round(decode_tok_s, 1),
        decode_ms_per_token_per_seq=round(per_token_ms, 3),
        **roof)


def bench_moe_decode(B=8, S0=512, new=256, dtype="bfloat16",
                     weight_only_int8=False):
    """MoE-LM shard decode (VERDICT r3 item 6): routed experts inside the
    scanned decode step via the grouped-GEMM dropless path. int8 halves
    the expert-stack HBM reads that dominate the weight traffic (r5)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.moe_llm import MoEForCausalLM, MoEConfig
    from paddle_tpu.generation import _decode_params, _make_decode_loop
    import paddle_tpu as paddle

    total = S0 + new
    # a per-chip MoE shard at Qwen2-MoE-A14B-ish layer geometry: 8 routed
    # experts (the ep=8 shard of 64), top-2, shared expert, dense layer 0
    cfg = MoEConfig(vocab_size=16032, hidden_size=2048,
                    intermediate_size=5632, num_hidden_layers=8,
                    num_attention_heads=16, num_key_value_heads=4,
                    max_position_embeddings=total, num_experts=8, top_k=2,
                    moe_intermediate_size=1408,
                    shared_expert_intermediate_size=1408,
                    moe_dropless=True, first_k_dense_replace=1)
    _log(f"init MoE model B={B} S0={S0} new={new} int8={weight_only_int8}")
    paddle.seed(0)
    model = MoEForCausalLM(cfg)
    model.eval()
    if dtype == "bfloat16":
        for prm in model.parameters():
            prm._data = prm._data.astype(jnp.bfloat16)
    p = _decode_params(model, weight_only_int8=weight_only_int8)
    w_bytes = _tree_bytes(p)
    KV, D = cfg.num_key_value_heads, cfg.head_dim
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S0)), jnp.int32)
    run = _make_decode_loop(p, S0, new, "greedy_search", None, None,
                            1.0, None, 0)
    key = jax.random.PRNGKey(0)
    _log("compiling MoE decode loop")
    t0 = time.time()
    toks, _ = run(ids, key)
    np.asarray(toks)
    compile_and_first = time.time() - t0
    _log("MoE decode loop compiled+run")
    reps = 3
    t0 = time.time()
    for _ in range(reps):
        toks, _ = run(ids, key)
    np.asarray(toks)
    dt = (time.time() - t0) / reps
    run_pf = _make_decode_loop(p, S0, 1, "greedy_search", None, None,
                               1.0, None, 0)
    toks_pf, _ = run_pf(ids, key)
    np.asarray(toks_pf)
    t0 = time.time()
    for _ in range(reps):
        toks_pf, _ = run_pf(ids, key)
    np.asarray(toks_pf)
    t_prefill = (time.time() - t0) / reps
    t_decode = max(dt - t_prefill, 1e-9)
    decode_tok_s = B * new / t_decode
    # roofline: weights + avg KV reads; top-2-of-8 experts mean only
    # ~2/8 of routed expert weight bytes are LIVE per token, but a whole
    # decode step at small B still reads every routed expert touched by
    # ANY token — report the conservative all-weights bound
    roof = _roofline("moe", B=B, S0=S0, new=new,
                     n_layers=len(p["layers"]), w_bytes=w_bytes,
                     decode_tok_s=decode_tok_s, kv_heads=KV, head_dim=D)
    return dict(
        config="moe_shard 8L h2048 E8 top2 mi1408 shared1408 (dropless "
               + ("[weight-only int8] " if weight_only_int8 else "")
               + "grouped-GEMM routing in the scanned decode step)",
        dtype="int8-weights" if weight_only_int8 else dtype,
        batch=B, prefill_len=S0, new_tokens=new,
        weight_bytes=int(w_bytes),
        compile_plus_first_s=round(compile_and_first, 2),
        decode_tokens_per_s_per_chip=round(decode_tok_s, 1),
        decode_ms_per_token_per_seq=round(t_decode / new * 1e3, 3),
        **roof)


def _mla_bench_model(total, dtype="bfloat16", weight_only_int8=False):
    """The ONE mla_shard bench config (both the headline decode bench and
    the context sweep must measure the same model — only the cache
    capacity differs)."""
    import jax.numpy as jnp
    from paddle_tpu.models.deepseek import (DeepSeekV2ForCausalLM,
                                            DeepSeekV2Config)
    from paddle_tpu.generation import _decode_params
    import paddle_tpu as paddle
    cfg = DeepSeekV2Config(
        vocab_size=16032, hidden_size=2048, num_hidden_layers=8,
        num_attention_heads=16, num_key_value_heads=16,
        intermediate_size=5632, max_position_embeddings=total,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, num_experts=8, top_k=2,
        moe_intermediate_size=1408, shared_expert_intermediate_size=1408,
        moe_dropless=True, first_k_dense_replace=1)
    paddle.seed(0)
    model = DeepSeekV2ForCausalLM(cfg)
    model.eval()
    if dtype == "bfloat16":
        for prm in model.parameters():
            prm._data = prm._data.astype(jnp.bfloat16)
    return cfg, _decode_params(model, weight_only_int8=weight_only_int8)


def bench_mla_decode(B=8, S0=512, new=256, dtype="bfloat16",
                     weight_only_int8=False):
    """DeepSeek-V2 MLA shard decode: absorbed latent-KV cache (r+dr per
    token) through the scanned decode loop."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.generation import _make_decode_loop

    total = S0 + new
    _log(f"init MLA model B={B} S0={S0} new={new} int8={weight_only_int8}")
    cfg, p = _mla_bench_model(total, dtype, weight_only_int8)
    w_bytes = _tree_bytes(p)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S0)), jnp.int32)
    from paddle_tpu.flags import flags_guard
    key = jax.random.PRNGKey(0)
    _log("compiling MLA decode loop (fused kernel path)")
    with flags_guard(mla_decode_impl="fused"):
        run = _make_decode_loop(p, S0, new, "greedy_search", None, None,
                                1.0, None, 0)
        t0 = time.time()
        toks, _ = run(ids, key)
        np.asarray(toks)
        compile_and_first = time.time() - t0
    _log("compiling MLA decode loop (einsum composite, A/B contender)")
    with flags_guard(mla_decode_impl="xla"):
        run_x = _make_decode_loop(p, S0, new, "greedy_search", None, None,
                                  1.0, None, 0)
        toks_x, _ = run_x(ids, key)
        np.asarray(toks_x)
    # low-bit rounding differs between impls (f32-tile vs bf16-aw), so a
    # near-tie argmax may flip and diverge the sequence: RECORD the
    # disagreement instead of asserting (exact parity is a test-suite
    # contract at short horizons, tests/test_pallas_mla.py)
    tok_disagree = int((np.asarray(toks) != np.asarray(toks_x)).sum())
    # same-run interleaved rounds (VERDICT r4 weak #3 comparison shape).
    # One untimed call of EACH contender after ALL compiles.
    reps = 3
    from bench_util import ab_rounds, band, ratio_band, ready
    for f in (run, run_x):
        ready(f(ids, key)[0])
    runs = ab_rounds({"fused": (lambda: run(ids, key)[0], ()),
                      "xla": (lambda: run_x(ids, key)[0], ())},
                     rounds=reps, reps=1, warmup=False)
    t_fused, t_xla = runs["fused"], runs["xla"]
    run_pf = _make_decode_loop(p, S0, 1, "greedy_search", None, None,
                               1.0, None, 0)
    toks_pf, _ = run_pf(ids, key)
    np.asarray(toks_pf)
    t0 = time.time()
    for _ in range(reps):
        toks_pf, _ = run_pf(ids, key)
    np.asarray(toks_pf)
    t_prefill = (time.time() - t0) / reps
    # headline = the impl the shipped default routes to (auto -> fused at
    # this lane-aligned rank) — never a silent best-of-both (review r5)
    t_decode = max(sum(t_fused) / reps - t_prefill, 1e-9)
    decode_tok_s = B * new / t_decode
    # latent cache: (r + dr) bf16 per token per layer — the MLA win
    roof = _roofline(
        "mla", B=B, S0=S0, new=new, n_layers=len(p["layers"]),
        w_bytes=w_bytes, decode_tok_s=decode_tok_s,
        kv_latent_dim=cfg.kv_lora_rank + cfg.qk_rope_head_dim)
    return dict(
        config="mla_shard 8L h2048 16h q768/kv512 nope128 rope64 v128 "
               + ("E8 top2 [weight-only int8] (absorbed latent-KV decode)"
                  if weight_only_int8
                  else "E8 top2 (absorbed latent-KV decode)"),
        dtype="int8-weights" if weight_only_int8 else dtype,
        batch=B, prefill_len=S0, new_tokens=new,
        weight_bytes=int(w_bytes),
        latent_cache_bytes_per_token_layer=(cfg.kv_lora_rank
                                            + cfg.qk_rope_head_dim) * 2,
        compile_plus_first_s=round(compile_and_first, 2),
        headline_impl="fused (the auto route at kv_lora_rank=512)",
        decode_tokens_per_s_per_chip=round(decode_tok_s, 1),
        decode_ms_per_token_per_seq=round(t_decode / new * 1e3, 3),
        **roof,
        impl_ab=dict(
            note="same-run interleaved whole-loop rounds (prefill "
                 "included in both, subtracted from the headline); "
                 "fused = ops/pallas_mla.py single-cache-read kernel, "
                 "xla = two-einsum composite; compile_plus_first_s "
                 "covers the fused program only",
            greedy_token_disagreements=tok_disagree,
            disagreement_note="bf16 near-tie argmax flips cascade: after "
                              "the first divergent token the sequences "
                              "differ, so every later token counts; "
                              "short-horizon exact-match is the test "
                              "contract (tests/test_pallas_mla.py)",
            fused_loop=band(t_fused),
            xla_loop=band(t_xla),
            xla_over_fused=ratio_band(t_xla, t_fused)))


def bench_mla_context_sweep(S0s=(512, 4096, 12288), B=8, new=128,
                            dtype="bfloat16"):
    """Where the fused MLA kernel earns its keep: decode-PHASE A/B
    (random pre-filled caches, scan of decode steps — no prefill, so long
    contexts fit without the dense [B,nh,S,T] prefill score tensor) at
    growing context. At T~768 the latent cache is ~3% of step traffic and
    fused==einsum within noise; by 12k context the einsum's double read
    of the cache is the dominant waste and the kernel's single pass wins
    outright. Same-run interleaved rounds per context."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.generation import _mla_cached_step_body, _llama_weights
    from paddle_tpu.flags import flags_guard
    from bench_util import ab_rounds, band, ratio_band, ready

    # ONE model at the max context (rope table covers every S0; only the
    # cache capacity and step-body max_len vary per context)
    _log("mla ctx sweep: init model")
    cfg, p = _mla_bench_model(max(S0s) + new, dtype)
    wa = _llama_weights(p)
    rows = []
    for S0 in S0s:
        total = S0 + new
        rng = np.random.RandomState(0)
        caches0 = [
            (jnp.asarray(rng.randn(B, total, cfg.kv_lora_rank) * 0.1,
                         jnp.bfloat16),
             jnp.asarray(rng.randn(B, total, cfg.qk_rope_head_dim) * 0.1,
                         jnp.bfloat16))
            for _ in range(cfg.num_hidden_layers)]
        tok0 = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, 1)),
                           jnp.int32)
        loops = {}
        for impl in ("fused", "xla"):
            with flags_guard(mla_decode_impl=impl):
                body = _mla_cached_step_body(p["cfg"], total,
                                             p.get("moe_static"))

                @jax.jit
                def loop(w, tok0, caches, body=body):
                    def step(carry, i):
                        tok, caches = carry
                        logits, caches = body(w, tok, caches, S0 + i)
                        nxt = jnp.argmax(logits, -1)[:, None]
                        return (nxt.astype(jnp.int32), caches), ()
                    (tok, _), _ = jax.lax.scan(
                        step, (tok0, caches), jnp.arange(new))
                    return tok
                out = loop(wa, tok0, caches0)
                np.asarray(out)
                loops[impl] = loop
        for f in loops.values():
            # warm each after all compiles
            ready(f(wa, tok0, caches0))
        t = ab_rounds(
            {name: (f, (wa, tok0, caches0)) for name, f in loops.items()},
            rounds=3, reps=1, warmup=False)
        _log(f"mla ctx sweep S0={S0}: fused {min(t['fused']):.3f}s "
             f"xla {min(t['xla']):.3f}s")
        rows.append(dict(
            context=S0, batch=B, decode_steps=new,
            fused_per_token=band([x / new for x in t["fused"]]),
            xla_per_token=band([x / new for x in t["xla"]]),
            xla_over_fused=ratio_band(t["xla"], t["fused"])))
    return dict(
        note="decode-phase only (no prefill term): scan of greedy decode "
             "steps over pre-filled caches; per-token bands in us; the "
             "fused kernel must never lose at short context and win at "
             "long (paged-kernel-style crossover record)",
        rows=rows)


def bench_prefill_long(family="llama", S0=8192, B=4, dtype="bfloat16"):
    """Long-context PREFILL throughput — the r5 flash-prefill record.
    Before r5 every cached body materialized [*, S, max_len] f32 scores
    at prefill: a 12k-token B=8 MLA prefill OOM'd the 16 GB chip and the
    masked (max_len - S) columns were wasted even when it fit. The
    prefill-from-zero flash route makes these shapes runnable; this row
    records the achieved prefill tok/s at 8k context (new=1 decode loop
    isolates prefill + one step, matching the subtraction method the
    decode rows use)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.generation import _make_decode_loop
    from bench_util import ready
    import paddle_tpu as paddle

    total = S0 + 16
    if family == "llama":
        _log(f"prefill_long llama: init S0={S0} B={B}")
        cfg, p = _llama_bench_model(total, dtype)
    else:
        _log(f"prefill_long mla: init S0={S0} B={B}")
        cfg, p = _mla_bench_model(total, dtype)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S0)), jnp.int32)
    key = jax.random.PRNGKey(0)
    run = _make_decode_loop(p, S0, 1, "greedy_search", None, None,
                            1.0, None, 0)
    t0 = time.time()
    toks, _ = run(ids, key)
    np.asarray(toks)
    compile_and_first = time.time() - t0
    ready(run(ids, key)[0])          # warm
    reps = 3
    ts = []
    for _ in range(reps):
        t0 = time.time()
        ready(run(ids, key)[0])
        ts.append(time.time() - t0)
    from bench_util import band
    mean = sum(ts) / len(ts)    # mean over reps, matching bench_decode's
                                # identically-named field
    return dict(
        family=family, batch=B, prefill_len=S0, dtype=dtype,
        compile_plus_first_s=round(compile_and_first, 2),
        prefill_tokens_per_s=round(B * S0 / mean),
        loop_band=band(ts),
        note="runnable at all only since the r5 flash prefill (the "
             "dense [S, max_len] f32 score path OOMs these shapes); "
             "includes one decode step")


def _static_batches(model, reqs, max_slots):
    """Static whole-batch baseline: batches of `max_slots` in arrival
    order, prompts right-padded to the batch max, every row decoded until
    the LAST row's token budget — the padded prefill work and dead decode
    steps continuous batching exists to avoid. Uses generate_compiled
    (the serving-grade static API): its programs persist in
    _DECODE_LOOP_CACHE across calls, so after warmup the baseline pays
    zero compile time — the comparison measures scheduling, not jit."""
    import paddle_tpu as paddle
    from paddle_tpu.generation import generate_compiled
    for i in range(0, len(reqs), max_slots):
        chunk = reqs[i:i + max_slots]
        S = max(p.size for p, _ in chunk)
        ids = np.zeros((len(chunk), S), dtype=np.int32)
        for r, (p, _) in enumerate(chunk):
            ids[r, :p.size] = p
        generate_compiled(model, paddle.to_tensor(ids),
                          max_new_tokens=max(m for _, m in chunk),
                          decode_strategy="greedy_search")


def _serving_engine_row(model, cfg, reqs, max_slots, page_size, rounds):
    import tempfile
    import jax
    from bench_util import ratio_band, write_serving_report
    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(model, max_slots=max_slots, page_size=page_size)

    def run_engine():
        for p, m in reqs:
            eng.add_request(p, max_new_tokens=m)
        eng.run_to_completion()

    useful = sum(m for _, m in reqs)
    # warmup: the engine compiles once per (model, slot-count); the
    # static loop compiles one decode program per batch shape
    run_engine()
    _static_batches(model, reqs, max_slots)
    eng_ts, sta_ts = [], []
    for _ in range(rounds):            # same-run interleaved A/B
        t0 = time.time()
        run_engine()
        eng_ts.append(time.time() - t0)
        t0 = time.time()
        _static_batches(model, reqs, max_slots)
        sta_ts.append(time.time() - t0)
    on_tpu = jax.default_backend() == "tpu"
    # full serving.engine.* slice next to the artifact (TPU only — a
    # CPU-host run must leave docs/ untouched, same rule as main())
    rep_path = os.path.join(os.path.dirname(__file__), "..", "docs",
                            "SERVING_ENGINE_REPORT.json") if on_tpu \
        else os.path.join(tempfile.mkdtemp(), "SERVING_ENGINE_REPORT.json")
    row = dict(
        requests=len(reqs), max_slots=max_slots, page_size=page_size,
        prompt_tokens=int(sum(p.size for p, _ in reqs)),
        useful_new_tokens=int(useful),
        inflight_tokens_per_s=round(useful * rounds / sum(eng_ts), 1),
        static_tokens_per_s=round(useful * rounds / sum(sta_ts), 1),
        # per-round static_time/engine_time: >1 means in-flight wins
        inflight_vs_static=ratio_band(sta_ts, eng_ts),
        # {program_name: cache_size} — every value must stay 1 (the
        # engine's PT002 contract): "unified" and "feed"
        programs_compiled=eng.program_cache_sizes(),
        note="same mixed-length trace both ways; tokens/s counts only "
             "the REQUESTED new tokens, so static batching pays for its "
             "padded rows and dead decode steps. The engine decodes via "
             "a per-step host loop vs the baseline's fused scan: on a "
             "CPU host the dispatch overhead dominates a tiny step and "
             "the ratio inverts — only on-chip bands (weight-read-bound "
             "steps) are the record")
    report = write_serving_report(rep_path, extra=dict(throughput=row))
    row["engine_totals"] = report["totals"]
    return row


def bench_serving_engine(n=16, max_slots=8, page_size=16, rounds=3,
                         smin=64, smax=513, mmin=32, mmax=257, seed=0,
                         dtype="bfloat16"):
    """In-flight continuous batching (ServingEngine) vs static whole-batch
    generate_cached on the SAME mixed-length request trace, same run: the
    engine retires each row the step it finishes and backfills the slot
    from the queue; static batching decodes every batch until its slowest
    row finishes."""
    total = 1024
    _log(f"serving_engine: init model n={n} slots={max_slots}")
    cfg, model = _llama_bench_raw_model(total, dtype)
    rng = np.random.RandomState(seed)
    reqs = [(rng.randint(0, cfg.vocab_size,
                         int(rng.randint(smin, smax))).astype(np.int32),
             int(rng.randint(mmin, mmax)))
            for _ in range(n)]
    _log("model built; running trace")
    return _serving_engine_row(model, cfg, reqs, max_slots, page_size,
                               rounds)


def _srv_metric(name):
    from paddle_tpu import serving as srv
    fam = srv.metrics().get(name)
    if not fam or not fam["series"]:
        return 0.0
    return fam["series"][0]["value"]


def bench_prefix_cache_multitenant(n_tenants=16, sys_len=256, tail_len=16,
                                   new=32, max_slots=4, page_size=16,
                                   dtype="bfloat16"):
    """Global radix prefix cache A/B (same model, same trace both ways):
    N tenants share one system prompt. Cache-ON admits every later
    tenant with the cached prefix pages adopted from the trie — only the
    per-tenant tail prefills; cache-OFF pays the full prompt prefill per
    tenant. Records the prompt-token hit rate and per-request TTFT both
    ways. Exactness under sharing is the test-suite contract
    (tests/test_prefix_cache.py)."""
    from paddle_tpu.serving import ServingEngine
    from bench_util import band, ratio_band

    total = 1024
    _log(f"prefix_cache_multitenant: init model tenants={n_tenants}")
    cfg, model = _llama_bench_raw_model(total, dtype)
    rng = np.random.RandomState(0)
    system = rng.randint(0, cfg.vocab_size, sys_len).astype(np.int32)
    prompts = [np.concatenate([system,
                               rng.randint(0, cfg.vocab_size,
                                           tail_len).astype(np.int32)])
               for _ in range(n_tenants)]
    warm = rng.randint(0, cfg.vocab_size,
                       sys_len + tail_len).astype(np.int32)

    def run(enable):
        eng = ServingEngine(model, max_slots=max_slots,
                            page_size=page_size, prefix_sharing=False,
                            enable_prefix_cache=enable)
        eng.add_request(warm, max_new_tokens=4)   # compile untimed
        eng.run_to_completion()
        ttfts, shared, total_prompt = [], 0, 0
        t_all = time.time()
        for t, prompt in enumerate(prompts):
            r = eng.add_request(prompt, max_new_tokens=new,
                                tenant=f"tenant{t}")
            t0 = time.time()
            first = None
            while eng.has_work():
                if eng.step().get("decoded"):
                    first = time.time() - t0   # first token emitted
                    break
            eng.run_to_completion()
            ttfts.append(first if first is not None
                         else time.time() - t0)
            shared += r.shared_tokens
            total_prompt += prompt.size
        return ttfts, shared, total_prompt, time.time() - t_all, eng

    _log("prefix_cache_multitenant: cache ON trace")
    ttft_on, shared, total_prompt, wall_on, eng_on = run(True)
    _log("prefix_cache_multitenant: cache OFF trace")
    ttft_off, shared_off, _, wall_off, _ = run(False)
    useful = n_tenants * new
    return dict(
        tenants=n_tenants, system_prompt_tokens=sys_len,
        tail_tokens=tail_len, new_tokens_per_request=new,
        max_slots=max_slots, page_size=page_size,
        prompt_tokens=int(total_prompt),
        shared_prompt_tokens=int(shared),
        prefix_hit_rate=round(shared / total_prompt, 3),
        ttft_cache_on=band(ttft_on),
        ttft_cache_off=band(ttft_off),
        # per-request ttft_off/ttft_on: >1 means the cache cuts TTFT
        ttft_speedup=ratio_band(ttft_off, ttft_on),
        cache_on_tokens_per_s=round(useful / wall_on, 1),
        cache_off_tokens_per_s=round(useful / wall_off, 1),
        cache_off_shared_tokens=int(shared_off),
        programs_compiled=eng_on.program_cache_sizes(),
        note="sequential per-tenant requests so TTFT isolates the "
             "prefill each request actually paid; tenant 0 is the cold "
             "miss that populates the trie, tenants 1.. adopt its pages "
             "and prefill only the tail. CPU-host numbers are not the "
             "record — the host step loop dominates tiny steps")


def bench_spec_decode_b1(k=4, new=128, rounds=3, dtype="bfloat16"):
    """N-gram self-drafting speculative decode at B=1 (the latency
    shape): a repetitive-text prompt (seed extended with its own greedy
    continuation, the drafter's favorable regime), spec engine (k drafts
    verified in ONE ragged launch) vs plain token-at-a-time decode on
    the same model, same-run interleaved rounds. Records mean accepted
    tokens per verify step and tokens/s both ways — output exactness is
    the test-suite contract (tests/test_spec_decode.py)."""
    import paddle_tpu as paddle
    from paddle_tpu.generation import generate_cached
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.spec_decode import accept_length, ngram_draft
    from bench_util import ratio_band

    total = 1024
    _log(f"spec_decode_b1: init model k={k} new={new}")
    cfg, model = _llama_bench_raw_model(total, dtype)
    rng = np.random.RandomState(0)
    seed = np.tile(rng.randint(0, cfg.vocab_size, 8).astype(np.int32), 3)
    cont, _ = generate_cached(model, paddle.to_tensor(seed[None]),
                              max_new_tokens=new + 48,
                              decode_strategy="greedy_search")
    c = [int(t) for t in cont.numpy()[0]]
    base = [int(t) for t in seed]
    # cut the prompt where its own greedy continuation is repetitive
    # (the repetitive-text trace this row measures): score each
    # candidate cut by the drafter's one-shot agreement with the known
    # greedy truth and take the best 16-step window — greedy
    # determinism makes the engine decode from seed+c[:cut] replay
    # c[cut:] exactly, so the score predicts the measured acceptance
    scores = [accept_length(ngram_draft(base + c[:p], k), c[p:p + k])
              for p in range(8, 49)]
    cut = 8 + max(range(len(scores) - 15),
                  key=lambda i: sum(scores[i:i + 16]))
    prompt = np.asarray(base + c[:cut], np.int32)

    engines = {"spec": ServingEngine(model, max_slots=1, page_size=16,
                                     spec_decode=k),
               "plain": ServingEngine(model, max_slots=1, page_size=16,
                                      spec_decode=0)}

    def run(eng):
        eng.add_request(prompt, max_new_tokens=new)
        eng.run_to_completion()

    for name, eng in engines.items():   # compile + warm the prefix trie
        _log(f"spec_decode_b1: warm {name}")
        run(eng)
    m0 = {kk: _srv_metric(f"serving.spec_decode.{kk}")
          for kk in ("draft_tokens", "accepted_tokens", "verify_steps")}
    ts = {"spec": [], "plain": []}
    for _ in range(rounds):             # same-run interleaved A/B
        for name, eng in engines.items():
            t0 = time.time()
            run(eng)
            ts[name].append(time.time() - t0)
    d = {kk: _srv_metric(f"serving.spec_decode.{kk}") - m0[kk]
         for kk in m0}
    vsteps = max(d["verify_steps"], 1.0)
    return dict(
        batch=1, draft_k=k, prompt_tokens=int(prompt.size),
        new_tokens=new, rounds=rounds,
        # the acceptance-bar stat: > 1 means each verify launch emits
        # more than one token on average (the speculative win)
        accepted_tokens_per_verify_step=round(
            d["accepted_tokens"] / vsteps, 2),
        draft_acceptance_rate=round(
            d["accepted_tokens"] / max(d["draft_tokens"], 1.0), 3),
        spec_tokens_per_s=round(new * rounds / sum(ts["spec"]), 1),
        plain_tokens_per_s=round(new * rounds / sum(ts["plain"]), 1),
        # per-round plain_time/spec_time: >1 means speculation wins
        spec_vs_plain=ratio_band(ts["plain"], ts["spec"]),
        programs_compiled=engines["spec"].program_cache_sizes(),
        note="metric deltas cover only the timed interleaved rounds "
             "(the plain engine drafts nothing, so the spec_decode.* "
             "movement is the spec engine's alone); tokens/s counts the "
             "requested new tokens. CPU-host numbers are not the record")


def bench_disaggregated(n_tenants=8, sys_len=128, tail_len=16, new=32,
                        max_slots=4, page_size=16, dtype="bfloat16"):
    """Disaggregated prefill/decode A/B (same model, same multitenant
    trace both ways): a 1-prefill + 1-decode replica fleet behind the
    FleetRouter — every request prefills on the prefill replica and
    crosses a KV-page handoff before its first decode step — vs ONE
    colocated engine. Tenants share a system prompt so the row also
    measures whether the prefill replica's radix trie keeps its
    prefill-skip rate under disaggregation. Records tokens/s, TTFT, and
    prefill-skip both ways plus the handoff count and mean latency.
    Output exactness across the handoff is the test-suite contract
    (tests/test_serving_engine.py::TestDisaggregated)."""
    from paddle_tpu.serving import FleetRouter, ServingEngine
    from bench_util import band, ratio_band

    total = 1024
    _log(f"disaggregated: init model tenants={n_tenants}")
    cfg, model = _llama_bench_raw_model(total, dtype)
    rng = np.random.RandomState(0)
    system = rng.randint(0, cfg.vocab_size, sys_len).astype(np.int32)
    prompts = [np.concatenate([system,
                               rng.randint(0, cfg.vocab_size,
                                           tail_len).astype(np.int32)])
               for _ in range(n_tenants)]
    warm = rng.randint(0, cfg.vocab_size,
                       sys_len + tail_len).astype(np.int32)

    def run(submit, step, drain, warmup):
        warmup()                           # compile untimed
        ttfts, shared, prompt_toks = [], 0, 0
        t_all = time.time()
        for t, prompt in enumerate(prompts):
            r = submit(prompt, t)
            t0 = time.time()
            first = None
            while first is None:
                if step().get("decoded"):
                    first = time.time() - t0   # first token emitted
            drain()
            ttfts.append(first)
            shared += r.shared_tokens
            prompt_toks += prompt.size
        return ttfts, shared, prompt_toks, time.time() - t_all

    _log("disaggregated: colocated trace")
    eng = ServingEngine(model, max_slots=max_slots, page_size=page_size)

    def _coloc_warm():
        eng.add_request(warm, max_new_tokens=4)
        eng.run_to_completion()
    ttft_c, shared_c, ptoks, wall_c = run(
        lambda p, t: eng.add_request(p, max_new_tokens=new,
                                     tenant=f"tenant{t}"),
        eng.step, eng.run_to_completion, _coloc_warm)

    _log("disaggregated: prefill+decode fleet trace")
    pf = ServingEngine(model, max_slots=max_slots, page_size=page_size,
                       role="prefill")
    dec = ServingEngine(model, max_slots=max_slots, page_size=page_size,
                        role="decode")
    router = FleetRouter({"prefill0": pf, "decode0": dec})

    def _fleet_warm():
        router.submit(warm, max_new_tokens=4)
        router.run_to_completion()
    ttft_d, shared_d, _, wall_d = run(
        lambda p, t: router.submit(p, max_new_tokens=new,
                                   tenant=f"tenant{t}"),
        router.step, router.run_to_completion, _fleet_warm)

    st = router.stats()
    useful = n_tenants * new
    return dict(
        tenants=n_tenants, system_prompt_tokens=sys_len,
        tail_tokens=tail_len, new_tokens_per_request=new,
        max_slots=max_slots, page_size=page_size,
        disagg_tokens_per_s=round(useful / wall_d, 1),
        colocated_tokens_per_s=round(useful / wall_c, 1),
        ttft_disagg=band(ttft_d),
        ttft_colocated=band(ttft_c),
        # per-request ttft_colocated/ttft_disagg: < 1 is the handoff tax
        ttft_ratio=ratio_band(ttft_c, ttft_d),
        prefill_skip_rate=round(shared_d / ptoks, 3),
        colocated_prefill_skip_rate=round(shared_c / ptoks, 3),
        handoffs=st["handoffs"],
        handoff_latency_ms=round(st["handoff_latency_s"] * 1e3, 2),
        programs_compiled={"prefill0": pf.program_cache_sizes(),
                           "decode0": dec.program_cache_sizes()},
        note="every fleet request pays one prefill→decode KV-page "
             "handoff before its first token; sequential per-tenant "
             "requests so TTFT isolates what each request actually "
             "paid. handoff_latency is export→import wall time "
             "(in-process host copy on CPU; DCN transfer on a real "
             "fleet). CPU-host numbers are not the record")


def bench_fleet_workloads(seed=0, dtype="bfloat16"):
    """Hostile-traffic scenario suite (ISSUE 16) on the real chip: the
    five seeded `paddle_tpu.serving.workloads` scenarios — burst,
    agentic multi-turn, long+short mix, cache-thrash, replica-kill
    chaos — each driving a fresh multi-replica fleet through the
    FleetRouter. The per-scenario rows land in the artifact verbatim
    (the tier-1 replica of this suite lives in docs/FLEET_BENCH.json
    via tools/fleetboard.py --selftest); the top-level aggregates are
    the worst case across scenarios, which is what an SLO burns down
    to."""
    from paddle_tpu.serving import workloads
    total = 1024
    _log(f"fleet_workloads: init model seed={seed}")
    cfg, model = _llama_bench_raw_model(total, dtype)
    rows = workloads.run_all(model, seed=seed)
    zero_loss = int(all(r["zero_loss"] for r in rows.values()))
    return dict(
        seed=seed, scenarios=rows,
        fleet_tokens_per_s=round(min(r["fleet_tokens_per_s"]
                                     for r in rows.values()), 2),
        fleet_zero_loss=zero_loss,
        fleet_handoffs=sum(r["handoffs"] for r in rows.values()),
        note="worst-scenario fleet throughput; per-scenario detail in "
             "'scenarios'. replica_kill asserts zero request loss and "
             "exact greedy outputs through a mid-burst drain")


# One entry per artifact row. Latency point (B=1) and a fatter-batch
# point: decode tok/s scales with B until the KV reads pass the weight
# reads in the roofline denominator. int8/int4/bf16_ref use
# decode-dominated lengths (the prefill-subtraction method needs the
# decode phase to dwarf prefill noise).
ROWS = {
    "decode": lambda: bench_decode(),
    "decode_b1": lambda: bench_decode(B=1, S0=1024, new=256),
    "decode_b16": lambda: bench_decode(B=16, S0=1024, new=256),
    "decode_int8": lambda: bench_decode(B=8, S0=256, new=1024,
                                        weight_only_int8=True),
    "decode_int4": lambda: bench_decode(B=8, S0=256, new=1024,
                                        weight_only_quant="int4"),
    "decode_bf16_ref": lambda: bench_decode(B=8, S0=256, new=1024),
    "moe_decode": lambda: bench_moe_decode(),
    "moe_decode_int8": lambda: bench_moe_decode(weight_only_int8=True),
    "mla_decode": lambda: bench_mla_decode(),
    "mla_decode_int8": lambda: bench_mla_decode(weight_only_int8=True),
    "mla_context_sweep": lambda: bench_mla_context_sweep(),
    "prefill_8k_llama": lambda: bench_prefill_long("llama"),
    "prefill_8k_mla": lambda: bench_prefill_long("mla"),
    "serving_engine": lambda: bench_serving_engine(),
    "prefix_cache_multitenant": lambda: bench_prefix_cache_multitenant(),
    "spec_decode_b1": lambda: bench_spec_decode_b1(),
    "disaggregated": lambda: bench_disaggregated(),
    "fleet_workloads": lambda: bench_fleet_workloads(),
}

_ROW_MARK = "__ROW_JSON__"


def main():
    import subprocess
    if "--probe" in sys.argv:
        import jax
        from paddle_tpu.device.peaks import peak
        dev = jax.devices()[0]
        pk = peak(dev)
        print(_ROW_MARK + json.dumps(
            dict(device=str(dev.device_kind),
                 on_tpu=dev.platform == "tpu",
                 hbm_bw_used=pk.hbm_bytes_per_s if pk else None)))
        return
    if "--row" in sys.argv:
        from paddle_tpu._bootstrap import configure_compile_cache
        configure_compile_cache()
        name = sys.argv[sys.argv.index("--row") + 1]
        print(_ROW_MARK + json.dumps(ROWS[name]()))
        return
    # the parent must NEVER initialize jax: on a real chip the client
    # holds the libtpu lock and every child row would fail to attach —
    # probe device facts through a subprocess like everything else
    probe = _run_row(["--probe"])
    if probe is None:
        # a dead probe must not let a 40-minute run silently discard its
        # artifact at the end — fail NOW
        print("device probe failed — aborting before any rows run",
              file=sys.stderr)
        sys.exit(1)
    on_tpu = bool(probe.get("on_tpu"))
    if not on_tpu:
        print("WARNING: no TPU — numbers are CPU-host and not the record",
              file=sys.stderr)
    report = dict(device=probe.get("device", "unknown"),
                  hbm_bw_used=probe.get("hbm_bw_used"),
                  measurement_protocol="each row runs in its OWN process: "
                  "rows measured after unrelated models/executables "
                  "accumulated on the chip showed 2x bimodal spikes on "
                  "the fused-program side only (r5 — 74-86% spread vs "
                  "0.2% standalone); per-row isolation reproduces the "
                  "standalone conditions every time")
    failed = []
    for name in ROWS:
        _log(f"row {name}: spawning")
        val = _run_row(["--row", name])
        if val is None:
            failed.append(name)
            continue
        report[name] = val
    out = os.path.join(os.path.dirname(__file__), "..", "docs",
                       "SERVING_BENCH.json")
    if failed:
        # never clobber the committed record with a partial report
        print(f"FAILED rows {failed} — artifact NOT written", file=sys.stderr)
        print(json.dumps(report, indent=2))
        sys.exit(1)
    if on_tpu:
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))


def _run_row(args):
    import subprocess
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, os.path.abspath(__file__)] + args,
                       capture_output=True, text=True, env=env)
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith(_ROW_MARK)), None)
    if line is None:
        _log(f"{args} FAILED:\n{r.stderr[-2000:]}")
        return None
    return json.loads(line[len(_ROW_MARK):])


if __name__ == "__main__":
    main()
