"""Benchmark: Llama pretrain tokens/sec/chip on the local TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "device": {...}}

Fails (non-zero exit, no result line) when jax finds no TPU, or one
whose peak is not in paddle_tpu/device/peaks.py: a CPU run is not a
measurement of this metric.

vs_baseline = measured MFU / 0.40 (the BASELINE.json north-star MFU target;
see BASELINE.md — no published reference throughput exists, so the
hardware-derived 40%-MFU bar is the baseline).
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp
    from paddle_tpu.trainer.pretrain import (PretrainConfig,
                                             build_llama_pretrain_step,
                                             make_hybrid_mesh_for,
                                             flops_per_token)

    from paddle_tpu._bootstrap import configure_compile_cache
    from paddle_tpu.device.peaks import require_peak

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py needs a TPU; jax found platform "
                 f"{dev.platform!r} ({dev.device_kind!r})")
    peak_flops = require_peak(dev).bf16_flops
    configure_compile_cache()
    # tuned recipe: on the full train step the bundled flash kernel is
    # ~0.8% faster on mean with the band CROSSING 1 (same-run interleaved
    # x3: bundled/intree step-time 0.977-1.004, docs/FLASH_RECIPE_AB.json)
    # — i.e. within noise; the recipe keeps the variant that never lost a
    # round, the in-tree kernel stays the default elsewhere and is the
    # only option for configs the bundled kernel refuses
    from paddle_tpu.flags import set_flags
    set_flags({"FLAGS_flash_impl": "bundled"})
    # Headline: the per-chip shard of an mp=8 x pp=4 partitioned
    # Llama-3-8B at the flagship seq 8192 — 8 true-shape decoder layers
    # (4 q-heads of head_dim 128 over the full 4096 residual stream,
    # FFN 14336/8) plus the vocab-parallel CE slice. This measures the
    # MXU efficiency of the flagship's per-chip computation; collectives
    # and pipeline bubbles are accounted in docs/FLAGSHIP.md.
    from paddle_tpu.models.llama import llama3_8b_shard_config
    # fused qkv/gate-up packs: +4 MFU pts on the thin TP-shard
    # matmul shapes (they were neutral on the old square proxy)
    mc = llama3_8b_shard_config(mp=8, pp=4,
                                max_position_embeddings=8192,
                                sequence_parallel=False,
                                fuse_attention_qkv=True,
                                fuse_attention_ffn=True)
    batch, seq, steps = 3, 8192, 8

    # remat="none": b3/s8192 residuals fit in HBM next to the f32
    # master+Adam state (flash attention saves only q/k/v/o/lse, never
    # the SxS probs); measured faster than "dots" at every feasible batch
    cfg = PretrainConfig(mc, global_batch=batch, seq_len=seq,
                         n_microbatches=1, param_dtype="bfloat16",
                         scan_layers=False, remat="none",
                         ce_chunks=2)
    mesh = make_hybrid_mesh_for(cfg, devices=jax.devices()[:1])
    state, train_step, meta = build_llama_pretrain_step(cfg, mesh)

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, mc.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, mc.vocab_size, (batch, seq)),
                         jnp.int32)

    # Warmup TWO steps: step 1 compiles for the initial arg layouts; because
    # the state is donated, step 2's inputs carry the output layouts and
    # trigger a second compile. Timing must start only after both executables
    # are cached.
    for _ in range(2):
        state, metrics = train_step(state, ids, labels)
        jax.block_until_ready(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = train_step(state, ids, labels)
    jax.block_until_ready((state, metrics))
    dt = time.perf_counter() - t0

    tokens = batch * seq * steps
    tok_per_sec = tokens / dt
    # 6N fwd+bwd weight FLOPs/token — the conservative model-FLOPs MFU
    # denominator (no attention term; flops_per_token_hw adds it, and
    # docs/FLAGSHIP.md reports both conventions)
    fpt = flops_per_token(mc)
    mfu = tok_per_sec * fpt / peak_flops
    print(json.dumps({
        "metric": "llama3_8b_shard_pretrain_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }))


if __name__ == "__main__":
    main()
