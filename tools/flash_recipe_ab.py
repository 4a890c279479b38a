"""Flash-recipe A/B on the FULL flagship train step (VERDICT r4 item 3:
the 'bundled ~2% faster on the train step' recipe claim rode a single
run). Builds the bench.py shard step twice in ONE process — once routed
through the in-tree flash kernel, once through the bundled kernel — and
times them in interleaved blocks so both see the same chip drift.
Writes docs/FLASH_RECIPE_AB.json; bench.py's recipe comment cites it.

Layout note: the state is donated, so the first block after a kernel
swap may recompile once for the other kernel's output layouts; all
executables are cached after the first A->B->A cycle, and timing skips
each block's first step.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.flags import flags_guard
    from paddle_tpu.models.llama import llama3_8b_shard_config
    from paddle_tpu.trainer.pretrain import (PretrainConfig,
                                             build_llama_pretrain_step,
                                             make_hybrid_mesh_for)

    on_tpu = jax.devices()[0].platform != "cpu"
    if not on_tpu:
        print("WARNING: not on TPU; numbers meaningless", file=sys.stderr)

    mc = llama3_8b_shard_config(mp=8, pp=4, max_position_embeddings=8192,
                                sequence_parallel=False,
                                fuse_attention_qkv=True,
                                fuse_attention_ffn=True)
    batch, seq = (3, 8192) if on_tpu else (2, 128)
    cfg = PretrainConfig(mc, global_batch=batch, seq_len=seq,
                         n_microbatches=1, param_dtype="bfloat16",
                         scan_layers=False, remat="none", ce_chunks=2)
    mesh = make_hybrid_mesh_for(cfg, devices=jax.devices()[:1])

    import gc
    steps = {}
    state = None
    for impl in ("intree", "bundled"):
        with flags_guard(flash_impl=impl):
            st, step, meta = build_llama_pretrain_step(cfg, mesh)
        steps[impl] = step
        if state is None:
            state = st  # ONE donated state threads through both variants
        # drop the second build's 3.9 GB state AND the meta-held model
        # (1.4 GB of f32 init params) NOW — two live copies plus the step
        # temps exceed the 16 GB chip
        del st, meta
        gc.collect()

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, mc.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, mc.vocab_size, (batch, seq)),
                         jnp.int32)

    def block(impl, n):
        """One timed block: first step absorbs any layout recompile and is
        NOT timed; the next n are."""
        nonlocal state
        state, m = steps[impl](state, ids, labels)
        float(m["loss"])
        t0 = time.time()
        for _ in range(n):
            state, m = steps[impl](state, ids, labels)
        float(m["loss"])
        return (time.time() - t0) / n

    # warm both variants (compile + donated-layout executables)
    block("intree", 1)
    block("bundled", 1)
    block("intree", 1)

    rounds, n = 3, 4
    runs = {"intree": [], "bundled": []}
    for _ in range(rounds):
        for impl in ("intree", "bundled"):
            runs[impl].append(block(impl, n))

    tok = batch * seq
    rep = {}
    for impl in ("intree", "bundled"):
        ts = runs[impl]
        mean = sum(ts) / len(ts)
        rep[impl] = {
            "step_s_runs": [round(t, 4) for t in ts],
            "tokens_per_s_mean": round(tok / mean, 1),
            "tokens_per_s_band": [round(tok / max(ts), 1),
                                  round(tok / min(ts), 1)],
            "spread_pct": round((max(ts) - min(ts)) / mean * 100, 2),
        }
    ratios = [b / a for a, b in zip(runs["intree"], runs["bundled"])]
    rep["bundled_over_intree_step_time"] = {
        "mean": round(sum(ratios) / len(ratios), 4),
        "min": round(min(ratios), 4), "max": round(max(ratios), 4),
        "reading": "<1 means bundled is faster on the full train step",
    }
    report = dict(device=str(jax.devices()[0].device_kind),
                  config=f"llama3_8b_shard mp8/pp4 b{batch} s{seq} "
                         f"remat=none ce_chunks=2 fused qkv/ffn",
                  rounds=rounds, steps_per_block=n, **rep)
    out = os.path.join(os.path.dirname(__file__), "..", "docs",
                       "FLASH_RECIPE_AB.json")
    if on_tpu:
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
