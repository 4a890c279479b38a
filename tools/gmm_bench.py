"""Grouped-GEMM A/B on the local chip (VERDICT r4 item 3: the 'in-tree
beats megablox 1.5-1.6x' claim rode single runs; this re-records it as
same-run interleaved rounds with bands). Contenders are the exact impls
`ops.grouped_gemm` routes between: jax.lax.ragged_dot (xla), the in-tree
Pallas kernel (ops/pallas_gmm.py), bundled megablox, and the one-hot
einsum fallback. Writes docs/GMM_BENCH.json.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_util import ab_rounds, band, ratio_band, ready  # noqa: E402


def bench_shape(name, M, K, N, G, rounds=3, reps=10):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.flags import flags_guard
    from paddle_tpu.ops.grouped_gemm import grouped_gemm

    rng = np.random.RandomState(0)
    lhs = jnp.asarray(rng.randn(M, K), jnp.bfloat16)
    rhs = jnp.asarray(rng.randn(G, K, N), jnp.bfloat16)
    sizes = jnp.full((G,), M // G, jnp.int32)

    def pinned(impl):
        def f(lhs, rhs, sizes):
            with flags_guard(gmm_impl=impl):
                return grouped_gemm(lhs, rhs, sizes)
        return jax.jit(f)

    kernels = {}
    for impl in ("xla", "intree", "bundled", "einsum"):
        try:
            fn = pinned(impl)
            ready(fn(lhs, rhs, sizes))  # compile / reject now
            kernels[impl] = (fn, (lhs, rhs, sizes))
        except Exception as e:  # noqa: BLE001 - record refusals honestly
            print(f"[gmm_bench] {name}: {impl} unavailable "
                  f"({type(e).__name__})", file=sys.stderr)

    runs = ab_rounds(kernels, rounds=rounds, reps=reps)
    row = dict(shape=name, M=M, K=K, N=N, G=G, rounds=rounds,
               **{impl: band(r) for impl, r in runs.items()})
    if "intree" in runs:
        for other in ("xla", "bundled", "einsum"):
            if other in runs:
                row[f"{other}_over_intree"] = ratio_band(runs[other],
                                                         runs["intree"])
    return row


def main():
    import jax
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        print("WARNING: not on TPU; numbers meaningless", file=sys.stderr)
    # MoE shapes this framework actually runs: training dispatch
    # (M = tokens x top_k) up/down projections at the moe_decode bench
    # geometry (h2048, mi1408, E8) and an 8B-style wider FFN
    shapes = [
        ("train_up_h2048_mi1408", 4096, 2048, 1408, 8),
        ("train_down_mi1408_h2048", 4096, 1408, 2048, 8),
        ("train_up_h4096_mi1792", 8192, 4096, 1792, 8),
        ("decode_up_B8top2", 128, 2048, 1408, 8),
    ]
    rows = [bench_shape(*s) for s in shapes]
    report = dict(device=str(jax.devices()[0].device_kind), rows=rows,
                  note="same-run interleaved rounds; ratios are "
                       "other/intree per-round bands — >1 means in-tree "
                       "is faster; a claim only counts where the whole "
                       "band clears 1")
    out = os.path.join(os.path.dirname(__file__), "..", "docs",
                       "GMM_BENCH.json")
    if on_tpu:
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
