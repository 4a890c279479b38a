"""The in-tree flash kernel on the local chip, launch by launch.

Default: the four-chip training cell's one-chip shape, [B, H, S, D] =
[2, 16, 8192, 128] causal at blocks of 512 (`mistral7b-train-zero2-mp2-
4chip`: 32 such launches a step), with the forward, dq and dk / dv
launches timed APART, interleaved in one process over every kernel text
given:

    python tools/flash_bench.py --text parent=<tree>/paddle_tpu/ops/pallas_flash.py

loads another checkout's `pallas_flash.py` beside this one's (a text
needs `_flash_fwd_impl` / `_flash_bwd_impl` with this file's signatures)
and prints each launch's time, the share of the chip's 197 TF/s its
matmuls reach, the largest difference of its results from this
checkout's, and a text's three launches against `flash_causal_cost`'s
FLOPs (the forward's two matmuls and five for the backward, where the
two backward sweeps run seven). The bundled kernel
(jax.experimental.pallas.ops.tpu.flash_attention) runs beside them once.

`--sweep` times this checkout's kernel against the bundled one, forward
and forward + backward, over the older bench shapes. It prints; it
writes no file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

_TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [_TOOLS, os.path.dirname(_TOOLS)]
from bench_util import ab_rounds, band, load_text, ratio_band  # noqa: E402

PEAK_FLOPS = 197e12      # bf16, one v5e chip (Google Cloud, "TPU v5e")


def _launches(mod, B, H, S, D, block):
    """{launch: (jitted fn, args)} of one kernel text at the cell's shape,
    and the forward's results for the comparison."""
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    q, k, v, do = (jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
                   for _ in range(4))
    seg = jnp.zeros((B, 1, S), jnp.int32)
    static = (D ** -0.5, True, block, block, False)
    fwd = jax.jit(lambda q, k, v: mod._flash_fwd_impl(
        q, k, v, seg, seg, *static))
    o, lse = fwd(q, k, v)

    def bwd(pick):
        # a launch whose result is dropped is dead code to XLA: each of
        # these compiles the di reduction and ONE sweep
        return jax.jit(lambda q, k, v, o, lse, do: pick(mod._flash_bwd_impl(
            q, k, v, seg, seg, o, lse, do, *static)))
    res = (q, k, v, o, lse, do)
    return {"fwd": (fwd, (q, k, v)),
            "dq": (bwd(lambda g: g[0]), res),
            "dkv": (bwd(lambda g: g[1:]), res)}


def cell(texts, B=2, H=16, S=8192, D=128, block=512, rounds=3, reps=10,
         launches=("fwd", "dq", "dkv")):
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as bundled)
    from paddle_tpu.ops.flash_attention import _flash_block_sizes

    kernels, results = {}, {}
    for name, mod in texts.items():
        for launch, (fn, args) in _launches(mod, B, H, S, D, block).items():
            if launch not in launches:
                continue
            kernels[f"{name}.{launch}"] = (fn, args)
            results[name, launch] = jax.tree.leaves(fn(*args))
    q, k, v = _launches(texts["change"], B, H, S, D, block)["fwd"][1]
    sizes = _flash_block_sizes(S, S)
    b_fwd = jax.jit(lambda q, k, v: bundled(
        q, k, v, causal=True, sm_scale=D ** -0.5, block_sizes=sizes))
    b_grad = jax.jit(jax.grad(lambda q, k, v: b_fwd(q, k, v).astype(
        jnp.float32).sum(), (0, 1, 2)))
    if jax.default_backend() == "tpu":      # it has no interpret mode
        kernels["bundled.fwd"] = (b_fwd, (q, k, v))
        kernels["bundled.fwd+bwd"] = (b_grad, (q, k, v))

    runs = ab_rounds(kernels, rounds=rounds, reps=reps)
    # FLOPs of one matmul over the S (S + 1) / 2 visible scores, and the
    # matmuls each launch runs
    matmul = 2.0 * B * H * D * S * (S + 1) // 2
    ran = {"fwd": 2, "dq": 3, "dkv": 4, "fwd+bwd": 9}
    mean = {name: sum(times) / len(times) for name, times in runs.items()}
    for name, times in runs.items():
        text, launch = name.split(".")
        row = dict(kernel=name, shape=[B, H, S, D], block=block,
                   **band(times, scale=1e3))
        row = {key.replace("_us", "_ms"): val for key, val in row.items()}
        row["mxu_pct"] = round(
            100 * ran[launch] * matmul / PEAK_FLOPS / mean[name], 1)
        if (text, launch) in results and text != "change":
            row["max_abs_diff_from_change"] = [
                float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.reshape(a.shape)
                                      .astype(jnp.float32))))
                for a, b in zip(results["change", launch],
                                results[text, launch])]
        print(json.dumps(row), flush=True)
    for text in texts if len(launches) == 3 else ():
        total = sum(mean[f"{text}.{launch}"] for launch in ("fwd", "dq", "dkv"))
        print(json.dumps(dict(
            kernel=text, fwd_dq_dkv_ms=round(total * 1e3, 3),
            roofline_pct=round(100 * 7 * matmul / PEAK_FLOPS / total, 1))),
            flush=True)


def sweep():
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as bundled)
    from paddle_tpu.ops.flash_attention import _flash_block_sizes
    from paddle_tpu.ops.pallas_flash import flash_sdpa

    # flagship shard attention (4 q-heads d128) and a fatter 8-head case,
    # causal, plus D=64 and unequal-length rows the bundled kernel refuses
    shapes = [
        ("8b_shard_s2048", 4, 2048, 2048, 4, 128, True),
        ("8b_shard_s8192", 1, 8192, 8192, 4, 128, True),
        ("h8_s4096", 2, 4096, 4096, 8, 128, True),
        ("noncausal_s2048", 4, 2048, 2048, 4, 128, False),
        ("D64_s4096", 2, 4096, 4096, 8, 64, True),
        ("cross_causal_1k_to_8k", 1, 1024, 8192, 4, 128, True),
    ]
    for name, B, Sq, Sk, H, D, causal in shapes:
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, Sq, H, D), jnp.bfloat16)
        k = jnp.asarray(rng.randn(B, Sk, H, D), jnp.bfloat16)
        v = jnp.asarray(rng.randn(B, Sk, H, D), jnp.bfloat16)

        def intree(q, k, v):
            return flash_sdpa(q, k, v, causal=causal)

        def from_bundled(qh, kh, vh):
            return bundled(qh, kh, vh, causal=causal, sm_scale=D ** -0.5,
                           block_sizes=_flash_block_sizes(Sq, Sk))

        def grad(f):
            return jax.jit(jax.grad(lambda *a: jnp.sum(
                f(*a).astype(jnp.float32) ** 2), (0, 1, 2)))

        kernels = {"intree_fwd": (jax.jit(intree), (q, k, v)),
                   "intree_fwdbwd": (grad(intree), (q, k, v))}
        if Sq == Sk or not causal:
            heads = tuple(jnp.swapaxes(x, 1, 2) for x in (q, k, v))
            kernels["bundled_fwd"] = (jax.jit(from_bundled), heads)
            kernels["bundled_fwdbwd"] = (grad(from_bundled), heads)
        # same-run interleaved rounds: intree and bundled alternate within
        # each round; every ratio carries the per-round band
        runs = ab_rounds(kernels, rounds=3, reps=10)
        row = dict(shape=name, B=B, Sq=Sq, Sk=Sk, H=H, D=D, causal=causal,
                   rounds=3, **{n: band(t) for n, t in runs.items()})
        if "bundled_fwd" in runs:
            row["fwd_ratio_intree_over_bundled"] = ratio_band(
                runs["intree_fwd"], runs["bundled_fwd"])
            row["fwdbwd_ratio_intree_over_bundled"] = ratio_band(
                runs["intree_fwdbwd"], runs["bundled_fwdbwd"])
        print(json.dumps(row), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--text", action="append", default=[],
                    metavar="NAME=PATH",
                    help="another checkout's ops/pallas_flash.py to time "
                         "beside this one's")
    ap.add_argument("--sweep", action="store_true",
                    help="the older shapes against the bundled kernel")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--launches", nargs="+", default=["fwd", "dq", "dkv"],
                    choices=["fwd", "dq", "dkv"])
    ap.add_argument("--shape", type=int, nargs=5, default=[2, 16, 8192, 128, 512],
                    metavar=("B", "H", "S", "D", "BLOCK"),
                    help="default: the training cell's one-chip shape")
    args = ap.parse_args()

    import jax
    from paddle_tpu.ops import pallas_flash
    if jax.default_backend() != "tpu":
        print("WARNING: not on a TPU; the times mean nothing",
              file=sys.stderr)
    print(json.dumps(dict(device=str(jax.devices()[0].device_kind))))
    if args.sweep:
        return sweep()
    texts = {"change": pallas_flash}
    for item in args.text:
        name, path = item.split("=", 1)
        texts[name] = load_text("pallas_flash", name, path)
    cell(texts, *args.shape, rounds=args.rounds,
         launches=tuple(args.launches))


if __name__ == "__main__":
    main()
