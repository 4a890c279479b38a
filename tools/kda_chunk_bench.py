"""The KDA chunk scan ALONE (`ops.pallas_kda.kda_chunk_scan`) at the Ling
cell's shapes — 32 heads x 128 x 128, the slot's state [32, 128, 128]
float32 — on the local chip (PERF.md section 6, PR 59): a 256-row chunk
in sub-chunks of 64, with 256 and with 160 live rows (the rest identity
rows, ``g`` 0 and ``beta`` 0), from zeros (a start) and from a state (a
continuation).

    chiprun -- python tools/kda_chunk_bench.py --text parent=.archive_check/parent/paddle_tpu/ops/pallas_kda.py

Forms: ``change``, this tree's `kda_chunk_scan` (plain XLA: the decayed
Gram matrices as matmuls between blocks of 16 rows, the heads
batch-major, the sub-chunks a Python loop), and ``--text NAME=PATH``,
another checkout's ``pallas_kda.py`` (as `tools/ssm_chunk_bench.py` does;
the parent's elementwise Gram forms under `vmap` and its `lax.scan`,
unpacked under .archive_check/).  A line says: DEVICE us a launch —
``--chain`` launches in ONE program (six, the cell's KDA blocks a step),
each its OWN rows (with the same rows XLA computes what does not read
the state once a program: PR 59's first reading, 4 x too fast) from the
last one's state; the program is traced (`jax.profiler`)
and the device's events are added up, loops and conditionals left out
(their bodies' events count) — with the largest events beside it;
the FLOPs the WY form requires (`benchmarks/lib/costs_ling.
kda_chunk_cost`'s count at sub-chunks of 64) over that time as a share
of the MXU's six-pass float32 peak (197 / 6 TFLOP/s); the host's clock
around the program, waited for once (the texts' repeats take turns, the
least is kept); and the largest difference of the live rows' o and of
the state from the token-by-token reference
(`ops.references.kda_recurrence_reference`).  It prints; it writes no
file.  ``--rows 32 --live 32 20 --chain 2 --repeats 1`` is the rehearsal
here on the CPU (no device events there: the device's time says "not
measured").  A form that is fast ALONE can be slow inside the step
program (PR 57): the cell's traced run decides.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_util import device_events, load_text  # noqa: E402

BF16_FLOPS = 197e12                          # one v5e chip

#: heads, key width, value width, sub-chunk: `ling-3.0-flash-serve-ep8-d7`
H, K, V, SUB = 32, 128, 128, 64


def required_flops(rows: int) -> float:
    """The FLOPs the benchmark's `kda_chunk_roofline` counts for a chunk
    (`benchmarks/lib/costs_ling.kda_chunk_cost`)."""
    from benchmarks.lib.costs_ling import kda_chunk_cost
    assert SUB == 64 and K == V     # what that count is taken at
    return kda_chunk_cost({"num_attention_heads": H, "head_dim": K},
                          rows, starts=False)[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=256)
    ap.add_argument("--live", type=int, nargs="*", default=[256, 160])
    ap.add_argument("--chain", type=int, default=6)
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--text", action="append", default=[],
                    metavar="NAME=PATH")
    ap.add_argument("--top", type=int, default=3,
                    help="the largest events a line names")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kda
    from paddle_tpu.ops.references import kda_recurrence_reference
    texts = {"change": pallas_kda.kda_chunk_scan}
    for item in args.text:
        name, path = item.split("=", 1)
        texts[name] = load_text("pallas_kda", name, path).kda_chunk_scan
    f32 = jnp.float32
    C = args.rows
    flops = required_flops(C)
    print(f"device {jax.devices()[0].device_kind}; {H} heads x {K} x {V}, a "
          f"{C}-row chunk in sub-chunks of {SUB}, {args.chain} launches a "
          f"program, least of {args.repeats}; {flops / 1e9:.2f} GFLOP = "
          f"{1e6 * 6 * flops / BF16_FLOPS:.1f} us at six passes")
    rng = np.random.default_rng(0)
    N = args.chain + 1      # a launch of the chain its own rows, and one
    q, k = rng.normal(size=(2, N, C, H, K))
    q = jnp.asarray(q / np.linalg.norm(q, axis=-1, keepdims=True)
                    * K ** -0.5, f32)
    k = jnp.asarray(k / np.linalg.norm(k, axis=-1, keepdims=True), f32)
    v = jnp.asarray(rng.normal(size=(N, C, H, V)), f32)
    g0 = jnp.asarray(rng.uniform(-5.0, 0.0, size=(N, C, H, K)), f32)
    beta0 = jnp.asarray(rng.uniform(0, 1, size=(N, C, H)), f32)
    held = jnp.asarray(rng.normal(size=(H, K, V)), f32)
    for live in args.live:
        valid = jnp.arange(C) < live
        g = jnp.where(valid[:, None, None], g0, 0)
        beta = jnp.where(valid[:, None], beta0, 0)
        rows = (q, k, v, g, beta)
        for start in (True, False):
            s0 = jnp.zeros_like(held) if start else held
            want_o, want_s = jax.jit(kda_recurrence_reference)(
                *(a[-1, :live] for a in rows), s0)
            runs, errs = {}, {}
            for text, scan in texts.items():
                def chain(q, k, v, g, beta, s, scan=scan):
                    # as a step's blocks: each launch its own rows (or
                    # what does not read the state is computed once a
                    # program) from the last one's state, every o kept
                    total = 0.0
                    for i in range(args.chain):
                        o, s = scan(q[i], k[i], v[i], g[i], beta[i], s,
                                    chunk=SUB)
                        total = total + o
                    return total, s
                o, s1 = jax.jit(lambda *a, scan=scan: scan(*a, chunk=SUB))(
                    *(a[-1] for a in rows), s0)
                errs[text] = (float(jnp.abs(o[:live] - want_o).max()),
                              float(jnp.abs(s1 - want_s).max()))
                runs[text] = jax.jit(chain)
                jax.block_until_ready(runs[text](*rows, s0))
            times = {text: [] for text in runs}
            for _ in range(args.repeats):       # the texts take turns
                for text, run in runs.items():
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(*rows, s0))
                    times[text].append(time.perf_counter() - t0)
            for text, run in runs.items():
                by_kind = device_events(run, (*rows, s0))
                t = sum(by_kind.values()) / args.chain
                top = sorted(by_kind.items(), key=lambda kv: -kv[1])[:args.top]
                timed = "device: no events (not measured)" if not t else (
                    f"device {1e6 * t:.1f} us a launch ("
                    + "; ".join(f"`{n}` {1e6 * s / args.chain:.1f}"
                                for n, s in top)
                    + f"), {100 * 6 * flops / BF16_FLOPS / t:.1f} % of the "
                    f"six-pass peak")
                print(f"  {live:3d} live, "
                      f"{'a start' if start else 'a continuation'}, "
                      f"{text}: {timed}; the host's clock "
                      f"{1e3 * min(times[text]) / args.chain:.3f} ms; "
                      f"|do| {errs[text][0]:.2e} (max |o| "
                      f"{float(jnp.abs(want_o).max()):.1f}), |dS| "
                      f"{errs[text][1]:.2e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
