"""The Mamba-2 chunk scan ALONE (`ops.pallas_ssm.ssm_chunk_scan`), at the
two cells' shapes, on the local chip (PERF.md section 6, PR 57):

- ``falcon``: Falcon-H1-34B — 32 heads x 128 in 2 groups over a state of
  256, the slot STATE-minor [32, 128, 256];
- ``nemotron``: Nemotron-3-Super — 128 heads x 64 in 8 groups over a
  state of 128, the slot HEADS-minor [64, 128, 128], which the kernel's
  caller turns around the launch;

a 256-row chunk in scan chunks of 128, with 256 and with 100 live rows
(the rest identity rows, ``dt`` 0), from zeros (a start) and from a
state (a continuation).

    chiprun -- python tools/ssm_chunk_bench.py --text parent=.archive_check/parent/paddle_tpu/ops/pallas_ssm.py

Three forms: ``change``, this tree's `ssm_chunk_scan` (plain XLA, the
heads batch-major, the scan chunks a Python loop); ``kernel``, the
Pallas launch in THIS file (`kernel_scan`: a block of 8 or 16 heads of
one group a grid cell, its state resident in VMEM over its scan chunks,
the two products with the state for all the block's heads at once) — the
candidate ISSUE 57 asked for, which lost by that issue's own rule (the
plain form within 0.2 ms a launch of it, and less code) and is kept here
so that the timing can be made again; and ``--text NAME=PATH``, another
checkout's ``pallas_ssm.py`` (as `tools/ragged_head_sweep.py` does; the
parent's `lax.scan` form, unpacked under .archive_check/).  A line says:
DEVICE us a launch — ``--chain`` launches in ONE program, each from the last one's state, as a
step's layers run them; the program is traced (`jax.profiler`) and the
device's events are added up, loops and conditionals left out (their
bodies' events count) — with the largest event beside it; the FLOPs the
chunk requires (``2 L L N G + 2 L L P H + 4 L N P H`` a scan chunk) over
that time as a share of the MXU's six-pass float32 peak (197 / 6
TFLOP/s: over 100 % where a form runs products in fewer passes — the
plain forms' ``C B^T`` and ``M x'`` at the default precision are one,
and XLA's ``HIGHEST`` reads faster than six full ones), the bytes it
has to move (x', dt A, B, C in float32, y out,
the state once in and once out) as a share of 819 GB/s; the host's clock
around the program, waited for once (the texts' repeats take turns, the
least is kept: on a one-chip machine ~0.1 ms a launch of it is dispatch,
PR 57); and the largest difference of the live rows' y and of the state
from the token-by-token reference
(`ops.references.ssm_recurrence_reference`).  It prints; it writes no
file.  ``--rows 40 --chain 2 --repeats 1`` is the rehearsal here on the
CPU (no device events there: the device's time says "not measured").
CAVEAT (PR 59): every launch of the chain reads the SAME rows, so XLA
computes what does not read the state (``C B^T``, the decays) ONCE a
program and a launch reads too fast by that share;
`tools/kda_chunk_bench.py` gives each launch its own rows.
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

BF16_FLOPS, HBM_BYTES_PER_S = 197e12, 819e9   # one v5e chip
#: heads, head width, groups, state, scan chunk
SHAPES = {"falcon": (32, 128, 2, 256, 128),
          "nemotron": (128, 64, 8, 128, 128)}


def _kernel(x_ref, cs_ref, cst_ref, b_ref, c_ref, s0_ref, y_ref, s1_ref, *,
            HB: int, P: int, R: int = 1):
    """One scan chunk of one block of HB heads of ONE group; R heads a
    128-lane tile where P < 128."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _start():
        s1_ref[...] = s0_ref[...]

    L, N = b_ref.shape
    TW = R * P

    def dot(a, b, ca: int, cb: int):
        return jax.lax.dot_general(
            a, b, (((ca,), (cb,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    bm, cm = b_ref[...], c_ref[...]                     # [L, N]
    cb = dot(cm, bm, 1, 1)                              # [L, L], a group's
    below = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    cs = cs_ref[...]                                    # [L, HB]
    head = jax.lax.broadcasted_iota(jnp.int32, (1, HB), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, TW), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (TW, 1), 0)
    s = s1_ref[...].reshape(HB * P, N)      # rows (head, p): free
    # from the state the scan chunk starts with, every head at once:
    # the lanes of y, (head, p), are the rows of the state
    ys = dot(cm, s, 1, 1)                               # [L, HB * P]
    xw, dec = [], []
    for tl in range(HB * P // TW):
        at = slice(tl * TW, (tl + 1) * TW)
        xt = x_ref[:, at]                               # [L, TW]
        y = e = w = d = 0.0
        for k in range(R):
            h = tl * R + k
            col = jnp.sum(jnp.where(head == h, cs, 0.0), 1, keepdims=True)
            m = jnp.exp(jnp.where(below, col - cst_ref[h:h + 1, :],
                                  -jnp.inf)) * cb
            last = col[L - 1:L]                         # [1, 1]
            if R == 1:
                y = dot(m, xt, 1, 0)
                e, w, d = jnp.exp(col), jnp.exp(last - col), jnp.exp(last)
                continue
            # several heads a 128-lane tile: each head's product over
            # the whole tile, the others' lanes zeroed
            mine = (lane >= k * P) & (lane < (k + 1) * P)
            y = y + dot(m, jnp.where(mine, xt, 0.0), 1, 0)
            e = jnp.where(mine, jnp.exp(col), e)
            w = jnp.where(mine, jnp.exp(last - col), w)
            d = jnp.where((row >= k * P) & (row < (k + 1) * P),
                          jnp.exp(last), d)
        y_ref[:, at] = y + e * ys[:, at]
        xw.append(xt * w)
        dec.append(jnp.broadcast_to(d, (TW, 1)))
    xw = xw[0] if len(xw) == 1 else jnp.concatenate(xw, 1)
    dec = dec[0] if len(dec) == 1 else jnp.concatenate(dec, 0)
    # the state the scan chunk leaves, every head at once
    s1_ref[...] = (dec * s + dot(xw, bm, 0, 0)).reshape(HB, P, N)


def kernel_scan(xdt, dA, bm, cm, state, *, chunk: int = 128,
                layout: str = "heads_minor"):
    """`ops.pallas_ssm.ssm_chunk_scan`'s operands and results through ONE
    Pallas launch: grid (H / HB, L / chunk), the block's state [HB, P,
    N] the resident output block; x' and y ride as [L, H x P], B and C
    as [L, G x N], the rows' cumulative decays in both orientations.  On
    a TPU N and HB x P have to be whole 128-lane registers."""
    import functools
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops import pallas_ssm
    L, H, P = xdt.shape
    G, N = bm.shape[1:]
    K = H // G
    f32 = jnp.float32
    pad = -L % chunk
    xdt, dA, bm, cm = (
        jnp.pad(a.astype(f32), ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        for a in (xdt, dA, bm, cm))
    Lp, nc = L + pad, (L + pad) // chunk
    HB = pallas_ssm._p_block(K, P * N * 4)
    J = H // HB
    TW = P if P % 128 == 0 else \
        128 if 128 % P == 0 and HB * P % 128 == 0 else HB * P
    cs = jnp.cumsum(dA.reshape(nc, chunk, H), 1)        # <= 0
    sm = layout == pallas_ssm.STATE_MINOR
    s0 = state.astype(f32) if sm else state.astype(f32).transpose(2, 0, 1)
    rows_spec = pl.BlockSpec((chunk, HB * P), lambda j, c: (c, j))
    group_spec = pl.BlockSpec((chunk, N), lambda j, c: (c, j * HB // K))
    state_spec = pl.BlockSpec((HB, P, N), lambda j, c: (j, 0, 0))
    y, s1 = pl.pallas_call(
        functools.partial(_kernel, HB=HB, P=P, R=TW // P),
        grid=(J, nc),
        in_specs=[rows_spec,
                  pl.BlockSpec((None, chunk, HB), lambda j, c: (j, c, 0)),
                  pl.BlockSpec((None, None, HB, chunk),
                               lambda j, c: (c, j, 0, 0)),
                  group_spec, group_spec, state_spec],
        out_specs=[rows_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((Lp, H * P), f32),
                   jax.ShapeDtypeStruct((H, P, N), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=jax.default_backend() != "tpu",
    )(xdt.reshape(Lp, H * P),
      cs.reshape(nc, chunk, J, HB).transpose(2, 0, 1, 3).reshape(J, Lp, HB),
      cs.transpose(0, 2, 1).reshape(nc, J, HB, chunk),
      bm.reshape(Lp, G * N), cm.reshape(Lp, G * N), s0)
    return y.reshape(Lp, H, P)[:L], s1 if sm else s1.transpose(1, 2, 0)


def device_seconds(run, args, calls: int = 3):
    """(device seconds a call, the largest event's stem and its seconds a
    call) of ``run(*args)``: `bench_util.device_events` added up."""
    by_kind = device_events(run, args, calls)
    top = max(by_kind.items(), key=lambda kv: kv[1], default=("-", 0.0))
    return sum(by_kind.values()), top[0], top[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--shape", nargs="*", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--rows", type=int, default=256)
    ap.add_argument("--live", type=int, nargs="*", default=[256, 100])
    ap.add_argument("--chain", type=int, default=9)
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--text", action="append", default=[],
                    metavar="NAME=PATH")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_ssm
    from paddle_tpu.ops.references import ssm_recurrence_reference
    texts = {"change": pallas_ssm.ssm_chunk_scan, "kernel": kernel_scan}
    for item in args.text:
        name, path = item.split("=", 1)
        texts[name] = load_text("pallas_ssm", name, path).ssm_chunk_scan
    f32 = jnp.float32
    C = args.rows
    print(f"device {jax.devices()[0].device_kind}; a {C}-row chunk, "
          f"{args.chain} launches a program, least of {args.repeats}")
    for shape in args.shape:
        H, P, G, N, chunk = SHAPES[shape]
        layout = pallas_ssm.state_layout(H, N)
        sm = layout == pallas_ssm.STATE_MINOR
        rng = np.random.default_rng(0)
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (C, H)))
        x = jnp.asarray(rng.normal(0, 1, (C, H, P)), f32)
        dA = jnp.asarray(-dt * rng.uniform(1, 16, H), f32)
        bm = jnp.asarray(rng.normal(0, 1, (C, G, N)), f32)
        cm = jnp.asarray(rng.normal(0, 1, (C, G, N)), f32)
        held = jnp.asarray(rng.normal(0, 1, pallas_ssm.state_pool_shape(
            1, H, P, N, layout)[1:]), f32)
        nc = -(-C // chunk)
        flops = nc * (2.0 * chunk * chunk * N * G + 2.0 * chunk * chunk * P * H
                      + 4.0 * chunk * N * P * H)
        byts = 4.0 * (2 * C * H * P + C * H + 2 * C * G * N + 2 * H * P * N)
        print(f"{shape}: {H} heads x {P} in {G} groups over {N}, slot "
              f"{tuple(held.shape)} ({layout}); {flops / 1e9:.2f} GFLOP = "
              f"{1e6 * 6 * flops / BF16_FLOPS:.1f} us at six passes, "
              f"{byts / 1e6:.1f} MB = {1e6 * byts / HBM_BYTES_PER_S:.1f} us")
        for live in args.live:
            valid = (np.arange(C) < live)[:, None]
            xdt = x * jnp.asarray(dt * valid, f32)[..., None]
            da = jnp.where(valid, dA, 0)
            for start in (True, False):
                s0 = jnp.zeros_like(held) if start else held
                turned = s0 if not sm else s0.transpose(1, 2, 0)
                want_y, want_s = jax.jit(ssm_recurrence_reference)(
                    xdt[:live], da[:live], bm[:live], cm[:live], turned)
                runs, errs = {}, {}
                for text, scan in texts.items():
                    one = jax.jit(lambda *a, scan=scan: scan(
                        *a, chunk=chunk, layout=layout))

                    def chain(xdt, da, bm, cm, s, scan=scan):
                        # as a step's layers: each launch from the last
                        # one's state, every y kept alive
                        total = 0.0
                        for _ in range(args.chain):
                            y, s = scan(xdt, da, bm, cm, s, chunk=chunk,
                                        layout=layout)
                            total = total + y
                        return total, s
                    try:
                        y, s1 = one(xdt, da, bm, cm, s0)
                        got_s = s1 if not sm else s1.transpose(1, 2, 0)
                        errs[text] = (
                            float(jnp.abs(y[:live] - want_y).max()),
                            float(jnp.abs(got_s - want_s).max()))
                        runs[text] = jax.jit(chain)
                        jax.block_until_ready(
                            runs[text](xdt, da, bm, cm, s0))
                    except Exception as e:  # noqa: BLE001 - a refusal
                        print(f"  {text}: REFUSED {type(e).__name__}: "
                              f"{str(e).splitlines()[0][:300]}")
                        runs.pop(text, None)
                times = {text: [] for text in runs}
                for _ in range(args.repeats):   # the texts take turns
                    for text, run in runs.items():
                        t0 = time.perf_counter()
                        jax.block_until_ready(run(xdt, da, bm, cm, s0))
                        times[text].append(time.perf_counter() - t0)
                for text, run in runs.items():
                    dev, top, top_s = device_seconds(
                        run, (xdt, da, bm, cm, s0))
                    t = dev / args.chain
                    timed = "device: no events (not measured)" if not t else (
                        f"device {1e6 * t:.1f} us a launch (`{top}` "
                        f"{1e6 * top_s / args.chain:.1f}), "
                        f"{100 * 6 * flops / BF16_FLOPS / t:.1f} % of the "
                        f"six-pass peak, "
                        f"{100 * byts / HBM_BYTES_PER_S / t:.1f} % of the "
                        f"bytes' bound")
                    print(f"  {live:3d} live, "
                          f"{'a start' if start else 'a continuation'}, "
                          f"{text}: {timed}; the host's clock "
                          f"{1e3 * min(times[text]) / args.chain:.3f} ms; "
                          f"|dy| {errs[text][0]:.2e} (max |y| "
                          f"{float(jnp.abs(want_y).max()):.1f}), |dS| "
                          f"{errs[text][1]:.2e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
