"""The Mamba-1 chunk scan ALONE, in three forms, at the Phi-4-mini-flash
cell's shape (256 rows x 5,120 channels x 16 state columns, float32),
on the chip:

    chiprun -- python tools/ssm1_scan_bench.py

1. ``lax.scan`` over the rows (`ops.references.ssm1_recurrence_reference`:
   256 sequential iterations of elementwise work on [16, 5120]);
2. ``lax.associative_scan`` over [256, 16, 5120] pairs (decay, input):
   log-depth, but every level reads and writes 84 MB operands;
3. the Pallas kernel (`ops.pallas_ssm.ssm1_chunk_scan`: a channel block's
   state in VMEM, rows walked in order), at each ``--cb`` lane width.

Prints ms a call (the median of ``--repeats`` after a warm-up) and the
largest difference from form 1; ``--rows 16 --channels 256`` is the
rehearsal here on the CPU.  Writes no file.
"""

import argparse
import statistics
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=256)
    ap.add_argument("--channels", type=int, default=5120)
    ap.add_argument("--state", type=int, default=16)
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--cb", type=int, nargs="*", default=[256, 512, 1024])
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import pallas_ssm
    from paddle_tpu.ops.references import ssm1_recurrence_reference

    L, C, N = args.rows, args.channels, args.state
    rng = np.random.default_rng(0)
    f32 = jnp.float32
    dt = jnp.asarray(np.exp(rng.uniform(np.log(2e-4), np.log(0.05),
                                        (L, C))), f32)
    x = jnp.asarray(rng.normal(0, 1, (L, C)), f32)
    a = -jnp.asarray(rng.uniform(1, 8, (N, C)), f32)
    bm = jnp.asarray(rng.normal(0, 1, (L, N)), f32)
    cm = jnp.asarray(rng.normal(0, 1, (L, N)), f32)
    s0 = jnp.asarray(rng.normal(0, 1, (1, N, C)), f32)

    def assoc(dt, x, a, bm, cm, s0):
        dec = jnp.exp(dt[:, None, :] * a[None])             # [L, N, C]
        inp = (dt * x)[:, None, :] * bm[:, :, None]
        # h_t = dec_t h_{t-1} + inp_t, from s0: fold s0 into row 0
        inp = inp.at[0].add(dec[0] * s0[0])

        def comb(l, r):
            return l[0] * r[0], r[0] * l[1] + r[1]

        _, h = jax.lax.associative_scan(comb, (dec, inp))
        return jnp.sum(h * cm[:, :, None], 1), h[-1][None]

    def timed(fn):
        f = jax.jit(fn)
        out = jax.block_until_ready(f(dt, x, a, bm, cm, s0))
        ts = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(f(dt, x, a, bm, cm, s0))
            ts.append(time.perf_counter() - t0)
        return out, 1e3 * statistics.median(ts)

    print(f"device {jax.devices()[0].device_kind}; rows {L}, channels {C}, "
          f"state {N}")
    (y0, s1), ms = timed(ssm1_recurrence_reference)
    print(f"lax.scan over rows: {ms:.3f} ms")
    (y, s), ms = timed(assoc)
    print(f"associative_scan: {ms:.3f} ms; max |dy| "
          f"{float(jnp.abs(y - y0).max()):.2e}, |ds| "
          f"{float(jnp.abs(s - s1).max()):.2e}")
    for cb in args.cb:
        if C % cb:
            continue
        pallas_ssm._CB = cb
        # (a fresh function a width: the jit cache is keyed by it)
        (y, s), ms = timed(lambda *o: pallas_ssm.ssm1_chunk_scan(*o))
        print(f"kernel, {cb} lanes a block: {ms:.3f} ms; max |dy| "
              f"{float(jnp.abs(y - y0).max()):.2e}, |ds| "
              f"{float(jnp.abs(s - s1).max()):.2e}")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ".")
    sys.exit(main())
