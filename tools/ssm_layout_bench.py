"""The state pool's layout for fewer heads than lanes, kernel alone, on
the local chip (PERF.md section 6, PR 54).

Default: Falcon-H1-34B's state update as its cell runs it — 64 slots +
the spare of 32 heads x 128 over a state of 256 (float32), 58 live —
under three layouts of the pool, one decode step each, in place:

- ``state_minor`` [slots, H, P, N]: `ops.pallas_ssm`'s second layout
  (the state's 256 columns along the lanes, a step holds 8 heads);
- ``rows`` [slots, P, H x N]: a head's columns side by side in a row of
  8,192 lanes, a step holds 32 rows (the kernel is in THIS file: it is
  the candidate that lost, kept here so the sweep can be run again);
- ``heads_minor`` [slots, P, N, H]: `ops.pallas_ssm`'s first layout,
  which stores 128 lanes for the 32 heads (4 x the bytes).

    chiprun -- python tools/ssm_layout_bench.py

prints, for each: the bytes of the pool as the device holds it, the
time of a launch (``--chain`` launches back to back, each on the last
one's pool, waited for once; the median of ``--rounds`` such chains),
the bytes the live slots' state requires (once in, once out) over
that time as a share of 819 GB/s, and the largest difference of y and
of the new state from the token-by-token reference.  It prints; it
writes no file.
"""

from __future__ import annotations

import argparse
import functools
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HBM_BYTES_PER_S = 819e9   # one v5e chip (Google Cloud, "TPU v5e")


def rows_update(pool, slots, n_live, xdt, dec, bm, cm, *, H: int,
                interpret: bool):
    """One step of the recurrence over a pool [NS, P, H x N]: ``xdt``
    [R, P, H], ``dec`` [R, 1, H], ``bm`` / ``cm`` [R, G, N] float32.
    -> (y [NS, P, H], the pool).  Grid (B, P / PB)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    NS, P, HN = pool.shape
    N, B = HN // H, slots.shape[0]
    G = bm.shape[1]
    K = H // G
    PB = 32 if P % 32 == 0 else P
    J = P // PB

    def kernel(slots_ref, n_ref, xdt_ref, dec_ref, b_ref, c_ref, sin_ref,
               y_ref, sout_ref):
        i, n = pl.program_id(0), n_ref[0]

        @pl.when(i < n)
        def _step():
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, H), 1)
            xdt, dec = xdt_ref[0], dec_ref[0]
            y = jnp.zeros((PB, H), jnp.float32)
            for h in range(H):
                mine = lane == h
                col = jnp.sum(jnp.where(mine, xdt, 0.0), 1, keepdims=True)
                d = jnp.sum(jnp.where(mine, dec, 0.0), 1, keepdims=True)
                g = h // K
                new = d * sin_ref[0, :, h * N:(h + 1) * N] \
                    + col * b_ref[0, g:g + 1, :]
                sout_ref[0, :, h * N:(h + 1) * N] = new
                y = jnp.where(mine, jnp.sum(new * c_ref[0, g:g + 1, :], 1,
                                            keepdims=True), y)
            y_ref[0] = y

    def at(i, slots, n):
        last = jnp.maximum(n[0] - 1, 0)
        return jnp.clip(slots[jnp.minimum(i, last)], 0, NS - 1), i >= n[0]

    def tile(i, j, slots, n):
        s, idle = at(i, slots, n)
        return (s, jnp.where(idle, J - 1, j), 0)

    def whole(i, j, slots, n):
        return (at(i, slots, n)[0], 0, 0)

    state = pl.BlockSpec((1, PB, HN), tile)
    row = pl.BlockSpec((1, PB, H), tile)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, J),
        in_specs=[row, pl.BlockSpec((1, 1, H), whole),
                  pl.BlockSpec((1, G, N), whole),
                  pl.BlockSpec((1, G, N), whole), state],
        out_specs=[row, state])
    return pl.pallas_call(
        kernel, grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct((NS, P, H), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(slots, n_live, xdt, dec, bm, cm, pool)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--live", type=int, default=58)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--state", type=int, default=256)
    ap.add_argument("--groups", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--chain", type=int, default=50)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_ssm
    from paddle_tpu.ops.references import ssm_state_update_reference
    B, H, P, N, G = (args.slots, args.heads, args.head_dim, args.state,
                     args.groups)
    NS = B + 1
    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(0)
    f32 = jnp.float32
    state = jnp.asarray(rng.normal(0, 1, (NS, H, P, N)), f32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (NS, H)))
    xdt = jnp.asarray(rng.normal(0, 1, (NS, P, H)) * dt[:, None], f32)
    dec = jnp.asarray(np.exp(-dt * 3.0)[:, None, :], f32)
    bm = jnp.asarray(rng.normal(0, 1, (NS, G, N)), f32)
    cm = jnp.asarray(rng.normal(0, 1, (NS, G, N)), f32)
    live = rng.permutation(B)[:args.live]
    slots = np.full(B, B, np.int32)
    slots[:len(live)] = live
    slots, n_live = jnp.asarray(slots), jnp.asarray([len(live)], jnp.int32)
    want_y, want_s = ssm_state_update_reference(
        state, slots, n_live, xdt, dec, bm, cm, layout="state_minor")
    expand = lambda m: jnp.repeat(m, H // G, 1).swapaxes(1, 2)  # noqa: E731
    need = 2 * len(live) * H * P * N * 4
    cases = {
        "state_minor": (
            state, (bm, cm), lambda s: s,
            functools.partial(pallas_ssm.ssm_state_update,
                              layout=pallas_ssm.STATE_MINOR)),
        "rows": (
            state.transpose(0, 2, 1, 3).reshape(NS, P, H * N), (bm, cm),
            lambda s: s.reshape(NS, P, H, N).transpose(0, 2, 1, 3),
            functools.partial(rows_update, H=H, interpret=interpret)),
        "heads_minor": (
            state.transpose(0, 2, 3, 1), (expand(bm), expand(cm)),
            lambda s: s.transpose(0, 3, 1, 2), pallas_ssm.ssm_state_update),
    }
    print(f"device {jax.devices()[0].device_kind}; {B} + 1 slots of "
          f"[{H}, {P}, {N}] float32, {len(live)} live: {need / 1e6:.1f} MB "
          f"of state to move, {1e3 * need / HBM_BYTES_PER_S:.3f} ms at "
          f"{HBM_BYTES_PER_S / 1e9:.0f} GB/s")
    for name, (pool, (b, c), back, fn) in cases.items():
        step = jax.jit(fn, donate_argnums=0)
        try:
            compiled = step.lower(pool, slots, n_live, xdt, dec, b,
                                  c).compile()
            ma = compiled.memory_analysis()
            stored = ma.argument_size_in_bytes + ma.temp_size_in_bytes
            y, pool = step(pool, slots, n_live, xdt, dec, b, c)
            jax.block_until_ready(pool)
            err_y = float(jnp.abs(y[live] - want_y[live]).max())
            err_s = float(jnp.abs(back(pool) - want_s).max())
            took = []
            for _ in range(args.rounds):
                # a chain of launches, each on the last one's pool, waited
                # for once: the device runs them back to back and the
                # host's dispatch hides behind them
                t0 = time.perf_counter()
                for _ in range(args.chain):
                    y, pool = step(pool, slots, n_live, xdt, dec, b, c)
                jax.block_until_ready((y, pool))
                took.append((time.perf_counter() - t0) / args.chain)
            t = statistics.median(took)
            print(f"{name:12s} pool {tuple(pool.shape)}: arguments + "
                  f"temporaries {stored / 1e6:.1f} MB; a launch "
                  f"{1e3 * t:.3f} ms (min {1e3 * min(took):.3f}): "
                  f"{100 * need / HBM_BYTES_PER_S / t:.1f} % of the "
                  f"roofline; |dy| {err_y:.2e}, |dS| {err_s:.2e}")
        except Exception as e:  # noqa: BLE001 - a layout the compiler refuses
            print(f"{name:12s} FAILED: {type(e).__name__}: "
                  f"{str(e).splitlines()[0][:300]}")
        del pool
    return 0


if __name__ == "__main__":
    sys.exit(main())
