"""The row append ALONE on the local chip, at the serving cells' launches
(PERF.md section 6, PR 51): `ops.fused.fused_append_rows` by cache-tile
runs against XLA's own scatter of the same rows and, with `--parent`,
against another checkout's `fused_append_rows` (the row-a-grid-step
kernel this tree no longer has: `(pages, rows, page_idx, page_off)`):

    chiprun -- python tools/append_sweep.py \
        --parent .archive_check/parent/paddle_tpu/ops/fused.py

One launch shape a cell (`LAUNCHES`), its row tables drawn from a seed
at each `--live` share of its slots (the chunk rides along from 50 % on);
`--chain` launches in ONE jitted program, the pools donated and carried
from launch to launch (in place, as the engine's are), timed on the
host's clock around `block_until_ready`, the least of `--repeats`. A
line a (launch, live share, kernel): ms a launch, the run table's live
runs of its G, us a live run. Before any timing the tool FAILS unless
the pools after one launch of every kernel are equal bit for bit —
every page, the trash page too, since idle rows write nothing. It
imports the kernels, is run by no cell, and appends its lines to
chiprun_out/append_sweep.jsonl.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: name -> the launch: decode slots, the chunk's rows, the pool(s)
#: [KV, pages, 256, D]; `pooled`: the rows are EvaByte's pooling slots
#: (a K / V pair, a slot a decode row and one for each of the 16 chunks
#: of 16 a prefill chunk closes: consecutive rows of one summary page)
LAUNCHES = {
    "xing": dict(slots=128, chunk=256, KV=1, pages=641, D=640),
    "axk1": dict(slots=32, chunk=256, KV=1, pages=2049, D=640),
    "ling": dict(slots=384, chunk=256, KV=1, pages=3073, D=640),
    "evabyte_pool": dict(slots=32, chunk=16, KV=32, pages=272, D=128,
                         pooled=True),
}
PSZ = 256


def _tables(spec, share, rng):
    """(page, offset, live) of the launch's T rows: `share` of the
    decode slots hold a row somewhere in a page of their own, the chunk
    (from a share of a half on) consecutive positions from a drawn
    start, across pages of its own; idle rows name the trash page 0."""
    B, C = spec["slots"], spec["chunk"]
    page, off = np.zeros(B + C, np.int32), np.zeros(B + C, np.int32)
    free = 1 + rng.permutation(spec["pages"] - 1)
    live = rng.permutation(B)[:max(int(round(share * B)), 1)]
    page[live] = free[:len(live)]
    off[live] = rng.randint(0, PSZ, len(live))
    if share >= 0.5:
        pos = rng.randint(0, PSZ) + np.arange(C)
        page[B:] = free[B + pos // PSZ]
        off[B:] = pos % PSZ
    return page, off, page > 0


def _load(path):
    """Another checkout's ops/fused.py as a module of THIS package (its
    relative imports resolve here; the oracles it registers on import
    are put back)."""
    from paddle_tpu.ops import fused, oracles  # noqa: F401 (registers)
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.ops._parent_fused", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    keep = dict(oracles._REGISTRY)
    spec.loader.exec_module(mod)
    oracles._REGISTRY.update(keep)
    return mod


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--launch", nargs="+", default=list(LAUNCHES))
    ap.add_argument("--live", type=float, nargs="+",
                    default=[0.05, 0.5, 1.0])
    ap.add_argument("--parent", help="another checkout's ops/fused.py")
    ap.add_argument("--chain", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pages", type=int, default=1 << 30,
                    help="cap the pools' pages (a rehearsal off the chip)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import fused

    if jax.default_backend() != "tpu":
        print("WARNING: not on a TPU; the times mean nothing",
              file=sys.stderr)
    parent = _load(args.parent) if args.parent else None
    bf16 = jnp.bfloat16
    tile = fused.append_tile(bf16, PSZ)
    out = []
    for name in args.launch:
        spec = LAUNCHES[name]
        B, C, KV, D = (spec[k] for k in ("slots", "chunk", "KV", "D"))
        T, pair = B + C, bool(spec.get("pooled"))
        G = B + -(-C // tile) + 1
        spec = dict(spec, pages=min(spec["pages"], args.pages))
        shape = (KV, spec["pages"], PSZ, D)
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        fresh = jax.jit(lambda k: jax.random.normal(k, shape, bf16))
        rows = tuple(jax.random.normal(k, (T, KV, D), bf16)
                     for k in keys[2:2 + 1 + pair])
        seq_start = jnp.arange(B + 1, dtype=jnp.int32)

        def runs_of(page, off, live):
            if pair:
                return fused.append_slot_run_table(page, off, tile=tile,
                                                   max_runs=G)
            nt = jnp.concatenate([live[:B], live[B:].sum()[None]])
            return fused.append_run_table(seq_start, nt.astype(jnp.int32),
                                          page, off, tile=tile, max_runs=G)

        # a kernel: what it makes of the row tables once a step (the
        # engine's layers share it), and its launch on that
        def by_runs(pools, runs):
            if pair:
                return fused.fused_append_rows(pools, rows, runs)
            return (fused.fused_append_rows(pools[0], rows[0], runs),)

        def by_rows(pools, tables):
            return tuple(parent.fused_append_rows(p, r, *tables[:2])
                         for p, r in zip(pools, rows))

        def by_scatter(pools, at):
            return tuple(p.at[:, at[0], at[1]].set(r.swapaxes(0, 1),
                                                   mode="drop")
                         for p, r in zip(pools, rows))

        kernels = {
            "runs": (runs_of, by_runs),
            # an idle row's page is sent out of range and dropped
            "xla_scatter": (lambda page, off, live: (
                jnp.where(live, page, shape[1]), off), by_scatter)}
        if parent is not None:
            kernels["parent_rows"] = (lambda *tables: tables, by_rows)
        for share in args.live:
            rng = np.random.RandomState(args.seed)
            tables = tuple(map(jnp.asarray, _tables(spec, share, rng)))
            table = np.asarray(jax.jit(runs_of)(*tables)).reshape(5, G)
            n_live = int((table[1] > 0).sum())
            assert table[1].sum() == int(tables[2].sum()), "G too small"

            # one launch of each from the same pools: every page equal.
            # (The parent's kernel writes its idle rows into the trash
            # page, whose content is garbage by its contract: page 0 is
            # compared among the kernels that leave it alone.)
            def bits(kname):
                made, launch = kernels[kname]
                pools = tuple(fresh(k) for k in keys[:1 + pair])
                got = jax.jit(lambda p, *t: launch(p, made(*t)))(
                    pools, *tables)
                return [jax.lax.bitcast_convert_type(g, jnp.uint16)
                        for g in got]

            want = bits("xla_scatter")
            for kname in kernels:
                if kname == "xla_scatter":
                    continue
                lo = int(kname == "parent_rows")
                for g, w in zip(bits(kname), want):
                    assert bool((g[:, lo:] == w[:, lo:]).all()), \
                        f"{name} {share}: {kname} differs from the scatter"
            del want

            for kname, (made, launch) in kernels.items():
                def chain(pools, *tables, _made=made, _launch=launch):
                    work = _made(*tables)
                    return jax.lax.fori_loop(
                        0, args.chain, lambda _, p: _launch(p, work), pools)

                run = jax.jit(chain, donate_argnums=0)
                pools = tuple(fresh(k) for k in keys[:1 + pair])
                pools = jax.block_until_ready(run(pools, *tables))
                times = []
                for _ in range(args.repeats):
                    t0 = time.perf_counter()
                    pools = jax.block_until_ready(run(pools, *tables))
                    times.append(time.perf_counter() - t0)
                del pools
                ms = 1e3 * min(times) / args.chain
                rec = dict(launch=name, live_share=share, kernel=kname,
                           ms_a_launch=ms, rows=T, live_rows=int(
                               tables[2].sum()), G=G, live_runs=n_live,
                           us_a_live_run=1e3 * ms / max(n_live, 1),
                           pools=1 + pair, pool_shape=list(shape),
                           device=jax.devices()[0].device_kind)
                print(json.dumps(rec), flush=True)
                out.append(rec)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/append_sweep.jsonl", "a") as f:
        for rec in out:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
