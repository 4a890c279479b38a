"""The ragged attention kernel ALONE on the local chip, at the serving
cells' launches, over the head block `hb` (PERF.md section 6, PR 42),
the tile block `tb` (PR 44) and the narrow window of a few-row
sequence's page visits (PR 45):

    chiprun -- python tools/ragged_head_sweep.py [--hb 1 2 4 8 16]
    chiprun -- python tools/ragged_head_sweep.py --hb 0 --tb 1 2 4 8 \
        --launch axk1_6k axk1_24k axk1_decode
    chiprun -- python tools/ragged_head_sweep.py --hb 0 --narrow 0 1
    chiprun -- python tools/ragged_head_sweep.py --hb 0 \
        --text parent=<tree>/paddle_tpu/ops/pallas_ragged.py

One launch shape a cell (`LAUNCHES`), its row tables drawn from a seed;
for every `hb` that divides the cell's KV heads and every `tb`,
`--chain` launches in ONE jitted program (each launch's output is the
next one's query, so nothing merges) timed on the host's clock around
`block_until_ready`, the least of `--repeats`. A line a (launch, hb,
tb): ms a launch, us a (KV head, page) visit, us a (tile, page) softmax
update, and whether the output equals the launch's first line's bit
for bit. `hb` and `tb` are forced by replacing
`pallas_ragged.ragged_head_block` / `ragged_tile_block` for the sweep
only; `0` leaves the kernel's own choice. `--narrow 0` forces the
window `ragged_narrow_rows` to 0 the same way (every visit computes the
tile's rows), `1` leaves the kernel's own; a line says the window, the
(tile, page) updates that ran on it, and with both given the tool fails
unless their outputs are equal bit for bit. `--depth` forces the ring's
slots the same way. `--text NAME=PATH` loads another checkout's
`pallas_ragged.py` beside this one's (PR 55: the parent's, unpacked
under .archive_check/): every line is then made once a text, this
tree's (`change`) first, the texts' repeats taking turns, and the tool
fails unless a text's output equals this tree's bit for bit. Appends
its lines to chiprun_out/ragged_head_sweep.jsonl.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [_TOOLS, os.path.dirname(_TOOLS)]
from bench_util import load_text  # noqa: E402

#: name -> the launch: T rows, q / KV heads, page size, pool pages, page
#: table width, decode contexts (lo, hi, how many of the slots are live),
#: the chunk's (rows, context before it), window, summary (EvaByte's
#: pooled rows: one a 16 tokens before the open 2,048-token window), D
#: the row's columns and v_dim (latent attention: K and V in ONE row)
LAUNCHES = {
    "mistral_decode": dict(T=288, H=32, KV=8, psz=256, pages=187, nj=16,
                           slots=32, live=32, ctx=(300, 750), chunk=None),
    "mistral_chunk": dict(T=288, H=32, KV=8, psz=256, pages=187, nj=16,
                          slots=32, live=32, ctx=(300, 750),
                          chunk=(256, 1024)),
    "ouro": dict(T=272, H=16, KV=16, psz=64, pages=320, nj=64, slots=16,
                 live=6, ctx=(150, 700), chunk=None),
    "evabyte": dict(T=288, H=32, KV=32, psz=256, pages=272, nj=16,
                    slots=32, live=10, ctx=(2048, 30000),
                    chunk=(256, 9216), summary=True),
    "laguna_full": dict(T=288, H=48, KV=8, psz=256, pages=1280, nj=128,
                        slots=32, live=32, ctx=(256, 4096),
                        chunk=(256, 20000)),
    "laguna_window": dict(T=288, H=72, KV=8, psz=256, pages=160, nj=128,
                          slots=32, live=32, ctx=(256, 4096),
                          chunk=(256, 20000), window=512),
    # A.X-K1's launch as `axk1-serve-longdoc-saturated` makes it: 64
    # query heads over one row of 640 columns, ~6 live decode rows at
    # 4-30 k beside a 256-row chunk at the cell's mean context and at
    # its p95 gap's; and the decode rows alone, which a block of query
    # tiles must not slow
    **{"axk1_" + name: dict(T=288, H=64, KV=1, D=640, v_dim=512, psz=256,
                            pages=2049, nj=128, slots=32, live=6,
                            ctx=(4096, 30000), chunk=chunk)
       for name, chunk in (("6k", (256, 6800 - 256)),
                           ("24k", (256, 24000 - 256)),
                           ("decode", None))},
    # Xing's launch as `xing4-serve-assistant-steady` makes it (ROADMAP
    # S13 c's regime): 384 rows, 32 query heads over the one latent row,
    # ~105 live decode rows at 64-3,072 beside a 256-row chunk at ~380
    # tokens of context; 128 slots of 18 pages over a pool of 641
    "xing_assist": dict(T=384, H=32, KV=1, D=640, v_dim=512, psz=256,
                        pages=641, nj=18, slots=128, live=105,
                        ctx=(64, 3072), chunk=(256, 380 - 256)),
}


def _tables(spec, rng):
    """seq_start, num_tokens, kv_lengths, page table, summary rows of
    one launch: slot i owns row i, the chunk the rows behind them."""
    B, psz, nj = spec["slots"], spec["psz"], spec["nj"]
    S = B + 1
    ss = np.append(np.arange(B), B).astype(np.int32)
    nt = np.zeros(S, np.int32)
    ctx = np.zeros(S, np.int64)
    live = rng.permutation(B)[:spec["live"]]
    nt[live] = 1
    ctx[live] = rng.randint(*spec["ctx"], size=len(live))
    if spec["chunk"] is not None:
        nt[B], ctx[B] = spec["chunk"][0], sum(spec["chunk"])
    window = spec.get("window")
    sr = np.zeros(S, np.int32)
    if spec.get("summary"):
        # pooled rows of the closed windows' chunks, then the open
        # window's exact rows from the next page boundary on
        sr = (ctx // 2048 * 2048 // 16).astype(np.int32)
        kvl = -(-sr // psz) * psz + ctx % 2048
        kvl = np.where(nt > 0, np.maximum(kvl, nt), 0)
    else:
        kvl = ctx
    tab = np.zeros((S, nj), np.int32)
    free = 1 + rng.permutation(spec["pages"] - 1)
    at = 0
    for i in range(S):
        n = -(-int(kvl[i]) // psz)
        first = 0 if window is None else \
            max(int(kvl[i]) - int(nt[i]) - window + 1, 0) // psz
        assert n <= nj, (n, nj)
        take = free[np.arange(at, at + n - first) % len(free)]
        tab[i, first:n] = take
        at += n - first
    return ss, nt, kvl.astype(np.int32), tab, sr


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--hb", type=int, nargs="+", default=[1, 2, 4, 8, 16, 0])
    ap.add_argument("--tb", type=int, nargs="+", default=[0])
    ap.add_argument("--narrow", type=int, nargs="+", default=[1],
                    choices=[0, 1])
    ap.add_argument("--depth", type=int, default=0)
    ap.add_argument("--launch", nargs="+", default=list(LAUNCHES))
    ap.add_argument("--chain", type=int, default=48)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--text", action="append", default=[],
                    metavar="NAME=PATH")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_ragged as pr

    if jax.default_backend() != "tpu":
        print("WARNING: not on a TPU; the times mean nothing",
              file=sys.stderr)
    texts = {"change": pr}
    for item in args.text:
        name, path = item.split("=", 1)
        texts[name] = load_text("pallas_ragged", name, path)
    # the choices are this tree's, forced on every text alike
    choose, choose_tb = pr.ragged_head_block, pr.ragged_tile_block
    choose_rows = pr.ragged_narrow_rows
    out = []
    for name in args.launch:
        spec = LAUNCHES[name]
        rng = np.random.RandomState(args.seed)
        T, H, KV, psz = spec["T"], spec["H"], spec["KV"], spec["psz"]
        D, v_dim = spec.get("D", 128), spec.get("v_dim")
        ss, nt, kvl, tab, sr = _tables(spec, rng)
        window = spec.get("window")
        rep = H // KV
        rows = pr.ragged_tile_tokens(T, rep, jnp.bfloat16) * rep
        counted = dict(T=T, rep=rep, dtype=jnp.bfloat16, page_size=psz,
                       pages_per_seq=spec["nj"], window=window)
        chains = pr.ragged_pages_visited(ss, nt, kvl, **counted)
        key = jax.random.PRNGKey(args.seed)
        kq, kk, kv_ = jax.random.split(key, 3)
        q = jax.random.normal(kq, (T, H, D), jnp.bfloat16)
        pool = (KV, spec["pages"], psz, D)
        kp = jax.random.normal(kk, pool, jnp.bfloat16)
        # a latent launch's output is its query with v_dim columns: the
        # chain pads it back to the row
        vp = None if v_dim else jax.random.normal(kv_, pool, jnp.bfloat16)
        tables = [jnp.asarray(x) for x in (ss, nt, kvl, tab)]
        summary = jnp.asarray(sr) if spec.get("summary") else None
        rest = (kp, vp, tables, summary)
        first = None
        for hb, tb, narrow in ((hb, tb, n) for hb in args.hb
                               for tb in args.tb for n in args.narrow):
            if hb and KV % hb:
                continue
            ones, runs = {}, {}
            for text, mod in texts.items():
                mod.ragged_head_block = choose if not hb else \
                    (lambda *a, _hb=hb, **k: _hb)
                mod.ragged_tile_block = choose_tb if not tb else \
                    (lambda *a, _tb=tb, **k: _tb)
                mod.ragged_narrow_rows = choose_rows if narrow else \
                    (lambda *a, **k: 0)
                if args.depth:
                    mod._page_buffers = lambda _bytes: args.depth
                mod._launch_jit.clear_cache()   # equal shapes: trace again

                # (the pools and tables are ARGUMENTS: closed over, they
                # would be constants of the program, 0.3-2.6 GB to compile)
                def launch(q, kp, vp, tables, summary, _mod=mod):
                    out = _mod.ragged_paged_attention(
                        q, kp, vp, *tables, window=window,
                        summary_rows=summary, v_dim=v_dim)
                    return out if not v_dim else jnp.pad(
                        out, ((0, 0), (0, 0), (0, D - v_dim)))

                def chain(q, *rest, _launch=launch):
                    return jax.lax.fori_loop(
                        0, args.chain, lambda _, x: _launch(x, *rest), q)

                try:
                    ones[text] = np.asarray(
                        jax.jit(launch)(q, *rest).astype(jnp.float32))
                    runs[text] = jax.jit(chain)
                    runs[text](q, *rest).block_until_ready()
                except Exception as e:  # noqa: BLE001 - the compiler's refusal
                    print(f"{name} {text} hb {hb} tb {tb}: REFUSED "
                          f"{str(e)[:300]!r}", flush=True)
            times = {text: [] for text in runs}
            for _ in range(args.repeats):       # the texts take turns
                for text, run in runs.items():
                    t0 = time.perf_counter()
                    run(q, *rest).block_until_ready()
                    times[text].append(time.perf_counter() - t0)
            used = hb or choose(KV, rows, D, psz, 2, latent=bool(v_dim))
            cell = tb or choose_tb(used, -(-T * rep // rows), rows, D, psz,
                                   2, v_dim)
            visits = pr.ragged_pages_visited(ss, nt, kvl, tb=cell, **counted)
            for text in runs:
                one = ones[text]
                if first is None:
                    first = one
                ms = min(times[text]) / args.chain * 1e3
                equal = bool(np.array_equal(one, first))
                if len(args.narrow) > 1 and not equal:
                    raise SystemExit(
                        f"{name} hb {used} tb {cell}: the narrow window's "
                        "output is not the tile's")
                if not np.array_equal(one, ones.get("change", one)):
                    raise SystemExit(f"{name} hb {used} tb {cell}: the "
                                     f"output of {text} is not this tree's")
                rec = dict(launch=name, text=text, hb=used, tb=cell,
                           forced=bool(hb or tb or not narrow),
                           narrow_rows=pr.ragged_narrow_rows(
                               rep, rows, jnp.bfloat16, cell),
                           narrow_updates_a_head=pr.ragged_narrow_updates(
                               ss, nt, kvl, tb=cell, **counted),
                           depth=args.depth, ms_a_launch=ms,
                           visits_a_head=visits,
                           us_a_head_visit=ms * 1e3 / (visits * KV),
                           us_a_block_visit=ms * 1e3 / (visits * KV // used),
                           tile_chains_a_head=chains,
                           us_a_tile_update=ms * 1e3 / (chains * KV),
                           equal_to_first=equal,
                           finite=bool(np.isfinite(one).all()),
                           device=jax.devices()[0].device_kind)
                out.append(rec)
                print(json.dumps(rec), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ragged_head_sweep.jsonl", "a") as f:
        f.writelines(json.dumps(rec) + "\n" for rec in out)


if __name__ == "__main__":
    main()
