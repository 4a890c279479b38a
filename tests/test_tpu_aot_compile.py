"""Compile the Pallas kernels of the llama train and serve paths for a
DESCRIBED TPU v5e, at the widths chip_smoke.py runs — the one file of
its kind (on-chip-measurement guide §2 step 3).

The TPU compiler is installed off-chip and compiles for a chip that is
described, not attached; interpret mode never sees what it refuses (a
block the tiling forbids, a store at an unaligned dynamic sublane
offset, a lane->sublane reshape, too much VMEM).  Nothing runs here: a
compile that passes is not a chip run.

Rules this file keeps: the topology is described inside a module-scoped,
non-autouse fixture (never at import, in a skipif, in parametrize args
or in conftest.py — only one process may load libtpu and every xdist
worker imports every test file); compiles happen in the test's own
process; the persistent compile cache is off around them; and the
kernels' ``_interpret`` switches are steered from here, not through a
program option.
"""

import functools
import math
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest

# the llama3_8b_shard_config(mp=8, pp=4) geometry under the smoke's
# engine: hidden 4096, 4 q / 1 kv heads x 128, FFN 1792, vocab slice
# 16032; T = 8 slots + 32 prefill-chunk rows, page 16, 8*128+1 pages
T, H, HQ, KV, D, I = 40, 4096, 4, 1, 128, 1792
PSZ, NP, S, NJ = 16, 8 * 128 + 1, 9, 128
N = (HQ + 2 * KV) * D
F32, I8, I32 = jnp.float32, jnp.int8, jnp.int32
ALGOS = {"bf16": None, "int8": "weight_only_int8",
         "int4": "weight_only_int4"}


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip: ``shape(dims, dtype)`` builds an argument
    placed on it, ``compiles(fn, *shapes)`` says whether the TPU compiler
    accepts ``jit(fn)`` (and keeps the reason when it does not, the
    compiled text under ``texts[fn]`` when it does)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops import (fused, pallas_flash, pallas_kda,
                                pallas_megadecode, pallas_megafront,
                                pallas_mhc, pallas_ragged, pallas_ssm, quant)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    mp = pytest.MonkeyPatch()
    for mod in (fused, pallas_flash, pallas_megadecode, pallas_megafront,
                pallas_ragged, pallas_ssm, pallas_kda, pallas_mhc, quant):
        mp.setattr(mod, "_interpret", lambda: False)
    # a described-device executable is written to the persistent cache
    # but cannot be read back without a chip: keep it off around these
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # conftest pins "highest" for the CPU numerics tests; the chip runs
    # the default, and that is what must compile
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)

    verdicts, refusals, texts = {}, {}, {}

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def compiles(fn, *shapes):
        key = (fn, shapes)      # each kernel variant compiles once
        if key not in verdicts:
            try:
                texts[fn] = jax.jit(fn).lower(*shapes).compile().as_text()
                verdicts[key] = True
            except Exception as e:  # noqa: BLE001 - the compiler's refusal
                refusals[fn] = str(e)[:400]
                verdicts[key] = False
        return verdicts[key]

    yield types.SimpleNamespace(shape=shape, compiles=compiles,
                                refusals=refusals, texts=texts,
                                devices=topo.devices)
    mp.undo()
    jax.config.update("jax_default_matmul_precision", precision_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()


def _pools(chip, kv=KV, d=D):
    p = chip.shape((kv, NP, PSZ, d))
    return p, p


def _quant_w(chip, algo, k, n):
    """(weight, scale) shapes of a [k, n] projection in deploy layout."""
    if algo == "bf16":
        return chip.shape((k, n)), None
    rows = k // 2 if algo == "int4" else k
    return chip.shape((rows, n), I8), chip.shape((n,), F32)


# -- the kernels, as module-level callables so verdict keys are stable --

def _front(algo):
    from paddle_tpu.ops.pallas_megafront import fused_qkv_rope_append

    def f(h, w, sc, c, s, kp, vp, pg, off):
        return fused_qkv_rope_append(h, w, sc, None, c, s, kp, vp, pg, off,
                                     heads=HQ, kv_heads=KV, head_dim=D,
                                     algo=ALGOS[algo])
    return f


def _oproj(algo):
    from paddle_tpu.ops.pallas_megadecode import fused_oproj_norm
    return lambda o, x, w, sc, nw: fused_oproj_norm(
        o, x, w, sc, None, nw, None, algo=ALGOS[algo])


def _ffn(algo):
    from paddle_tpu.ops.pallas_megadecode import fused_ffn
    return lambda h, x, wg, sg, wu, su, wd, sd: fused_ffn(
        h, x, wg, sg, wu, su, wd, sd, algo=ALGOS[algo])


_FRONT = {a: _front(a) for a in ALGOS}
_OPROJ = {a: _oproj(a) for a in ALGOS}
_FFN = {a: _ffn(a) for a in ALGOS}


def _front_args(chip, algo):
    w, sc = _quant_w(chip, algo, H, N)
    tok = chip.shape((T,), I32)
    trig = chip.shape((T, D // 2), F32)
    return (chip.shape((T, H)), w, sc, trig, trig, *_pools(chip), tok, tok)


def _back_compiles(chip, algo, hidden, inter, ow):
    """Both back-half kernels at one geometry."""
    act = chip.shape((T, hidden))
    w, sc = _quant_w(chip, algo, ow, hidden)
    ok1 = chip.compiles(_OPROJ[algo], chip.shape((T, ow)), act, w, sc,
                        chip.shape((hidden,)))
    wg, sg = _quant_w(chip, algo, hidden, inter)
    wd, sd = _quant_w(chip, algo, inter, hidden)
    ok2 = chip.compiles(_FFN[algo], act, act, wg, sg, wg, sg, wd, sd)
    return ok1 and ok2


# ---------------------------------------------------------------------------
# serve path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", list(ALGOS))
def test_fused_qkv_rope_append_compiles(chip, algo):
    """The default front half (one-token blocks, page-row append)."""
    f = _FRONT[algo]
    assert chip.compiles(f, *_front_args(chip, algo)), chip.refusals.get(f)


def _rope_append(q, k, v, c, s, kp, vp, ss, nt, pg, off):
    """The step's two pieces: the run table from the row tables, once,
    and the kernel that works by it (32 slots + a 256-row chunk where T
    is 288, the smoke's 8 + 32 else)."""
    from paddle_tpu.ops.fused import fused_rope_append
    return fused_rope_append(q, k, v, c, s, kp, vp,
                             _run_table(ss, nt, pg, off, kp))


def _rope_append_args(chip, t, hq, kv, s, n_pages, psz):
    tok, seq = chip.shape((t,), I32), chip.shape((s,), I32)
    trig = chip.shape((t, D // 2), F32)
    pool = chip.shape((kv, n_pages, psz, D))
    return (chip.shape((t, hq, D)), chip.shape((t, kv, D)),
            chip.shape((t, kv, D)), trig, trig, pool, pool, seq, seq,
            tok, tok)


@pytest.mark.parametrize("t,hq,kv,s,n_pages,psz", [
    (T, HQ, KV, S, NP, PSZ), (288, 32, 8, 33, 187, 256),
    (288, 32, 32, 33, 272, 256), (288, 72, 8, 33, 160, 256),
    (288, 48, 8, 33, 1280, 256), (896, 32, 4, 161, 1281, 256),
    (128, 32, 4, 33, 1281, 256)],
    ids=["smoke", "mistral", "evabyte_kv32", "laguna_hq72", "laguna_hq48",
         "sdar", "sdar_riding"])
def test_fused_rope_append_compiles(chip, t, hq, kv, s, n_pages, psz):
    """The engine's front half: projections, then rope + append by
    cache-tile runs — one (KV, 1, 16, 128) block of K and of V a grid
    step, at the smoke's widths and at the serving cells' (Mistral,
    EvaByte's 32 KV heads, Laguna's two head counts, SDAR's launch of
    896 rows whose K and V stay resident, and the call of their own its
    32 riding commits' 128 rows take). The compiler's
    default VMEM holds each: nothing asks for more."""
    import inspect
    from paddle_tpu.ops import fused
    assert "vmem_limit_bytes" not in inspect.getsource(
        fused.fused_rope_append)
    assert chip.compiles(
        _rope_append, *_rope_append_args(chip, t, hq, kv, s, n_pages, psz)),\
        chip.refusals.get(_rope_append)


def _run_table(ss, nt, pg, off, pages):
    """The step's one work list of its appends, as the engine bounds
    it: a decode row a run, the chunk's one for each tile it touches."""
    from paddle_tpu.ops.fused import append_run_table, append_tile
    tile = append_tile(pages.dtype, pages.shape[2])
    slots = ss.shape[0] - 1
    bound = slots + -(-(pg.shape[0] - slots) // tile) + 1
    return append_run_table(ss, nt, pg, off, tile=tile, max_runs=bound)


def _append_rows(pages, rows, ss, nt, pg, off):
    from paddle_tpu.ops.fused import fused_append_rows
    return fused_append_rows(pages, rows, _run_table(ss, nt, pg, off, pages),
                             scope="cache_write")


@pytest.mark.parametrize("t,s,n_pages,psz,width", [
    (T, S, NP, PSZ, 576), (384, 129, 641, 256, 640),
    (640, 385, 3073, 256, 640)], ids=["smoke", "xing", "ling"])
def test_fused_append_rows_compiles(chip, t, s, n_pages, psz, width):
    """The latent-row append by cache-tile runs ([latent 512 | rope 64]
    rows, ONE (1, 1, 16, width) block a grid step, the launch's rows
    resident as float32 [T, width]) at the smoke's widths and at the
    Xing and Ling cells': T 384 / G 145 and T 640 / G 401."""
    tok, seq = chip.shape((t,), I32), chip.shape((s,), I32)
    assert chip.compiles(_append_rows, chip.shape((1, n_pages, psz, width)),
                         chip.shape((t, 1, width)), seq, seq, tok, tok), \
        chip.refusals.get(_append_rows)


def _ragged(q, kp, vp, ss, nt, kvl, tab):
    from paddle_tpu.ops.pallas_ragged import ragged_paged_attention
    return ragged_paged_attention(q, kp, vp, ss, nt, kvl, tab)


def test_ragged_paged_attention_compiles_where_gate_says(chip):
    from paddle_tpu.ops.pallas_ragged import ragged_kernel_eligible
    seq = chip.shape((S,), I32)
    assert ragged_kernel_eligible(HQ, KV, D, PSZ)
    assert chip.compiles(_ragged, chip.shape((T, HQ, D)), *_pools(chip),
                         seq, seq, seq, chip.shape((S, NJ), I32)), \
        chip.refusals.get(_ragged)


def _ragged_one_row(q, pool, ss, nt, kvl, tab):
    from paddle_tpu.ops.pallas_ragged import ragged_paged_attention
    return ragged_paged_attention(q, pool, None, ss, nt, kvl, tab, v_dim=512)


@pytest.mark.parametrize("name,t,hq,kv,width,psz,n_pages,s,nj,hb,tb", [
    # A.X-K1's and Xing's latent launches: 64 / 32 query heads over ONE
    # row of 640 columns, the value its first 512, a block of 8 tiles:
    # the statistic tiled twice along the scores, four times along the
    # accumulator (the cells' rows, tables and blocks; the pool, which
    # stays in HBM and shapes nothing of the kernel, cut to 65 pages)
    ("axk1_latent", 288, 64, 1, 640, 256, 65, 33, 128, 1, 8),
    ("xing_latent", 384, 32, 1, 640, 256, 65, 129, 18, 1, 8),
    # Ouro's: a block of 16 heads over pages of 64, where the statistic
    # meets the scores through a lane slice; decode rows on 16 rows
    ("ouro_pages_of_64", 272, 16, 16, 128, 64, 65, 17, 64, 16, 1),
])
def test_the_lane_replicated_softmax_state_compiles(chip, name, t, hq, kv,
                                                     width, psz, n_pages, s,
                                                     nj, hb, tb):
    """The kernel's running maximum and sum as [rows, 128] scratch (PR
    55), in each form they meet the data, at the blocks the cells' shapes
    give: what Mosaic refuses of it is caught here, off the chip."""
    from paddle_tpu.ops.pallas_ragged import (ragged_head_block,
                                              ragged_narrow_rows,
                                              ragged_tile_block,
                                              ragged_tile_tokens)
    rep, latent = hq // kv, kv == 1
    rows = ragged_tile_tokens(t, rep, jnp.bfloat16) * rep
    assert rows == 128
    assert ragged_head_block(kv, rows, width, psz, 2, latent=latent) == hb
    assert ragged_tile_block(hb, -(-t * rep // rows), rows, width, psz, 2,
                             512 if latent else None) == tb
    assert ragged_narrow_rows(rep, rows, jnp.bfloat16, tb) \
        == (0 if latent else 16)
    seq = chip.shape((s,), I32)
    pool = chip.shape((kv, n_pages, psz, width))
    pools, fn = ((pool,), _ragged_one_row) if latent else \
        ((pool, pool), _ragged)
    assert chip.compiles(fn, chip.shape((t, hq, width)), *pools,
                         seq, seq, seq, chip.shape((s, nj), I32)), \
        chip.refusals.get(fn)


def test_ragged_paged_attention_compiles_at_the_serving_cells_shapes(chip):
    """`mistral-7b-v0.3-serve-d16` as BENCHMARK.json's serving cells run
    it: T = 32 slots + a 256-row chunk, 32 q / 8 kv heads x 128, page
    256, 187 pages, 33 sequences of 16 pages."""
    t, hq, kv, psz, n_pages, s, nj = 288, 32, 8, 256, 187, 33, 16
    seq = chip.shape((s,), I32)
    pool = chip.shape((kv, n_pages, psz, D))
    assert chip.compiles(_ragged, chip.shape((t, hq, D)), pool, pool,
                         seq, seq, seq, chip.shape((s, nj), I32)), \
        chip.refusals.get(_ragged)


def test_ragged_paged_attention_compiles_at_the_ouro_cell_shapes(chip):
    """`ouro-2.6b-serve-whole` as its cell runs it: T = 16 slots + a
    256-row chunk, 16 query heads over 16 KV heads x 128 (tiles of 128
    tokens), pages of 64, one pass's 80 pages of a layer's pool of 320,
    17 sequences of 64 pages. A page visit serves all 16 heads: the
    widest block of the cells (a strided DMA of 16 x 16 KB)."""
    from paddle_tpu.ops.pallas_ragged import (ragged_head_block,
                                              ragged_tile_tokens)
    t, hq, psz, n_pages, s, nj = 272, 16, 64, 320, 17, 64
    assert ragged_tile_tokens(t, 1, jnp.bfloat16) == 128
    assert ragged_head_block(hq, 128, D, psz, 2) == 16
    seq = chip.shape((s,), I32)
    pool = chip.shape((hq, n_pages, psz, D))
    assert chip.compiles(_ragged, chip.shape((t, hq, D)), pool, pool,
                         seq, seq, seq, chip.shape((s, nj), I32)), \
        chip.refusals.get(_ragged)


def _ragged_blocks(q, kp, vp, ss, nt, kvl, tab):
    from paddle_tpu.ops.pallas_ragged import ragged_paged_attention
    return ragged_paged_attention(q, kp, vp, ss, nt, kvl, tab, block=4)


@pytest.mark.parametrize("t", [896, 640])
def test_ragged_paged_attention_compiles_at_the_sdar_cell_shapes(chip, t):
    """`sdar-30b-a3b-serve-pp8-d6` as its cell runs it: 128 slots of a
    BLOCK of four rows, 32 riding commits of four rows (PR 61) (+ a
    256-row chunk), the block-causal rule, 32 query heads over 4 KV
    heads x 128 (tiles of 16 tokens), pages of 256, a pool of 1,281,
    161 sequences of 22 pages; and the per-head RMSNorm of q as the step
    calls it."""
    from paddle_tpu.ops.fused import fused_rms_norm
    hq, kv, psz, n_pages, s, nj = 32, 4, 256, 1281, 161, 22
    seq = chip.shape((s,), I32)
    pool = chip.shape((kv, n_pages, psz, D))
    assert chip.compiles(_ragged_blocks, chip.shape((t, hq, D)), pool, pool,
                         seq, seq, seq, chip.shape((s, nj), I32)), \
        chip.refusals.get(_ragged_blocks)
    assert chip.compiles(fused_rms_norm, chip.shape((t, hq, D)),
                         chip.shape((D,))), chip.refusals.get(fused_rms_norm)


def _ragged_windowed(q, kp, vp, ss, nt, kvl, tab):
    from paddle_tpu.ops.pallas_ragged import ragged_paged_attention
    return ragged_paged_attention(q, kp, vp, ss, nt, kvl, tab, window=512)


@pytest.mark.parametrize("hq,fn,n_pages", [
    (48, _ragged, 1280), (72, _ragged_windowed, 160)],
    ids=["full_rep6", "window512_rep9"])
def test_ragged_paged_attention_compiles_at_the_laguna_cell_shapes(
        chip, hq, fn, n_pages):
    """`laguna-s-2.1-serve-ep8-d8` as its cell runs it: T = 32 slots + a
    256-row chunk; full layers 48 q heads (6 a KV head: tiles of 16
    tokens, 96 rows) over the 1,280-page pool, sliding layers 72 (9 a
    KV head: tiles of 16 tokens, 144 rows) with window 512 over the
    160-page pool; 8 KV heads x 128, page 256, 33 sequences of 128
    pages.  The eligibility gate has to say what the compiler says."""
    from paddle_tpu.ops.pallas_ragged import (ragged_kernel_eligible,
                                              ragged_tile_tokens)
    t, kv, psz, s, nj = 288, 8, 256, 33, 128
    assert ragged_kernel_eligible(hq, kv, D, psz)
    assert ragged_tile_tokens(t, hq // kv, jnp.bfloat16) == 16
    seq = chip.shape((s,), I32)
    pool = chip.shape((kv, n_pages, psz, D))
    assert chip.compiles(fn, chip.shape((t, hq, D)), pool, pool,
                         seq, seq, seq, chip.shape((s, nj), I32)), \
        chip.refusals.get(fn)


def _ragged_latent(q, pool, rows, ss, nt, kvl, tab, pg, off):
    from paddle_tpu.ops.fused import fused_append_rows
    from paddle_tpu.ops.pallas_ragged import ragged_paged_attention
    pool = fused_append_rows(pool, rows, _run_table(ss, nt, pg, off, pool))
    return ragged_paged_attention(q, pool, None, ss, nt, kvl, tab,
                                  v_dim=512), pool


def test_latent_append_and_attention_compile_at_the_axk1_cell_shapes(chip):
    """`a.x-k1-serve-ep16-d6` as its cell runs it: T = 32 slots + a
    256-row chunk, 64 query heads over ONE cache row of 576 values
    stored in 640 columns (tiles of 2 tokens, 128 rows), K the row and
    V its first 512 columns, page 256, 2,049 pages, 33 sequences of 128
    pages.  The unpadded 576 is what the gate refuses."""
    from paddle_tpu.ops.pallas_ragged import (ragged_kernel_eligible,
                                              ragged_tile_tokens)
    from paddle_tpu.serving.engine import _latent_row_width
    t, hq, psz, n_pages, s, nj = 288, 64, 256, 2049, 33, 128
    width = _latent_row_width(512, 64)
    assert width == 640 and ragged_kernel_eligible(hq, 1, width, psz)
    assert not ragged_kernel_eligible(hq, 1, 576, psz)
    assert ragged_tile_tokens(t, hq, jnp.bfloat16) == 2
    seq, row = chip.shape((s,), I32), chip.shape((t,), I32)
    assert chip.compiles(
        _ragged_latent, chip.shape((t, hq, width)),
        chip.shape((1, n_pages, psz, width)), chip.shape((t, 1, width)),
        seq, seq, seq, chip.shape((s, nj), I32), row, row), \
        chip.refusals.get(_ragged_latent)


def _eva_layer_kernels(q, k, v, c, s, kp, vp, phi, mu, ss, nt, kvl, sr, tab,
                       pg, off, pool_pg, pool_off):
    """One EvaByte layer's kernels in the engine's order: rope + append
    into the window's pages, the pooling of the chunks that closed, the
    ONE append of their pooled K and V rows (48 slots: 32 decode rows'
    and the 16 a chunk can close, 34 runs at most), ONE softmax over
    pooled and exact rows."""
    from paddle_tpu.ops.fused import (append_slot_run_table, append_tile,
                                      fused_append_rows, fused_chunk_pool)
    from paddle_tpu.ops.pallas_ragged import ragged_paged_attention
    q, kp, vp = _rope_append(q, k, v, c, s, kp, vp, ss, nt, pg, off)
    kt, vt = fused_chunk_pool(kp, vp, phi, mu, pool_pg[0], pool_off[0],
                              chunk=16, scale=D ** -0.5)
    tile = append_tile(kp.dtype, kp.shape[2])
    slots = ss.shape[0] - 1
    runs = append_slot_run_table(
        pool_pg[1], pool_off[1], tile=tile,
        max_runs=slots + -(-(pool_pg.shape[1] - slots) // tile) + 1)
    kp, vp = fused_append_rows((kp, vp), (kt, vt), runs, scope="eva_pool")
    return ragged_paged_attention(q, kp, vp, ss, nt, kvl, tab,
                                  summary_rows=sr), kp, vp


def test_chunk_summary_kernels_compile_at_the_evabyte_cell_shapes(chip):
    """`evabyte-6.5b-serve-pp4-d8` as its cell runs it: T = 32 slots + a
    256-row chunk, 32 query heads over 32 KV heads x 128 (tiles of 128
    tokens), page 256, ONE pool of 272 pages, 33 sequences whose table
    is 8 pooled + 8 window pages, 48 pooling slots of 16 rows."""
    from paddle_tpu.ops.pallas_ragged import (ragged_kernel_eligible,
                                              ragged_tile_tokens)
    t, hq, psz, n_pages, s, nj, p = 288, 32, 256, 272, 33, 16, 48
    assert ragged_kernel_eligible(hq, hq, D, psz)
    assert ragged_tile_tokens(t, 1, jnp.bfloat16) == 128
    seq, row = chip.shape((s,), I32), chip.shape((t,), I32)
    tok = chip.shape((t, hq, D))
    pool = chip.shape((hq, n_pages, psz, D))
    trig = chip.shape((t, D // 2), F32)
    vec = chip.shape((hq, D))
    slots = chip.shape((2, p), I32)
    assert chip.compiles(
        _eva_layer_kernels, tok, tok, tok, trig, trig, pool, pool, vec, vec,
        seq, seq, seq, seq, chip.shape((s, nj), I32), row, row, slots,
        slots), chip.refusals.get(_eva_layer_kernels)


def _ssm_layer_kernels(pool, tab, xdt, dec, bh, ch, xc, dac, bc, cc):
    from paddle_tpu.ops.pallas_ssm import (ssm_chunk_scan, ssm_state_put,
                                           ssm_state_update)
    b = tab.shape[0] - 3
    y, pool = ssm_state_update(pool, tab[:b], tab[b:b + 1], xdt, dec, bh, ch)
    yc, s1 = ssm_chunk_scan(xc, dac, bc, cc, pool[tab[b + 1]], chunk=128)
    return y, yc, ssm_state_put(pool, tab[b + 1:], s1)


def _chunk_scan_holds_no_loop(text, heads, groups):
    """The chunk's scan in a compiled text is not the `lax.scan` of
    einsums it was (PR 57: inside the step program that loop's stacked
    output was half its time): no `while`, and none of that form's
    float32 [128, 128, heads] / [128, 128, groups] intermediates, whose
    minor dimension of 2 to 32 took 128 lanes."""
    assert " while(" not in text
    assert not re.search(r"f32\[128,128,(%d|%d)\]" % (heads, groups), text)
    return True


def test_state_space_kernels_compile_at_the_nemotron_cell_shapes(chip):
    """`nemotron-3-super-serve-ep4-d11` as its cell runs it: a state pool
    of 128 slots + the spare x [64, 128, 128] float32 (heads minor), the
    decode rows' update in place, a 256-row chunk's scan in two scan
    chunks of 128 (128 heads x 64 in 8 groups, state 128: the heads
    batch-major, the slot turned around it, no loop) and its state's
    write in place."""
    ns, p, n, h, g, c = 129, 64, 128, 128, 8, 256
    assert chip.compiles(
        _ssm_layer_kernels, chip.shape((ns, p, n, h), F32),
        chip.shape((ns + 2,), I32), chip.shape((ns, p, h), F32),
        chip.shape((ns, 1, h), F32), chip.shape((ns, n, h)),
        chip.shape((ns, n, h)), chip.shape((c, h, p), F32),
        chip.shape((c, h), F32), chip.shape((c, g, n)),
        chip.shape((c, g, n))), chip.refusals.get(_ssm_layer_kernels)
    assert _chunk_scan_holds_no_loop(chip.texts[_ssm_layer_kernels], h, g)


def _ssm1_layer_kernels(pool, tab, dt, x, a, bm, cm, dtc, xc, bc, cc):
    from paddle_tpu.ops.pallas_ssm import (ssm1_chunk_scan,
                                           ssm1_state_update, ssm_state_put)
    b = tab.shape[0] - 3
    y, pool = ssm1_state_update(pool, tab[:b], tab[b:b + 1], dt, x, a, bm, cm)
    yc, s1 = ssm1_chunk_scan(dtc, xc, a, bc, cc, pool[tab[b + 1]])
    return y, yc, ssm_state_put(pool, tab[b + 1:], s1)


def test_mamba1_kernels_compile_at_the_phi4flash_cell_shapes(chip):
    """`phi-4-mini-flash-serve-whole` as its cell runs it: a state pool of
    32 slots + the spare x [1, 16, 5120] float32 (channels along the
    lanes), the decode rows' update in place, a 256-row chunk's
    selective scan in four row blocks a channel block and its state's
    write in place; and the ragged kernel tiles the pair layout (40
    query heads over 10 KV pairs of 128, pages of 256)."""
    from paddle_tpu.ops.pallas_ragged import ragged_kernel_eligible
    ns, n, c, rows = 33, 16, 5120, 256
    assert chip.compiles(
        _ssm1_layer_kernels, chip.shape((ns, 1, n, c), F32),
        chip.shape((ns + 2,), I32), chip.shape((ns, c), F32),
        chip.shape((ns, c), F32), chip.shape((n, c), F32),
        chip.shape((ns, n), F32), chip.shape((ns, n), F32),
        chip.shape((rows, c), F32), chip.shape((rows, c), F32),
        chip.shape((rows, n), F32), chip.shape((rows, n), F32)), \
        chip.refusals.get(_ssm1_layer_kernels)
    assert ragged_kernel_eligible(40, 10, 128, 256)


def _ssm_state_minor_kernels(pool, tab, xdt, dec, bm, cm, xc, dac, bc, cc):
    from paddle_tpu.ops.pallas_ssm import (STATE_MINOR, ssm_chunk_scan,
                                           ssm_state_put, ssm_state_update)
    b = tab.shape[0] - 3
    y, pool = ssm_state_update(pool, tab[:b], tab[b:b + 1], xdt, dec, bm, cm,
                               layout=STATE_MINOR)
    yc, s1 = ssm_chunk_scan(xc, dac, bc, cc, pool[tab[b + 1]], chunk=128,
                            layout=STATE_MINOR)
    return y, yc, ssm_state_put(pool, tab[b + 1:], s1)


def test_state_space_kernels_compile_at_the_falcon_cell_shapes(chip):
    """`falcon-h1-34b-serve-pp8-d9` as its cell runs it: a state pool of
    64 slots + the spare x [32, 128, 256] float32 (STATE minor: 32 heads
    would fill a quarter of the lanes), the decode rows' update in place
    with a group's B / C rows not expanded, a 256-row chunk's scan in two
    scan chunks of 128 (32 heads x 128 in 2 groups, state 256: the heads
    batch-major over the slot as stored, no loop) and its state's write
    in place in blocks of 8 heads."""
    from paddle_tpu.ops.pallas_ssm import STATE_MINOR, state_layout
    ns, p, n, h, g, c = 65, 128, 256, 32, 2, 256
    assert state_layout(h, n) == STATE_MINOR
    assert chip.compiles(
        _ssm_state_minor_kernels, chip.shape((ns, h, p, n), F32),
        chip.shape((ns + 2,), I32), chip.shape((ns, p, h), F32),
        chip.shape((ns, 1, h), F32), chip.shape((ns, g, n)),
        chip.shape((ns, g, n)), chip.shape((c, h, p), F32),
        chip.shape((c, h), F32), chip.shape((c, g, n)),
        chip.shape((c, g, n))), chip.refusals.get(_ssm_state_minor_kernels)
    assert _chunk_scan_holds_no_loop(
        chip.texts[_ssm_state_minor_kernels], h, g)


def _kda_layer_kernels(pool, tab, q, k, v, g, beta, qc, kc, vc, gc, bc):
    from paddle_tpu.ops.pallas_kda import kda_chunk_scan, kda_state_update
    from paddle_tpu.ops.pallas_ssm import ssm_state_put
    b = tab.shape[0] - 3
    o, pool = kda_state_update(pool, tab[:b], tab[b:b + 1], q, k, v, g, beta)
    oc, s1 = kda_chunk_scan(qc, kc, vc, gc, bc, pool[tab[b + 1]], chunk=64)
    return o, oc, ssm_state_put(pool, tab[b + 1:], s1)


def test_delta_rule_kernels_compile_at_the_ling_cell_shapes(chip):
    """`ling-3.0-flash-serve-ep8-d7` as its cell runs it: a state pool of
    384 slots + the spare x [32, 128, 128] float32 (a head's [key, value]
    tile, values along the lanes), the decode rows' update in place (the
    rows' [8, 128] operands turned to columns in the kernel), a 256-row
    chunk's scan in four sub-chunks of 64 (PR 59: the heads batch-major,
    the sub-chunks a Python loop — no `while` — and none of the
    elementwise Gram form's [64, 64, heads] float32 intermediates, whose
    32 heads took 128 lanes) and its state's write in place by the
    state-space pool's own kernel."""
    ns, h, d, c = 385, 32, 128, 256
    row, crow = chip.shape((ns, h, d), F32), chip.shape((c, h, d), F32)
    assert chip.compiles(
        _kda_layer_kernels, chip.shape((ns, h, d, d), F32),
        chip.shape((ns + 2,), I32), row, row, row, row,
        chip.shape((ns, h, 1), F32), crow, crow, crow, crow,
        chip.shape((c, h), F32)), chip.refusals.get(_kda_layer_kernels)
    text = chip.texts[_kda_layer_kernels]
    assert " while(" not in text
    assert not re.search(r"f32\[4,64,64,%d\]" % h, text)


def _mhc_sublayer_kernels(x, phi_t, ab, y):
    from paddle_tpu.ops.pallas_mhc import mhc_post, mhc_pre
    x_in, coef = mhc_pre(x, phi_t, ab, n=4)
    return mhc_post(x, y + x_in, coef, n=4)


def test_hyper_connection_kernels_compile_at_the_xing_cell_shapes(chip):
    """`xing4.0-29b-a4b-serve-ep4-d20` as its cell runs it: 384 flat
    rows (128 slots + a 256-row chunk) of a four-stream residual, [384,
    14336] bfloat16; `mhc_pre` holds 128 rows a step (the product with
    the turned `phi_t` [32, 14336], the sum of squares, the Sinkhorn
    iterations on [1, 128] vectors, two turns of a 128 x 128 register
    square), `mhc_post` 64 rows in and out in place."""
    t, n, c = 384, 4, 3584
    assert chip.compiles(
        _mhc_sublayer_kernels, chip.shape((t, n * c)),
        chip.shape((32, n * c)), chip.shape((32, 128), F32),
        chip.shape((t, c))), chip.refusals.get(_mhc_sublayer_kernels)


def _serve_norm_and_linears(x, nw, w8, s8, w4, s4):
    """What the engine's split chain adds around the kernels above:
    the rms norm and, on quantized deploys, the weight-only linears."""
    from paddle_tpu.ops.fused import fused_rms_norm
    from paddle_tpu.ops.quant import weight_only_linear
    h = fused_rms_norm(x, nw, 1e-6)
    return (weight_only_linear(h, w8, s8, algo="weight_only_int8"),
            weight_only_linear(h, w4, s4, algo="weight_only_int4"))


def test_norm_and_weight_only_linears_compile(chip):
    w8, s8 = _quant_w(chip, "int8", H, I)
    w4, s4 = _quant_w(chip, "int4", H, I)
    assert chip.compiles(_serve_norm_and_linears, chip.shape((T, H)),
                         chip.shape((H,)), w8, s8, w4, s4), \
        chip.refusals.get(_serve_norm_and_linears)


# ---------------------------------------------------------------------------
# the gates agree with the compiler
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", list(ALGOS))
def test_megafront_gate_matches_compiler(chip, algo):
    from paddle_tpu.ops.pallas_megafront import megafront_eligible
    wbytes = 1 if algo == "int8" else 2
    says = megafront_eligible(H, N, D, int4=algo == "int4",
                              dtype_bytes=wbytes)
    assert says == chip.compiles(_FRONT[algo], *_front_args(chip, algo))
    assert says     # the smoke's engine takes the fused front


@pytest.mark.parametrize("algo", list(ALGOS))
def test_megadecode_gate_matches_compiler(chip, algo):
    """At the smoke widths the resident FFN slabs do not fit VMEM in any
    layout: the gate says so, the compiler agrees, and the engine's back
    half is the split chain (re-tiling fused_ffn is a later perf_opt).
    At the 8-way-shard hidden the same kernels fit, and compile."""
    from paddle_tpu.ops.pallas_megadecode import megadecode_eligible
    gate = functools.partial(megadecode_eligible, int4=algo == "int4",
                             dtype_bytes=1 if algo == "int8" else 2,
                             tokens=T)
    assert not gate(H, I, HQ * D)
    assert not _back_compiles(chip, algo, H, I, HQ * D)
    assert gate(512, I, 512)
    assert _back_compiles(chip, algo, 512, I, 512)


# ---------------------------------------------------------------------------
# the step reads its head-split projections as they are stored (ISSUE
# 48): a q / k / v (latent: q_b / kv_b) output is split into heads for a
# kernel, the compiler reads such a weight as [heads, D, in], and a
# stored [in, heads * D] was transposed once a layer of every step.
# Stored [heads, D, in] (`generation._heads_w`) and contracted on the
# last axis (`_mm_heads`, `_kvb_heads`) no copy of a weight's shape is
# left.
# ---------------------------------------------------------------------------

#: the leaves `_heads_w` stores [heads, D, in]
HEAD_SPLIT = ("wq", "wk", "wv", "wqb", "wkvb")


def _wide_engine(family):
    """Two layers at a serving cell's ATTENTION widths (Mistral 32 / 8
    heads x 128 over 4096, EvaByte 32 / 32, Ouro 16 / 16 over 2048,
    A.X-K1's 64 latent heads over ranks 1536 / 512), its slots, chunk
    and page; FFN, vocabulary (and the latent family's hidden width)
    small. A toy weight is staged through fast memory whichever way it
    is stored, and that hides the copy this test is about."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    paddle.seed(0)
    small = dict(intermediate_size=512, vocab_size=512, num_hidden_layers=2,
                 max_position_embeddings=1024)
    eng = dict(max_slots=32, page_size=256, max_context=1024,
               prefill_chunk=256)
    if family == "eva":
        from paddle_tpu.models.evabyte import (EvaByteForCausalLM,
                                               evabyte_tiny_config)
        model = EvaByteForCausalLM(evabyte_tiny_config(**dict(
            small, hidden_size=4096, num_attention_heads=32,
            num_key_value_heads=32, chunk_size=16, window_size=512,
            num_pred_heads=2, vocab_size=320, rope_positions=1024)))
    elif family == "looped":
        from paddle_tpu.models.ouro import OuroForCausalLM, ouro_tiny_config
        model = OuroForCausalLM(ouro_tiny_config(**dict(
            small, hidden_size=2048, num_attention_heads=16,
            num_key_value_heads=16, head_dim=128, total_ut_steps=4,
            rope_positions=1024)))
        eng.update(max_slots=16, page_size=64)
    elif family == "latent":
        from paddle_tpu.models.axk1 import (AXK1ForCausalLM,
                                            axk1_tiny_config)
        model = AXK1ForCausalLM(axk1_tiny_config(**dict(
            small, hidden_size=1024, num_attention_heads=64,
            num_key_value_heads=64, q_lora_rank=1536, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            intermediate_size=256, moe_intermediate_size=64,
            experts_held=(4, 4), max_position_embeddings=4096,
            rope_positions=1024)))
    else:
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        model = LlamaForCausalLM(llama_tiny_config(**dict(
            small, hidden_size=4096, num_attention_heads=32,
            num_key_value_heads=8, head_dim=128)))
    model.eval()
    for _, prm in model.named_parameters():
        prm._data = prm._data.astype(jnp.bfloat16)
    return ServingEngine(model, **eng)


def _weight_copies(text, shapes):
    """Lines of a compiled program that `copy` an array of a weight's
    shape ``(heads, D, in)``: as stored, or 2-D in either orientation.
    Not the `copy-done` of an asynchronous one: that is the prefetch of
    a weight into fast memory as it is stored (ROADMAP S11 keeps it),
    not a transposition."""
    pat = re.compile("= (" + "|".join(
        re.escape("bf16[" + ",".join(map(str, s)) + "]")
        for h, d, k in shapes
        for s in ((h, d, k), (h * d, k), (k, h * d))) + r")\S* copy\(")
    return [ln.strip() for ln in text.splitlines() if pat.search(ln)]


@pytest.mark.parametrize("chunk_part", ["chunk", "nochunk"])
@pytest.mark.parametrize("family", ["llama", "eva", "looped", "latent"])
def test_unified_step_reads_head_split_weights_as_stored(chip, family,
                                                         chunk_part,
                                                         monkeypatch):
    """... at both of the step's row counts (ISSUE 53): 288 / 272 flat
    rows with the chunk's behind the decode rows, 32 / 16 without."""
    from paddle_tpu.serving import engine as engine_mod
    eng = _wide_engine(family)
    assert eng.ragged
    B = eng.max_slots
    C = eng.prefill_chunk if chunk_part == "chunk" else 0
    rows, seqs = chip.shape((B + C,), I32), chip.shape((B + 1,), I32)
    table = chip.shape((B + 1, eng.pages_per_seq), I32)
    lens, page, off = seqs, rows, rows
    if family == "eva":     # three operands are pairs (a pooling slot list)
        slots = chip.shape((2, B + C // eng.allocator.chunk), I32)
        lens, page, off = (seqs, seqs), (rows, slots), (rows, slots)

    def on_chip(tree):
        return jax.tree.map(lambda a: chip.shape(a.shape, a.dtype), tree)

    def args(w):
        return (on_chip(w), rows, on_chip(eng._pools), rows, seqs, lens,
                table, page, off)

    held = [(k, L[k].shape) for L in eng._w["layers"] for k in HEAD_SPLIT
            if k in L]
    shapes = {s for _, s in held}
    assert len(held) == {"latent": 2}.get(family, 3) * len(eng._w["layers"])
    text = jax.jit(eng._make_unified_body(C)).lower(
        *args(eng._w)).compile().as_text()
    copies = _weight_copies(text, shapes)
    assert not copies, copies[:3]
    if not C:
        return
    # the same body fed [in, out] weights through a plain `h @ w` (the
    # kv_b through the read a quantized pair takes): one copy a weight,
    # so the pattern still reads what the compiler prints (asked once,
    # at the full row count)
    monkeypatch.setattr(engine_mod, "_mm_heads",
                        lambda h, L, key: h @ L[key])
    monkeypatch.setattr(
        engine_mod, "_kvb_heads", lambda L, nh, dtype: L["wkvb"].reshape(
            L["wkvb"].shape[0], nh, -1).transpose(1, 2, 0))
    plain_w = dict(eng._w, layers=[
        {k: jax.ShapeDtypeStruct((v.shape[2], v.shape[0] * v.shape[1]),
                                 v.dtype)
         if k in HEAD_SPLIT else v for k, v in L.items()}
        for L in eng._w["layers"]])
    plain = jax.jit(eng._make_unified_body(C)).lower(
        *args(plain_w)).compile().as_text()
    assert len(_weight_copies(plain, shapes)) >= len(held)
