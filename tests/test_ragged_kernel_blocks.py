"""The ragged kernel's blocks: a visit that serves a block of query tiles
(`ragged_tile_block`) and one that computes a narrow window of a tile's
rows (`ragged_narrow_rows`), each against the one-tile, tile-wide visit
bit for bit. The layouts, the oracle and the head blocks' cases are
test_ragged_kernel.py's; a file of their own because under `--dist
loadfile` a file is one worker's unit of work, and the two together
(417 s beside five busy workers, ISSUE 52) ended the run alone."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_ragged
from paddle_tpu.ops.pallas_ragged import (_work_list, ragged_head_block,
                                          ragged_narrow_rows,
                                          ragged_narrow_updates,
                                          ragged_paged_attention,
                                          ragged_pages_visited,
                                          ragged_tile_block,
                                          ragged_tile_tokens)
from test_ragged_kernel import (_HEAD_BLOCKS, _LAYOUTS, _engine_layout,
                                _layout, ragged_attention_reference)


#: launches of pages that hold K and V in ONE row (latent attention: 16
#: query heads over the one row, so tiles of 8 tokens in float32 and
#: six of them in the 48 flat rows), whose page visits serve a BLOCK of
#: query tiles: the `_engine_layout` keys of each
_TILE_BLOCKS = {
    # 8 decode rows fill tile 0; the chunk owns tiles 1-5 whole
    "chunk_on_cell_boundaries": dict(
        kv_dec=[17, 33, 9, 60, 1, 25, 40, 8], chunk=40, kv_chunk=24 + 40),
    # the chunk starts inside tile 1 and ends inside tile 4: its first
    # and last cells serve some of their tiles only, at every block
    "chunk_starts_and_ends_mid_cell": dict(
        kv_dec=[5, 12, 30, 2, 44, 19], chunk=27, kv_chunk=64,
        chunk_row=11, T=48),
    # idle slots inside the cell the decode rows share with the chunk
    "an_empty_slot_inside_a_cell": dict(
        kv_dec=[7, 0, 19, 0, 0, 33, 0, 4, 0, 21], chunk=31, kv_chunk=50,
        T=48),
    "decode_rows_only": dict(
        kv_dec=[7, 19, 0, 64, 33, 2, 50, 0, 11, 3], chunk=0, kv_chunk=0,
        T=48),
}


def _latent_layout(name):
    """q, the one pool, the row tables of a `_TILE_BLOCKS` launch."""
    q, kp, _, *tables = _engine_layout(H=16, KV=1, D=128, **_TILE_BLOCKS[name])
    return q, kp, tables


def _forced_tile_block(monkeypatch, tb):
    monkeypatch.setattr(pallas_ragged, "ragged_tile_block",
                        lambda *a, **k: tb)
    # (the launch is traced once for equal shapes: trace it again)
    pallas_ragged._launch_jit.clear_cache()


class TestTileBlock:
    @pytest.mark.parametrize("name", list(_TILE_BLOCKS))
    def test_a_tile_block_is_one_tile_a_visit_bit_for_bit(
            self, name, monkeypatch):
        q, kp, tables = _latent_layout(name)
        ref = ragged_attention_reference(q, kp, None, *tables, v_dim=64)
        outs = {}
        for tb in (1, 2, 4, 8):
            _forced_tile_block(monkeypatch, tb)
            outs[tb] = np.asarray(ragged_paged_attention(
                q, kp, None, *tables, v_dim=64))
        pallas_ragged._launch_jit.clear_cache()
        np.testing.assert_allclose(outs[1], np.asarray(ref), atol=2e-5,
                                   rtol=2e-5)
        for tb in (2, 4, 8):
            np.testing.assert_array_equal(outs[tb], outs[1], str(tb))

    @pytest.mark.parametrize("hb,window", [(1, None), (2, None), (2, 13)])
    def test_a_tile_block_under_a_head_block(self, hb, window, monkeypatch):
        """What no launch takes from its shapes today (a block of tiles
        is for one KV head): the body serves hb x tb chains a visit,
        with two pools and under a window too."""
        q, kp, vp, *tables = _engine_layout(
            kv_dec=[17, 0, 9, 30, 5], chunk=39, kv_chunk=16 + 39, T=48,
            chunk_row=7, H=32, KV=2)
        monkeypatch.setattr(pallas_ragged, "ragged_head_block",
                            lambda *a, **k: hb)
        outs = {}
        for tb in (1, 4):
            _forced_tile_block(monkeypatch, tb)
            outs[tb] = np.asarray(ragged_paged_attention(
                q, kp, vp, *tables, window=window))
        pallas_ragged._launch_jit.clear_cache()
        ref = ragged_attention_reference(q, kp, vp, *tables, window=window)
        np.testing.assert_allclose(outs[1], np.asarray(ref), atol=2e-5,
                                   rtol=2e-5)
        np.testing.assert_array_equal(outs[4], outs[1])

    @pytest.mark.parametrize("name,KV,tiles,rows,D,psz,v_dim,want", [
        # the configurations' launches in bfloat16 (T = 288, Ouro 272,
        # Nemotron 384 rows): the one that serves ONE head a visit takes
        # a block of tiles, every block of heads exactly one tile
        ("axk1_latent", 1, 144, 128, 640, 256, 512, 8),
        ("mistral", 8, 9, 128, 128, 256, None, 1),
        ("laguna_full_rep6", 8, 18, 96, 128, 256, None, 1),
        ("laguna_window_rep9", 8, 18, 144, 128, 256, None, 1),
        ("evabyte", 32, 3, 128, 128, 256, None, 1),
        ("ouro", 16, 3, 128, 128, 64, None, 1),
        ("nemotron_rep16", 2, 48, 128, 128, 256, None, 1),
        # no more tiles than the launch has, in powers of two
        ("a_launch_of_five_tiles", 1, 5, 128, 640, 256, 512, 4),
        ("a_launch_of_one_tile", 1, 1, 128, 640, 256, 512, 1),
        # one KV head without the latent row (multi-query attention)
        ("one_kv_head_two_pools", 1, 36, 128, 128, 256, None, 8),
        # a cell that does not fit at 8 tiles: 4 (1,024-column rows)
        ("rows_too_wide_for_eight", 1, 144, 128, 1024, 64, 1024, 4),
    ])
    def test_tile_block_follows_the_shapes(self, name, KV, tiles, rows, D,
                                           psz, v_dim, want):
        hb = ragged_head_block(KV, rows, D, psz, 2, latent=v_dim is not None)
        tb = ragged_tile_block(hb, tiles, rows, D, psz, 2, v_dim)
        assert tb == want and (tb == 1 or hb == 1)
        assert tb <= min(tiles, pallas_ragged._TILE_BLOCK_MAX)
        assert hb * tb <= pallas_ragged._HEAD_BLOCK_MAX
        vmem = pallas_ragged._block_vmem(hb, rows, D, psz, 2, tb, v_dim)
        # the cell's VMEM by hand: q and out rows twice, f32 state (m
        # and l a 128-lane column each), one ring of rows that hold K
        # and V, else two
        block = hb * psz * D * 2
        out = v_dim or D
        assert vmem == (
            2 * hb * tb * rows * (D + out) * 2
            + hb * tb * rows * (out + 256) * 4
            + (1 if v_dim else 2) * pallas_ragged._page_buffers(block)
            * block)
        if tb > 1:
            assert vmem <= pallas_ragged._VMEM_BUDGET
        if hb == 1 and 2 * tb <= min(tiles, pallas_ragged._TILE_BLOCK_MAX):
            assert pallas_ragged._block_vmem(
                hb, rows, D, psz, 2, 2 * tb, v_dim) \
                > pallas_ragged._VMEM_BUDGET

    @pytest.mark.parametrize("tb", [1, 2, 4, 8])
    @pytest.mark.parametrize("name", list(_TILE_BLOCKS))
    def test_visit_count_follows_the_cells(self, name, tb):
        # the exported count at a block of tb tiles is the kernel's own
        # work list over cells of tb tiles; the (tile, page) updates it
        # computes are the count at ONE tile a cell whatever tb, so a
        # decode row's are its pages, not tb x them
        q, kp, (ss, nt, kvl, tab) = _latent_layout(name)
        T, psz, pps = q.shape[0], kp.shape[2], tab.shape[1]
        tq = ragged_tile_tokens(T, 16, q.dtype)
        assert tq == 8
        counted = dict(T=T, rep=16, dtype=q.dtype, page_size=psz,
                       pages_per_seq=pps)
        visited = ragged_pages_visited(ss, nt, kvl, tb=tb, **counted)
        cell_first, _, pair_first = _work_list(
            ss, nt, kvl, tq=tb * tq, n_tiles=-(-T // (tb * tq)),
            page_size=psz, pages_per_seq=pps)
        assert int(pair_first[cell_first[-1]]) == visited
        chains = ragged_pages_visited(ss, nt, kvl, **counted)
        live = int(np.sum(-(-np.asarray(kvl)[np.asarray(nt) > 0] // psz)))
        assert live <= visited <= chains
        # the decode rows' part of both: their live pages, once each
        dec = ragged_pages_visited(ss[:-1], nt[:-1], kvl[:-1], tb=tb,
                                   **counted)
        assert dec == live - -(-int(kvl[-1]) // psz) == ragged_pages_visited(
            ss[:-1], nt[:-1], kvl[:-1], **counted)
        if int(nt[-1]):
            # the chunk's pages cross once a CELL it has rows in: by hand
            first, last = int(ss[-1]), int(ss[-1]) + int(nt[-1]) - 1
            base = int(kvl[-1]) - int(nt[-1]) - first
            want = sum(
                (base + min(last, c + tb * tq - 1)) // psz + 1
                for c in range(0, T, tb * tq)
                if c <= last and c + tb * tq > first)
            assert visited - dec == want
            assert (visited < chains) == (tb > 1)

    def test_the_kernels_tile_test_is_the_work_lists_rule(self):
        """`_walk` on scalars (the kernel's test of which tiles of a
        cell a page meets) against `_tile_pages` on arrays (the count),
        windowed too."""
        _, _, (ss, nt, kvl, _) = _latent_layout(
            "chunk_starts_and_ends_mid_cell")
        for window in (None, 13):
            tiling = dict(tq=8, page_size=8, pages_per_seq=8, window=window)
            table = pallas_ragged._tile_pages(
                np, *(np.asarray(x) for x in (ss, nt, kvl)), n_tiles=6,
                **tiling)
            for t in range(6):
                for i in range(len(ss)):
                    got = pallas_ragged._walk(
                        jnp, jnp.int32(t * 8), ss[i], nt[i], kvl[i], **tiling)
                    want = table[t, i] if window is None else \
                        (table[0][t, i], table[1][t, i])
                    assert np.array_equal(np.asarray(got), np.asarray(want))


def _forced_narrow_rows(monkeypatch, rows):
    """Every page visit on `rows` rows of the tile (0: the tile's)."""
    monkeypatch.setattr(pallas_ragged, "ragged_narrow_rows",
                        lambda *a, **k: rows)
    # (the launch is traced once for equal shapes: trace it again)
    pallas_ragged._launch_jit.clear_cache()


def _narrow_by_hand(ss, nt, kvl, *, T, rep, dtype, psz, window=None):
    """An instrumented reference of the kernel's page walk: for every
    (tile, sequence) pair the pages that hold a key some row of the pair
    sees, counted row by row, and whether SOME window of
    `ragged_narrow_rows` rows on a packed row of the tile holds the
    pair's rows. Returns (updates in such pairs, all updates, pairs
    that fit, pairs)."""
    tq = ragged_tile_tokens(T, rep, dtype)
    rows, pack = tq * rep, 32 // jnp.dtype(dtype).itemsize
    W = ragged_narrow_rows(rep, rows, dtype)
    narrow = total = fit = pairs = 0
    for t0 in range(0, T, tq):
        for i in range(len(ss)):
            own = [r for r in range(t0, min(t0 + tq, T))
                   if int(ss[i]) <= r < int(ss[i]) + int(nt[i])]
            if not own:
                continue
            pos = [int(kvl[i]) - int(nt[i]) + r - int(ss[i]) for r in own]
            lo = [0 if window is None else max(p_ - window + 1, 0)
                  for p_ in pos]
            pages = len({k // psz for a, p_ in zip(lo, pos)
                         for k in range(a, p_ + 1)})
            if window is None:      # a full walk starts at page 0
                pages = max(pos) // psz + 1
            r0, r1 = (own[0] - t0) * rep, (own[-1] + 1 - t0) * rep
            fits = bool(W) and any(w <= r0 and r1 <= w + W
                                   for w in range(0, rows - W + 1, pack))
            pairs, fit = pairs + 1, fit + fits
            total, narrow = total + pages, narrow + fits * pages
    return narrow, total, fit, pairs


#: the launches whose sequences own a few rows of a tile: a `_LAYOUTS`
#: name -> (pairs whose rows fit the narrow window, pairs) by hand
_NARROW = {
    "engine": (8, 10),                      # 8 decode rows; the chunk x 2
    "chunk_inside_a_tile": (4, 5),
    "idle_between_live": (4, 6),            # idle slots are no pair
    "no_chunk_decode_only": (4, 4),
    "shared_physical_pages": (3, 5),
    "chunk_tail_of_two_tokens": (9, 10),    # the tail's tile too
    "rep1_decode_rows_and_a_chunk": (5, 6),
    # slots 0 and 2; the chunk has rows in both tiles
    "speculative_runs_that_fit_and_not": (2, 7),
    "rep6_runs_of_two_and_three": (3, 7),   # slots 0, 1 and 4
    "window_rep6_chunk_straddles": (6, 8),
    "window_rep9_dead_pages": (4, 7),       # a token straddles 8 rows
    "window_one_page": (4, 6),
}


def _pinned_launch(name):
    """The output of a `_PARENTS_OUTPUT` launch."""
    if name == "latent_rows":           # a block of 4 tiles, one pool
        q, kp, tables = _latent_layout("chunk_starts_and_ends_mid_cell")
        return ragged_paged_attention(q, kp, None, *tables, v_dim=64)
    if name == "two_pools_windowed":    # the same, two pools
        arrays, _ = _layout("one_kv_head_a_block_of_tiles")
        return ragged_paged_attention(*arrays, window=13)
    if name in _LAYOUTS:
        arrays, window = _layout(name)
        return ragged_paged_attention(*arrays, window=window)
    if name in _HEAD_BLOCKS:            # `test_under_a_head_block`'s
        spec = _HEAD_BLOCKS[name]
        *arrays, kvl, tab = _engine_layout(
            kv_dec=[17, 0 if spec.get("idle") else 9, 30], chunk=11,
            kv_chunk=16 + 11, H=spec["KV"] * spec["rep"], KV=spec["KV"],
            pps=4)
        if spec.get("sentinel"):
            live = -(-np.asarray(kvl) // arrays[1].shape[2])
            tab = jnp.where(np.arange(tab.shape[1])[None] < live[:, None],
                            tab, -1)
        summary = None if "summary" not in spec else \
            jnp.asarray(spec["summary"], jnp.int32)
        return ragged_paged_attention(*arrays, kvl, tab,
                                      window=spec.get("window"),
                                      summary_rows=summary)
    # the widths a TPU launch has, in bfloat16
    spec = dict(_LANE_WIDTHS[name])
    v_dim = spec.pop("v_dim", None)
    q, kp, vp, *tables = _engine_layout(**spec)
    q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    return ragged_paged_attention(q, kp, None if v_dim else vp, *tables,
                                  v_dim=v_dim)


#: launches at lane widths: `_engine_layout` keys (and `v_dim`)
_LANE_WIDTHS = {
    # Ouro's: one query head a KV head over pages of 64, a block of 4
    # heads, decode rows on the narrow window: the statistic meets the
    # scores through a lane slice and the accumulator as it is
    "pages_of_64_rep1": dict(kv_dec=[70, 0, 150, 33], chunk=140,
                             kv_chunk=30 + 140, H=4, KV=4, D=128, psz=64,
                             pps=3),
    # latent attention's: 16 query heads over one row of 384 columns
    # whose first 256 are the value, pages of 256, a block of tiles: the
    # statistic is tiled twice along the scores and the accumulator
    "latent_row_of_384": dict(kv_dec=[300, 17, 0, 511], chunk=30,
                              kv_chunk=250 + 30, H=16, KV=1, D=384,
                              v_dim=256, psz=256, pps=2),
}

#: launch -> sha256 of its output's bytes at the parent commit, for every
#: form the softmax's statistic takes: a block of tiles with one pool and
#: with two under a window, a block of KV heads (GQA, a window at rep 9,
#: chunk-summary rows), the narrow window, pages of 64, lane multiples
_PARENTS_OUTPUT = {
    "latent_rows":
        "56b2dc733fbb06a34c204186245ef5a3130d9541c6a6a970d82fa1d6a36fcdff",
    "two_pools_windowed":
        "ac16643d25cff9981aa756dc7e4ac57accf1ea59434819ae9f42125d75fdc98b",
    "kv8_rep4_idle_slot_sentinel":
        "678b5184188c9e13ff20917e8feaae2f16f6bb5e022d706509942910ca5d3aa5",
    "kv2_rep9_window":
        "5efbb6d331068b7060f1513f87e5def8f2d7c8b41f8bdaf5248fe8094e949563",
    "kv32_rep1_summary":
        "e6099abe7bf9b68597357ca739316cc0daa8c849cf933fece4e82d5ffa5d0467",
    "engine":
        "50f522c092bd75a205ea12d8ec06472270ef373435f512d159893a64509b4067",
    "speculative_runs_that_fit_and_not":
        "01502411ee84e7dafb55831fc714c3faceb588597e5c5f0efc4da38b7deb651c",
    "pages_of_64_rep1":
        "60052d1602a34f7f58bc4aace85f570e74d7783846a55a4c622b3400efccf7c9",
    "latent_row_of_384":
        "c7eff5daa3656be30ed38062a5c9441ebd89dfbbd307ee685a535263e3efefb8",
}


class TestNarrowWindow:
    """A page visit of a sequence that owns a few rows of its tile runs
    on the window of `ragged_narrow_rows` rows that holds them: the same
    numbers as on the tile's rows, bit for bit (at the widths the kernel
    takes on a TPU: at D = 32 XLA's CPU code sums a page's keys in
    another order for an 8-row operand of the values matmul than for a
    64-row one)."""

    @pytest.mark.parametrize("name", list(_NARROW))
    def test_a_narrow_visit_is_the_tiles_bit_for_bit(self, name,
                                                     monkeypatch):
        arrays, window = _layout(name)
        q, kp, vp, ss, nt, kvl, tab = arrays
        out = np.asarray(ragged_paged_attention(*arrays, window=window))
        ref = ragged_attention_reference(
            q, jnp.nan_to_num(kp), jnp.nan_to_num(vp), ss, nt, kvl, tab,
            window=window)
        np.testing.assert_allclose(out, np.asarray(ref), atol=2e-5,
                                   rtol=2e-5)
        _forced_narrow_rows(monkeypatch, 0)
        full = np.asarray(ragged_paged_attention(*arrays, window=window))
        pallas_ragged._launch_jit.clear_cache()
        np.testing.assert_array_equal(out, full)

    @pytest.mark.parametrize("name", list(_NARROW))
    def test_the_count_is_the_kernels_rule(self, name):
        """`ragged_narrow_updates` (the engine's `attn_narrow_updates`)
        against a reference that walks rows and pages one by one."""
        (q, kp, _, ss, nt, kvl, tab), window = _layout(name)
        rep = q.shape[1] // kp.shape[0]
        launch = dict(T=q.shape[0], rep=rep, dtype=q.dtype)
        narrow, total, fit, pairs = _narrow_by_hand(
            ss, nt, kvl, psz=kp.shape[2], window=window, **launch)
        assert (fit, pairs) == _NARROW[name]
        tiling = dict(page_size=kp.shape[2], pages_per_seq=tab.shape[1],
                      window=window, **launch)
        assert ragged_pages_visited(ss, nt, kvl, **tiling) == total
        assert ragged_narrow_updates(ss, nt, kvl, **tiling) == narrow
        assert 0 < narrow < total or name == "no_chunk_decode_only"
        # a cell that is a block of tiles has no narrow visit
        assert ragged_narrow_updates(ss, nt, kvl, tb=2, **tiling) == 0

    @pytest.mark.parametrize("name", list(_HEAD_BLOCKS))
    def test_under_a_head_block(self, name, monkeypatch):
        """The launches of `_HEAD_BLOCKS` (a window at rep 9, an idle
        slot and sentinel tables, rep 6, chunk-summary rows at rep 1)
        with every visit on the tile's rows: the same output."""
        spec = dict(_HEAD_BLOCKS[name])
        KV, rep = spec["KV"], spec["rep"]
        q, kp, vp, ss, nt, kvl, tab = _engine_layout(
            kv_dec=[17, 0 if spec.get("idle") else 9, 30], chunk=11,
            kv_chunk=16 + 11, H=KV * rep, KV=KV, pps=4)
        if spec.get("sentinel"):
            live = -(-np.asarray(kvl) // kp.shape[2])
            tab = jnp.where(np.arange(tab.shape[1])[None] < live[:, None],
                            tab, -1)
        kw = dict(window=spec.get("window"))
        if "summary" in spec:
            kw["summary_rows"] = jnp.asarray(spec["summary"], jnp.int32)
        tq = ragged_tile_tokens(q.shape[0], rep, q.dtype)
        assert 0 < ragged_narrow_rows(rep, tq * rep, q.dtype) < tq * rep
        out = np.asarray(
            ragged_paged_attention(q, kp, vp, ss, nt, kvl, tab, **kw))
        _forced_narrow_rows(monkeypatch, 0)
        full = np.asarray(
            ragged_paged_attention(q, kp, vp, ss, nt, kvl, tab, **kw))
        pallas_ragged._launch_jit.clear_cache()
        np.testing.assert_array_equal(out, full)

    def test_bfloat16_rows_pack_by_sixteen(self, monkeypatch):
        """The dtype the cells run: a decode row's window is 16 rows."""
        q, kp, vp, *tables = _engine_layout(
            kv_dec=[17, 33, 0, 60, 1, 25], chunk=40, kv_chunk=24 + 40)
        q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
        assert ragged_narrow_rows(4, 128, q.dtype) == 16
        out = ragged_paged_attention(q, kp, vp, *tables)
        _forced_narrow_rows(monkeypatch, 0)
        full = ragged_paged_attention(q, kp, vp, *tables)
        pallas_ragged._launch_jit.clear_cache()
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(full, np.float32))

    @pytest.mark.parametrize("name,rep,rows,dtype,tb,want", [
        # the configurations' launches in bfloat16 (16 rows a packed
        # row): a token of 1, 4 or 16 query heads never straddles one
        ("mistral", 4, 128, jnp.bfloat16, 1, 16),
        ("ouro", 1, 128, jnp.bfloat16, 1, 16),
        ("evabyte", 1, 128, jnp.bfloat16, 1, 16),
        ("nemotron_rep16", 16, 128, jnp.bfloat16, 1, 16),
        # 6 and 9 do: two packed rows
        ("laguna_full_rep6", 6, 96, jnp.bfloat16, 1, 32),
        ("laguna_window_rep9", 9, 144, jnp.bfloat16, 1, 32),
        # a block of tiles has no narrow visit; its one-tile launch has
        ("axk1_latent", 64, 128, jnp.bfloat16, 8, 0),
        ("a_latent_launch_of_one_tile", 64, 128, jnp.bfloat16, 1, 64),
        # float32 packs 8 rows
        ("float32_rep4", 4, 128, jnp.float32, 1, 8),
        ("float32_rep9", 9, 72, jnp.float32, 1, 16),
        # a tile no taller than the window is computed whole
        ("a_tile_of_one_packed_row", 1, 16, jnp.bfloat16, 1, 0),
        ("rep9_in_a_tile_of_two", 9, 32, jnp.bfloat16, 1, 0),
    ])
    def test_narrow_rows_follow_the_shapes(self, name, rep, rows, dtype, tb,
                                           want):
        W = ragged_narrow_rows(rep, rows, dtype, tb)
        assert W == want
        if not W:
            return
        pack = 32 // jnp.dtype(dtype).itemsize
        assert W % pack == 0 and W < rows
        # every token's rows lie in the window on the packed row at or
        # before its first, and in no narrower one
        starts = range(0, rows, rep)
        assert all(r % pack + rep <= W for r in starts)
        assert any(r % pack + rep > W - pack for r in starts)

    @pytest.mark.parametrize("name", list(_PARENTS_OUTPUT))
    def test_the_output_is_the_parents_bit_for_bit(self, name):
        """sha256 of the launch's output bytes at this PR's parent
        (5980fd3: m, l and alpha as [rows, 1] columns), on the CPU under
        the suite's matmul precision: held lane-replicated the statistic
        is the same float32 operations on the same values, in every form
        it meets the scores and the accumulator (a lane slice, itself,
        tiled). The bytes are the CPU backend's (its matmul and `exp`):
        if a jax / XLA or host change fails all nine at once, tell it
        from a kernel regression by re-recording at the parent's kernel
        — `git archive 5980fd3 paddle_tpu/ops/pallas_ragged.py | tar -x
        -C <dir>`, load that file as `paddle_tpu.ops._pallas_ragged_text`
        (`tools/bench_util.load_text`), point this module's
        `ragged_paged_attention` at its function and print
        `_pinned_launch(name)`'s sha256: hashes that move with the
        parent's kernel too are the backend's, not this kernel's."""
        out = np.asarray(_pinned_launch(name), np.float32)
        assert np.isfinite(out).all()
        assert hashlib.sha256(out.tobytes()).hexdigest() \
            == _PARENTS_OUTPUT[name]
