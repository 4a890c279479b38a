"""Ragged mixed prefill+decode paged-attention kernel.

Reference capability: Ragged Paged Attention (arXiv 2604.15464) — ONE
`pallas_call` serves a mixed batch of prefill chunks and decode tokens
over the paged KV cache, replacing the engine's alternating
`_prefill_chunk` / `_decode` dispatches.

Layout: the step's new tokens ride in a FLAT buffer q [T, H, D] with
per-sequence row tables as scalar prefetch:

  - seq_start [S]:  first flat row of sequence i's new tokens;
  - num_tokens [S]: how many new tokens sequence i contributes this step
    (1 for a decode slot, the chunk length for a prefill row, 0 for an
    inactive slot — its rows emit zeros);
  - kv_lengths [S]: sequence i's KV length INCLUDING its new tokens
    (append-then-attend: the new K/V rows are already in the pages);
  - page_tables [S, pages_per_seq]: physical pages, sentinel entries
    clamped into the pool.

Causality is per sequence over its new tokens: local token t (0-based)
attends KV positions 0 .. kv_lengths[i] - num_tokens[i] + t. A decode
row (num_tokens=1) therefore sees its whole context; a prefill chunk is
causal within the chunk and sees everything before it (shared-prefix
pages included). With a `block` (static; generation by diffusion over
blocks) the rule is BLOCK-causal: the token at position p attends
positions 0 .. the END of p's block of `block` positions,
``min(kv - 1, (p // block + 1) * block - 1)`` — the rows of a block see
each other and everything before them; ``block`` 1 is the causal rule.
The walk of the pages does not move: a block never crosses a page
(``page_size % block == 0``, which the caller holds), so the page of a
tile's last row is still the last page any of its rows sees.

Blocking: the flat buffer is cut into static TILES of TQ tokens
(`ragged_tile_tokens`: TQ*rep query rows of one KV head, a multiple of
the dtype's sublane packing) and a grid CELL owns a block of tb of them
(`ragged_tile_block`, read from the shapes: one, but where a page visit
serves ONE KV head — latent attention's 64 query heads over one row are
tiles of 2 tokens, and 8 of them a cell; T is padded up to whole
cells). The work is a list of (cell, sequence) PAIRS — a sequence with
rows in the cell — each with the number of pages the cell walks for it:
the pages up to the causal limit of the sequence's LAST row inside the
cell, never more than its live pages. `_tile_pages` computes that table
for the kernel (in XLA, once a step: the per-layer calls are identical
and merge) and for the engine's `pages_visited` counter
(`ragged_pages_visited`), so the two cannot drift. Grid (KV / hb,
cells): a cell owns the tb [TQ*rep, D] query tiles of a BLOCK of hb KV
heads (`ragged_head_block`, read from the shapes) and their output
tiles; the K/V pools stay in HBM and the cell walks its pairs' pages,
each page visit ONE K and ONE V DMA of the page as all hb heads hold it
(a strided copy out of the [KV, pages, psz, D] pools) into a ring of
`_page_buffers` slots. What a visit costs whatever the page holds — the
work-list reads, the slot arithmetic, the read-ahead cursor, the DMA
starts and waits — is paid once a visit; the hb heads' and tb tiles'
softmax updates are hb x tb independent chains in the one loop body,
each under its tile's mask, taken in three passes over the heads (all
scores, all softmaxes, all values). A visit serves only the tiles whose OWN walk
(`_walk`, the work list's rule on the tile's tokens) holds the page: a
decode row's pages meet the one tile it lives in, and where every tile
of the cell is served — a chunk's cells but its first and last — the
tiles' query rows go through the two matmuls together, the page being
the MXU's stationary operand once for all of them. The DMAs run ahead of
the compute along the block's FLAT walk, across pair and cell boundaries
(the read-ahead cursor is carried from cell to cell in SMEM). No grid
step, DMA or branch exists for a dead (sequence, page) entry, and a page
meets only the TQ*rep rows of a tile that holds rows of its sequence.
Rows of OTHER sequences in that tile are masked (s = _MASKED -> p = 0,
and m, l, acc untouched), so the per-row online-softmax state lets
sequences share a tile — and a sequence that owns only a FEW of the
tile's rows (a decode row's rep query heads, a short speculative run, a
chunk's last tokens) has its pages computed on the window of
`ragged_narrow_rows` rows that holds them (16 rows of bfloat16 for a
decode row, on a packed row of the tile), not on the tile's 128: the
same update on a narrower extent of q, m, l and acc, chosen once a pair
from the row tables (`_narrow_window`, the rule `ragged_narrow_updates`
counts with). GQA-native, f32 scores / softmax state /
accumulator, interpret mode off-TPU.

The softmax state m and l (and a visit's m_new and alpha) is held
LANE-REPLICATED: [rows, 128] with a row's value in every lane, meeting
the [rows, page] scores and the [rows, D] accumulator lane for lane
(`lane_stat.lanes`: tiled along a multiple of 128 columns, a lane
slice under them). As [rows, 1] columns — one live lane a register,
spread along the lanes again for every chain of every visit — a (tile,
page) update of the latent launch took 0.52-0.56 us where this form
takes 0.42-0.47, and the output is that form's bit for bit (PERF.md
section 6, PR 55).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lane_stat import lanes as _lanes
from .pallas_paged import paged_kernel_eligible

__all__ = ["ragged_paged_attention", "ragged_attention_reference",
           "ragged_kernel_eligible", "ragged_tile_tokens",
           "ragged_head_block", "ragged_tile_block",
           "ragged_narrow_rows", "ragged_visit_counts",
           "ragged_pages_visited", "ragged_narrow_updates"]

_NEG = -1e30
_MASKED = -3e38
#: query rows (tokens x rep) of one tile: the MXU's height on the chips
#: this runs on. PERF.md (PR 25) has the sweep on a v5e: fewer rows make a
#: decode page cheaper, more rows refetch a prefill chunk's pages less.
_TILE_ROWS = 128
#: K+V bytes the page DMAs keep in flight ahead of the compute
_BYTES_IN_FLIGHT = 256 * 1024
#: VMEM a grid cell's blocks may take: what `ragged_head_block` sums,
#: under the compiler's default scoped limit (16 MiB on the chips this
#: runs on; no `vmem_limit_bytes`) with room for the scores and
#: probabilities of the head being computed
_VMEM_BUDGET = 10 * 1024 * 1024
#: KV heads a page visit serves at most: PERF.md (PR 42) has the sweep
#: on a v5e
_HEAD_BLOCK_MAX = 16
#: query tiles a page visit serves at most, where it serves one KV
#: head: PERF.md (PR 44) has the sweep on a v5e
_TILE_BLOCK_MAX = 8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def ragged_kernel_eligible(H: int, KV: int, D: int,
                           page_size: int) -> bool:
    """Same tiling constraints as the decode kernel: the [rows, D] query
    group wants MXU-friendly D (64, or a multiple of 128); any page_size
    >= 8 works (masks handle partial pages and ragged chunk tails). D is
    the row AS STORED: latent attention's 512 + 64 values are refused
    at 576 and taken at the 640 columns the engine stores them in
    (`serving.engine._latent_row_width`), so every family the engine
    serves takes the unified step on a TPU at published widths; what is
    refused today is toy presets."""
    return paged_kernel_eligible(H, KV, D, page_size)


def _sublane_pack(dtype) -> int:
    """Rows of `dtype` one packed row of a vector register holds (8 rows
    of 32 bits): what a slice of rows has to start on."""
    return 32 // jnp.dtype(dtype).itemsize


def ragged_tile_tokens(T: int, rep: int, dtype) -> int:
    """TQ, the tokens of one query tile: about _TILE_ROWS / rep, in
    units that keep TQ*rep a multiple of the dtype's sublane packing
    (8 rows of 32 bits), and no more than T rounded up to a unit."""
    pack = _sublane_pack(dtype)
    unit = pack // math.gcd(rep, pack)
    tq = max(unit, _TILE_ROWS // rep // unit * unit)
    return min(tq, -(-T // unit) * unit)


def _page_buffers(block_bytes: int) -> int:
    """K (and V) buffers of a grid cell, each a page as the cell's head
    block holds it: all but one are in flight while one is computed,
    _BYTES_IN_FLIGHT of K+V between them (one, where a block alone is
    more)."""
    return 1 + min(7, max(1, _BYTES_IN_FLIGHT // (2 * block_bytes)))


def _block_vmem(hb: int, rows: int, D: int, psz: int, itemsize: int,
                tb: int = 1, v_dim: Optional[int] = None) -> int:
    """VMEM bytes of a grid cell that serves `hb` KV heads and `tb`
    query tiles: the query and output tiles (double-buffered by the
    pipeline), the f32 accumulator, m and l (lane-replicated [rows,
    128]: what a [rows, 1] column took too, in whole 128-lane tiles;
    PERF.md section 6, PR 55), the K and the V ring. Pages that hold K and
    V in one row (`v_dim`) have ONE ring, and an output and an
    accumulator of `v_dim` columns."""
    q, o = hb * tb * rows * D, hb * tb * rows * (v_dim or D)
    state = o * 4 + 2 * hb * tb * rows * 128 * 4
    block = hb * psz * D * itemsize
    rings = 1 if v_dim else 2
    return (2 * (q + o) * itemsize + state
            + rings * _page_buffers(block) * block)


def ragged_head_block(KV: int, rows: int, D: int, psz: int, itemsize: int,
                      latent: bool = False) -> int:
    """hb, the KV heads one page visit serves: the largest divisor of
    KV, no more than _HEAD_BLOCK_MAX, whose cell (`_block_vmem`) fits
    _VMEM_BUDGET; 1 where none does, and for a `latent` cache (one row
    serves every query head: there is one KV head to visit)."""
    if latent:
        return 1
    return max([1] + [hb for hb in range(2, min(KV, _HEAD_BLOCK_MAX) + 1)
                      if KV % hb == 0 and _block_vmem(
                          hb, rows, D, psz, itemsize) <= _VMEM_BUDGET])


def ragged_tile_block(hb: int, tiles: int, rows: int, D: int, psz: int,
                      itemsize: int, v_dim: Optional[int] = None) -> int:
    """tb, the query tiles one page visit serves: 1 where the visit
    already serves a block of KV heads (`hb` > 1); where it serves one
    head (latent attention's one row for every query head), the largest
    power of two, no more than _TILE_BLOCK_MAX, than the chains a visit
    carries at most (_HEAD_BLOCK_MAX) and than the launch's `tiles`,
    whose cell (`_block_vmem`) fits _VMEM_BUDGET."""
    if hb > 1:
        return 1
    tb = 1
    while 2 * tb <= min(_TILE_BLOCK_MAX, _HEAD_BLOCK_MAX, tiles) \
            and _block_vmem(hb, rows, D, psz, itemsize, 2 * tb,
                            v_dim) <= _VMEM_BUDGET:
        tb *= 2
    return tb


def ragged_narrow_rows(rep: int, rows: int, dtype, tb: int = 1) -> int:
    """W, the rows of a tile a page visit computes for a sequence that
    owns only a few of them (a decode row's `rep`, a short speculative
    run, a chunk's last tokens): the least multiple of the dtype's
    sublane packing that holds a token's `rep` rows wherever in the tile
    they start (past a packed row by a multiple of gcd(rep, packing)) —
    16 rows of bfloat16 at rep 1, 4 and 16, 32 at rep 6 and 9. 0, no
    narrow visit, where that is the whole tile of `rows` and where a
    cell is a block of `tb` tiles (`ragged_tile_block`: its decode row
    fills half a tile)."""
    pack = _sublane_pack(dtype)
    width = -(-(pack - math.gcd(rep, pack) + rep) // pack) * pack
    return width if tb == 1 and width < rows else 0


def _walk(xp, lo, seq_start, num_tokens, kv_lengths, *, tq, page_size,
          pages_per_seq, window=None, lift=lambda x: x):
    """The K/V pages the `tq` flat rows from `lo` walk for a sequence —
    pages 0 .. the causal limit of the sequence's last row among them;
    0 where it has no row there. With a `window` the walk starts at the
    page of the oldest key the FIRST such row still sees (position -
    window + 1), and the result is the pair (pages walked, first page).
    The ONE rule of the work list (`_tile_pages`: arrays, `lift` lays a
    sequence's values against the tiles') and of the kernel's own test
    of which of a cell's tiles a page meets (scalars)."""
    first = xp.maximum(lift(seq_start), lo)
    last = xp.minimum(lift(seq_start + num_tokens), lo + tq) - 1
    base = lift(kv_lengths - num_tokens - seq_start)
    pages = xp.clip((base + last) // page_size + 1, 0, pages_per_seq)
    if window is None:
        return xp.where(last >= first, pages, 0).astype(xp.int32)
    start = xp.clip((base + first - (window - 1)) // page_size, 0,
                    pages_per_seq)
    live = last >= first
    return (xp.where(live, xp.maximum(pages - start, 0), 0).astype(xp.int32),
            xp.where(live, start, 0).astype(xp.int32))


def _tile_pages(xp, seq_start, num_tokens, kv_lengths, *, tq, n_tiles,
                **walk):
    """[n_tiles, S] int32: `_walk` of tile t (`tq` tokens: a grid cell's,
    where it owns a block of tiles) for sequence i. `xp` is numpy (the
    counter) or jax.numpy (the kernel's work list)."""
    lo = (xp.arange(n_tiles, dtype=xp.int32) * tq)[:, None]
    return _walk(xp, lo, seq_start, num_tokens, kv_lengths, tq=tq,
                 lift=lambda x: x[None, :], **walk)


def _narrow_window(xp, lo, seq_start, num_tokens, *, tq, rep, pack, width,
                   lift=lambda x: x):
    """Whether the query rows a sequence owns among the `tq` tokens from
    `lo` (a tile of `tq * rep` rows) lie inside ONE window of `width`
    rows that starts on a packed row of the tile, and that window's
    first row (the packed row at or before the sequence's first, no
    further than `width` rows before the tile's end). The ONE rule of
    the kernel's choice, once a pair, of the rows its page visits
    compute (scalars) and of the `attn_narrow_updates` count (arrays,
    `lift` as in `_walk`; only pairs that walk pages are counted)."""
    first = xp.maximum(lift(seq_start), lo)
    end = xp.minimum(lift(seq_start + num_tokens), lo + tq)
    w0 = xp.minimum((first - lo) * rep // pack * pack, tq * rep - width)
    return (end - lo) * rep <= w0 + width, w0


def ragged_visit_counts(seq_start, num_tokens, kv_lengths, *, T: int,
                        rep: int, dtype, page_size: int,
                        pages_per_seq: int, window: Optional[int] = None,
                        tb: int = 1):
    """`ragged_pages_visited` and `ragged_narrow_updates` of one launch,
    from ONE table of the pages each (cell, sequence) pair walks
    (host-side numpy)."""
    tile = ragged_tile_tokens(T, rep, dtype)
    tq = tb * tile
    ss, nt, kvl = (np.asarray(x, np.int32)
                   for x in (seq_start, num_tokens, kv_lengths))
    n_tiles = -(-T // tq)
    pages = _tile_pages(np, ss, nt, kvl, tq=tq, n_tiles=n_tiles,
                        page_size=page_size, pages_per_seq=pages_per_seq,
                        window=window)
    if window is not None:
        pages = pages[0]
    width = ragged_narrow_rows(rep, tile * rep, dtype, tb)
    if not width:
        return int(pages.sum()), 0
    fits, _ = _narrow_window(
        np, (np.arange(n_tiles, dtype=np.int32) * tq)[:, None], ss, nt,
        tq=tq, rep=rep, pack=_sublane_pack(dtype), width=width,
        lift=lambda x: x[None, :])
    return int(pages.sum()), int(np.where(fits, pages, 0).sum())


def ragged_pages_visited(seq_start, num_tokens, kv_lengths, **launch) -> int:
    """K/V page fetches PER KV HEAD that `ragged_paged_attention` makes
    for this launch (`ragged_visit_counts`' keywords; the engine's
    `pages_visited`): the sum over grid cells of `tb` tiles
    (`ragged_tile_block`) of the pages each walks. At `tb` 1 whatever
    the launch's: the (tile, page) softmax updates it computes, since a
    visit serves only the tiles whose own walk holds the page."""
    return ragged_visit_counts(seq_start, num_tokens, kv_lengths,
                               **launch)[0]


def ragged_narrow_updates(seq_start, num_tokens, kv_lengths, **launch) -> int:
    """Of the (tile, page) softmax updates a KV head that this launch
    computes, those that run on `ragged_narrow_rows` rows and not on the
    tile's (the engine's `attn_narrow_updates`): the pages of every
    (tile, sequence) pair whose rows fit the window, by the kernel's own
    rule. 0 where the launch has no narrow visit."""
    return ragged_visit_counts(seq_start, num_tokens, kv_lengths,
                               **launch)[1]


def _work_list(seq_start, num_tokens, kv_lengths, **tiling):
    """The kernel's scalar-prefetched work: (tile, sequence) pairs with
    pages to walk, compacted tile-major. Returns tile_first [n_tiles+1]
    (pairs of tile t are tile_first[t] .. tile_first[t+1]), pair_seq
    [n_tiles + S] and pair_first [n_tiles + S + 1], the running page
    count (pair p walks pair_first[p+1] - pair_first[p] pages; the count
    also picks the DMA slot). Disjoint row ranges give at most
    n_tiles + S - 1 pairs; more — overlapping ranges — are dropped.
    With a `window` in the tiling pair_seq is twice as long: its second
    half is the first page each pair walks."""
    pages = _tile_pages(jnp, seq_start, num_tokens, kv_lengths, **tiling)
    start = None
    if tiling.get("window") is not None:
        pages, start = pages
    n_tiles, S = pages.shape
    cap = n_tiles + S
    flat = pages.reshape(-1)
    idx = jnp.nonzero(flat > 0, size=cap, fill_value=0)[0]
    zero = jnp.zeros(1, jnp.int32)
    per_tile = jnp.sum(pages > 0, axis=1, dtype=jnp.int32)
    tile_first = jnp.minimum(
        jnp.concatenate([zero, jnp.cumsum(per_tile)]), cap)
    pair_first = jnp.concatenate([zero, jnp.cumsum(flat[idx])])
    pair_seq = idx % S
    if start is not None:
        pair_seq = jnp.concatenate([pair_seq, start.reshape(-1)[idx]])
    return (tile_first.astype(jnp.int32), pair_seq.astype(jnp.int32),
            pair_first.astype(jnp.int32))


def _in_hbm(x):
    """Pin a kernel operand to HBM (no op is emitted). Left free, XLA's
    memory-space assignment prefetches the small row tables into its
    alternate memory before 15 of a 16-layer step's calls: copies that
    gain nothing, and the kernel's operands lose their names in the
    trace (`benchmarks/layer_metrics/ragged_attn_roofline.py` finds the
    kernel by its `kv_lengths` operand)."""
    return pltpu.with_memory_space_constraint(x, pltpu.HBM)


def _tile_map(h, t, ss, nt, kvl, tab, tile_first, pair_seq, pair_first):
    return (h, t, 0)


def _latent_kernel(ss_ref, nt_ref, kvl_ref, tab_ref,    # scalar prefetch
                   first_ref, pseq_ref, pfirst_ref,
                   q_ref, k_hbm, o_ref,
                   kbuf, acc_ref, m_ref, l_ref, ahead_ref, sem, **static):
    """The launch whose pages hold K and V in ONE row (latent
    attention): no V pool, no V buffers, one DMA a page."""
    _ragged_kernel(ss_ref, nt_ref, kvl_ref, tab_ref, first_ref, pseq_ref,
                   pfirst_ref, q_ref, k_hbm, None, o_ref, kbuf, None,
                   acc_ref, m_ref, l_ref, ahead_ref, sem, **static)


def _ragged_kernel(ss_ref, nt_ref, kvl_ref, tab_ref,    # scalar prefetch
                   first_ref, pseq_ref, pfirst_ref,
                   q_ref, k_hbm, v_hbm, o_ref,
                   kbuf, vbuf, acc_ref, m_ref, l_ref, ahead_ref, sem,
                   *, page_size, rep, tq, total_pages, scale, window,
                   summary=False, tb=1, narrow=0, block=None):
    h = pl.program_id(0)
    t = pl.program_id(1)
    n_pairs = first_ref[pl.num_programs(1)]
    depth = kbuf.shape[0]
    # the cell's block of KV heads: an axis of the ring and, with the
    # cell's block of `tb` query tiles (`rows` query rows each, one
    # after the other in the q and output blocks), of the f32 state:
    # [hb x tb chains, rows, ..]. The launch of one-row pages (one KV
    # head) goes without it where a cell is one tile
    hb = q_ref.shape[0]
    blocked = kbuf.ndim == 4
    heads = pl.ds(h * hb, hb) if blocked else h
    rows = q_ref.shape[1] // tb
    # a windowed launch's pair table is twice as long: pair pi's first
    # page sits `pairs` entries after its sequence
    pairs = pseq_ref.shape[0] // 2

    def part(g):
        """The index of head g's part of a ring slot."""
        return (g, ...) if blocked else (...,)

    def chain(g, b, at=...):
        """The index of the f32 state of head g's tile b (of its rows
        `at`)."""
        return (g * tb + b, at) if acc_ref.ndim == 3 else (at,)

    def run(g, tiles):
        """The index of a run of tiles in head g's q or output block."""
        return g if len(tiles) == tb else \
            (g, pl.ds(tiles[0] * rows, len(tiles) * rows))

    def page_dma(pi, j):
        # page j of pair pi, as the block's heads hold it, lands in the
        # slot its running count picks; sentinel / -1 table entries
        # never emit an out-of-range DMA
        slot = jax.lax.rem(pfirst_ref[pi] + j, depth)
        if window is not None:
            j = j + pseq_ref[pairs + pi]
        phys = jnp.clip(tab_ref[pseq_ref[pi], j], 0, total_pages - 1)
        k_dma = pltpu.make_async_copy(k_hbm.at[heads, phys], kbuf.at[slot],
                                      sem.at[0, slot])
        if v_hbm is None:       # V is the page's first columns
            return slot, (k_dma,)
        return slot, (
            k_dma,
            pltpu.make_async_copy(v_hbm.at[heads, phys], vbuf.at[slot],
                                  sem.at[1, slot]))

    def fetch_ahead(pi, j):
        """Start the DMAs of page (pi, j) of the block's flat walk if
        there is one; return the page after it."""
        @pl.when(pi < n_pairs)
        def _start():
            for dma in page_dma(pi, j)[1]:
                dma.start()

        # (past the last pair the clamped read is never used)
        c = jnp.minimum(pi, jnp.maximum(n_pairs - 1, 0))
        wrap = j + 1 >= pfirst_ref[c + 1] - pfirst_ref[c]
        return jnp.where(wrap, pi + 1, pi), jnp.where(wrap, 0, j + 1)

    # the walk's read-ahead cursor lives across the block's grid cells:
    # depth - 1 pages fly ahead of the one being computed, whichever
    # pair or cell they belong to
    @pl.when(t == 0)
    def _warmup():
        ahead = (jnp.int32(0), jnp.int32(0))
        for _ in range(depth - 1):
            ahead = fetch_ahead(*ahead)
        ahead_ref[0], ahead_ref[1] = ahead

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, _NEG)
    l_ref[:] = jnp.zeros_like(l_ref)
    if tb == 1:
        q = [q_ref[g] for g in range(hb)]                # [TQ*rep, D] each

    def first_token(b):
        """The flat token tile b of the cell starts at."""
        return t * tq if tb == 1 else (t * tb + b) * tq

    # flat token of each query row of tile b ([TQ*rep, 1]: the rep query
    # heads of one token are adjacent rows of the KV head's group)
    tok = [first_token(b) + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) // rep for b in range(tb)]
    in_page = jax.lax.broadcasted_iota(
        jnp.int32, (rows, page_size), 1)

    def pair(pi, ahead):
        i = pseq_ref[pi]
        first_row, nt = ss_ref[i], nt_ref[i]

        def limit_of(tk):
            """Local token t of this sequence attends positions <= its
            limit (with a `block`: up to the end of its block); the rows
            of other sequences attend nothing."""
            mine = (tk >= first_row) & (tk < first_row + nt)
            pos = kvl_ref[i] - nt + (tk - first_row)
            if block is not None:
                pos = jnp.minimum(kvl_ref[i] - 1,
                                  (pos // block + 1) * block - 1)
            return jnp.where(mine, pos, -1)

        limit = [limit_of(tk) for tk in tok]
        page0 = 0 if window is None else pseq_ref[pairs + pi]
        if summary:
            # the sequence's KV starts with `lo` pooled rows; the rest
            # of their last page, up to `hi`, is a hole nobody sees
            lo = kvl_ref[kvl_ref.shape[0] // 2 + i]
            hi = (lo + page_size - 1) // page_size * page_size

        def visible(j, limit, in_page):
            """The keys of page j each of a tile's rows sees, by the
            rows' limits: the same for every head, so one mask a tile
            and visit."""
            rel = limit - (page0 + j) * page_size
            seen = in_page <= rel
            if window is not None:
                # each row's own lower bound: keys older than its window
                seen &= in_page > rel - window
            if summary:
                at = (page0 + j) * page_size
                seen &= (in_page < lo - at) | (in_page >= hi - at)
            return seen

        def update(j, slot, tiles, own=None):
            """Page j meets the run `tiles` of the cell's tiles: for each
            head of the block ONE scores matmul and ONE values matmul
            over the run's query rows (the page is the MXU's stationary
            operand once for all of them), and between the two one
            softmax update a tile under the tile's one mask — hb x
            len(tiles) independent chains, in THREE PASSES over the
            heads: every head's scores, every head's softmax, every
            head's values. (One head after the other, each chain's
            matmuls wait for its own softmax and the next head's for
            them: on the chip a visit of 16 rows took 1.5 x longer.)
            With `own` — the window of the one tile's rows that holds
            the sequence's, those rows' limits, the key index over them
            — the same on those rows alone: rows are independent in both
            matmuls and in the state, and the others' m, l and acc are
            what the whole tile's update leaves them."""
            rows_at, limits, keys = own or (..., limit, in_page)
            seen = {}
            v_run, s_run = [], []
            for g in range(hb):
                k = kbuf[(slot, *part(g))]               # [psz, D]
                v_run.append(k[:, :acc_ref.shape[-1]] if vbuf is None
                             else vbuf[(slot, *part(g))])
                s_run.append(jax.lax.dot_general(
                    q_ref[g, rows_at] if own
                    else q[g] if tb == 1 else q_ref[run(g, tiles)],
                    k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale)
            chains, p_run = [[] for _ in range(hb)], [[] for _ in range(hb)]
            for g in range(hb):
                for n, b in enumerate(tiles):
                    at = chain(g, b, rows_at)
                    s = s_run[g] if len(tiles) == 1 \
                        else s_run[g][n * rows:(n + 1) * rows]
                    if b not in seen:   # after the first head's scores,
                        # where one head a cell
                        seen[b] = visible(j, limits[b], keys)
                    # _MASKED is so far below any m (>= _NEG) that exp
                    # gives an exact 0: a row with nothing to attend here
                    # keeps m, l, acc
                    s = jnp.where(seen[b], s, _MASKED)
                    # m, l, alpha: [rows, 128], a row's value in every
                    # lane (`_lanes`)
                    m_prev = m_ref[at]
                    m_new = jnp.maximum(m_prev,
                                        jnp.max(s, -1, keepdims=True))
                    p = jnp.exp(s - _lanes(m_new, s.shape[1]))
                    alpha = jnp.exp(m_prev - m_new)
                    l_ref[at] = l_ref[at] * alpha \
                        + jnp.sum(p, -1, keepdims=True)
                    chains[g].append((at, alpha, m_new))
                    p_run[g].append(p.astype(v_run[g].dtype))
            for g in range(hb):
                for n, (at, alpha, m_new) in enumerate(chains[g]):
                    scaled = acc_ref[at] * _lanes(alpha, acc_ref.shape[-1])
                    if n == 0:  # (behind the first rescale: the order of
                        # the one-tile launch's text, which is pinned)
                        pv = jax.lax.dot_general(
                            p_run[g][0] if len(tiles) == 1
                            else jnp.concatenate(p_run[g]),
                            v_run[g], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                    acc_ref[at] = scaled + (
                        pv if len(tiles) == 1
                        else pv[n * rows:(n + 1) * rows])
                    m_ref[at] = m_new

        if tb > 1:
            # each tile's OWN walk for the sequence, by the work list's
            # rule: a page meets the tiles whose walk holds it (a decode
            # row's pages ONE tile, not the cell's tb), and where that
            # is all of them they go through the matmuls as one run
            walks = [_walk(jnp, first_token(b), first_row, nt, kvl_ref[i],
                           tq=tq, page_size=page_size,
                           pages_per_seq=tab_ref.shape[1], window=window)
                     for b in range(tb)]
            # [first page, end) of each tile, and of all of them
            walks = [(0, n) for n in walks] if window is None else \
                [(start, start + n) for n, start in walks]
            every = [functools.reduce(f, pages) for f, pages in zip(
                (jnp.maximum, jnp.minimum), zip(*walks))]

        def meets(at, walk):
            """Whether page `at` of the sequence lies in [first, end)."""
            inside = at < walk[1]
            return inside if window is None else inside & (at >= walk[0])

        def page(j, ahead, own=None):
            ahead = fetch_ahead(*ahead)
            slot, dmas = page_dma(pi, j)
            for dma in dmas:
                dma.wait()
            if tb == 1:
                update(j, slot, (0,), own)
                return ahead
            at = page0 + j
            whole = meets(at, every)

            pl.when(whole)(
                functools.partial(update, j, slot, tuple(range(tb))))

            @pl.when(jnp.logical_not(whole))
            def _some_tiles():
                for b in range(tb):
                    pl.when(meets(at, walks[b]))(
                        functools.partial(update, j, slot, (b,)))

            return ahead

        n_pages = pfirst_ref[pi + 1] - pfirst_ref[pi]
        if not narrow:
            return jax.lax.fori_loop(0, n_pages, page, ahead)
        # a sequence that owns a few rows of the tile (a decode row, a
        # short speculative run, a chunk's last tokens): its pages meet
        # the window of `narrow` rows that holds them, not the tile.
        # Chosen once a pair, by the rule the host counts with
        pack = _sublane_pack(q_ref.dtype)
        fits, w0 = _narrow_window(jnp, t * tq, first_row, nt, tq=tq,
                                  rep=rep, pack=pack, width=narrow)

        def own_rows(ahead):
            own_tok = t * tq + (w0 + jax.lax.broadcasted_iota(
                jnp.int32, (narrow, 1), 0)) // rep
            own = (pl.ds(pl.multiple_of(w0, pack), narrow),
                   [limit_of(own_tok)],
                   jax.lax.broadcasted_iota(
                       jnp.int32, (narrow, page_size), 1))
            return jax.lax.fori_loop(
                0, n_pages, functools.partial(page, own=own), ahead)

        return jax.lax.cond(
            fits, own_rows,
            lambda ahead: jax.lax.fori_loop(0, n_pages, page, ahead), ahead)

    ahead = jax.lax.fori_loop(first_ref[t], first_ref[t + 1], pair,
                              (ahead_ref[0], ahead_ref[1]))
    ahead_ref[0], ahead_ref[1] = ahead
    # rows of no sequence kept l == 0 and acc == 0: they emit zeros
    for g in range(hb):
        for b in range(tb):
            l = l_ref[chain(g, b)]
            o_ref[run(g, (b,))] = (acc_ref[chain(g, b)] / _lanes(
                jnp.where(l == 0.0, 1.0, l), acc_ref.shape[-1])).astype(
                    o_ref.dtype)


def ragged_paged_attention(q, k_pages, v_pages, seq_start, num_tokens,
                           kv_lengths, page_tables,
                           scale: Optional[float] = None,
                           window: Optional[int] = None,
                           v_dim: Optional[int] = None,
                           summary_rows=None, scope: Optional[str] = None,
                           block: Optional[int] = None,
                           _launch: bool = False):
    """q [T, H, D] flat new-token buffer; k/v_pages [KV, total_pages,
    page_size, D]; seq_start/num_tokens/kv_lengths [S] int32;
    page_tables [S, pages_per_seq] int32. Sequences own DISJOINT row
    ranges [seq_start[i], seq_start[i]+num_tokens[i]); seq_start is
    non-decreasing in every caller (the work list is built tile-major
    from the ranges themselves and does not lean on the order). Rows
    covered by no sequence return zeros. Returns [T, H, D].

    `v_pages=None` with `v_dim` (static, a multiple of 128 on a TPU) is
    latent attention's cache: a row of `k_pages` is the key whole and
    the value in its first `v_dim` columns, so a page is fetched ONCE
    and serves both matmuls. Returns [T, H, v_dim].

    `window` (static) is a sliding window: the query at position i sees
    keys j with i - window < j <= i. Pages wholly below a tile's oldest
    visible key are neither fetched nor read from the page table, so
    their table entries may be dead. `window=None` is full causal
    attention and compiles the program it did before the argument
    existed.

    `summary_rows` [S] int32 is chunk-summary attention's cache: a
    sequence's KV, as its page table lists it, STARTS with that many
    pooled rows, every one visible to every query of the sequence, and
    the exact rows follow from the next page boundary on (causal as
    ever, `kv_lengths` counting from the table's first row); the rows
    between, the unfilled tail of the last pooled page, are seen by
    nobody. It rides behind `kv_lengths` in that operand, so the launch
    has the operands it had; `None` compiles the program it did before
    the argument existed.

    `block` (static) makes the rule block-causal: the query at position
    i sees keys j <= the end of i's block of `block` positions (and <
    its sequence's KV length). The caller holds ``page_size % block ==
    0`` and gives every sequence whole blocks, so the work list is the
    causal one. Not with a `window`. `block=None` compiles the program
    it did before the argument existed.

    `scope` (static) names the launch in the compiled program (the
    caller's own `jax.named_scope`, said again: the launch is traced
    and lowered ONCE for equal shapes inside a jitted copy of this
    function, which a step's layers then share, and the instruction
    takes the innermost name).

    VMEM (`_block_vmem`): for each of the cell's `ragged_head_block`
    KV heads `ragged_tile_block` [TQ*rep, D] query tiles and output
    tiles (double-buffered by the pipeline), that much f32 state, and
    `_page_buffers` K and V pages."""
    T, H, D = q.shape
    KV, total, psz, _ = k_pages.shape
    rep = H // KV
    S, nj = page_tables.shape
    if scale is None:
        scale = D ** -0.5
    interpret = _interpret()
    if (v_pages is None) != (v_dim is not None):
        raise ValueError("give v_pages, or v_dim for rows that hold K "
                         "and V together; not both, not neither")
    latent = v_pages is None
    if block is not None and (window is not None or psz % block):
        raise ValueError(
            f"block {block}: a block-causal launch takes no window and "
            f"pages of whole blocks (page_size {psz})")
    if not _launch:
        # one trace and one lowering of the launch (the heads' and the
        # tiles' chains are unrolled in it) for all the layers of a step
        # that make it, and the trace the operands' memory-space pins
        # below need
        return _launch_jit(
            q, k_pages, v_pages, seq_start, num_tokens, kv_lengths,
            page_tables, scale=float(scale), window=window, v_dim=v_dim,
            summary_rows=summary_rows, scope=scope,
            **({} if block is None else {"block": block}))
    tq = ragged_tile_tokens(T, rep, q.dtype)
    rows = tq * rep
    itemsize = k_pages.dtype.itemsize
    hb = ragged_head_block(KV, rows, D, psz, itemsize, latent=latent)
    tb = ragged_tile_block(hb, -(-T // tq), rows, D, psz, itemsize, v_dim)
    # a grid cell: `tb` tiles, `cell` tokens, `tb * rows` query rows a head
    cell = tb * tq
    n_cells = -(-T // cell)
    Tp = n_cells * cell
    depth = _page_buffers(hb * psz * D * itemsize)
    ss = seq_start.astype(jnp.int32)
    nt = num_tokens.astype(jnp.int32)
    kvl = kv_lengths.astype(jnp.int32)
    tiling = {} if window is None else dict(window=window)
    work = _work_list(ss, nt, kvl, tq=cell, n_tiles=n_cells, page_size=psz,
                      pages_per_seq=nj, **tiling)
    # [T, H, D] -> [KV, Tp*rep, D]: a KV head's flat query group (rep
    # rows per token, token-major), cut into cells of `tb` tiles of TQ
    # tokens
    qg = (jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0)))
          .reshape(Tp, KV, rep, D).transpose(1, 0, 2, 3)
          .reshape(KV, Tp * rep, D))
    static = dict(page_size=psz, rep=rep, tq=tq, total_pages=total,
                  scale=float(scale), window=window, tb=tb,
                  narrow=ragged_narrow_rows(rep, rows, q.dtype, tb))
    if block is not None:
        static["block"] = block
    if summary_rows is not None:
        kvl = jnp.concatenate([kvl, summary_rows.astype(jnp.int32)])
        static["summary"] = True
    tables = (ss, nt, kvl, page_tables.astype(jnp.int32), *work)
    if latent:
        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            out = _latent_call(qg, k_pages, tables, v_dim, rows, depth,
                               n_cells, interpret, **static)
        return _ungroup(out, T, H)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,      # row tables, page tables, work list
        grid=(KV // hb, n_cells),
        in_specs=[
            pl.BlockSpec((hb, tb * rows, D), _tile_map),
            pl.BlockSpec(memory_space=pltpu.HBM),    # the pools stay put
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec((hb, tb * rows, D), _tile_map),
        scratch_shapes=[pltpu.VMEM((depth, hb, psz, D), k_pages.dtype),
                        pltpu.VMEM((depth, hb, psz, D), v_pages.dtype),
                        pltpu.VMEM((hb * tb, rows, D), jnp.float32),
                        pltpu.VMEM((hb * tb, rows, 128), jnp.float32),
                        pltpu.VMEM((hb * tb, rows, 128), jnp.float32),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.SemaphoreType.DMA((2, depth))],
    )
    # the tile axis is sequential: a head block's page DMAs run ahead
    # from one tile into the next
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        out = pl.pallas_call(
            functools.partial(_ragged_kernel, **static),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((KV, Tp * rep, D), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(*(x if interpret else _in_hbm(x) for x in (
            *tables, qg, k_pages, v_pages)))
    return _ungroup(out, T, H)


@functools.partial(jax.jit,
                   static_argnames=("scale", "window", "v_dim", "scope",
                                    "block"))
def _launch_jit(*operands, **options):
    return ragged_paged_attention(*operands, _launch=True, **options)


def _ungroup(out, T: int, H: int):
    """[KV, Tp*rep, Dv] (a KV head's flat query group) -> [T, H, Dv]."""
    KV, flat, Dv = out.shape
    Tp = flat * KV // H
    return (out.reshape(KV, Tp, H // KV, Dv).transpose(1, 0, 2, 3)
            .reshape(Tp, H, Dv)[:T])


def _latent_call(qg, pages, tables, v_dim, rows, depth, n_cells,
                 interpret, *, tb, **static):
    """`ragged_paged_attention`'s launch for pages that hold K and V in
    one row: the same grid, work list and tile maps at a head block of
    one, so without that axis; one pool operand, one ring of page
    buffers, a [tb * rows, v_dim] accumulator and output."""
    KV, flat, D = qg.shape
    psz = pages.shape[2]
    chains = (tb,) if tb > 1 else ()    # the state's axis of tiles
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(KV, n_cells),
        in_specs=[pl.BlockSpec((1, tb * rows, D), _tile_map),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, tb * rows, v_dim), _tile_map),
        scratch_shapes=[pltpu.VMEM((depth, psz, D), pages.dtype),
                        pltpu.VMEM((*chains, rows, v_dim), jnp.float32),
                        pltpu.VMEM((*chains, rows, 128), jnp.float32),
                        pltpu.VMEM((*chains, rows, 128), jnp.float32),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.SemaphoreType.DMA((1, depth))],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, tb=tb, **static),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KV, flat, v_dim), qg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*(x if interpret else _in_hbm(x) for x in (*tables, qg, pages)))


def ragged_attention_reference(q, k_pages, v_pages, seq_start,
                               num_tokens, kv_lengths, page_tables,
                               scale: Optional[float] = None,
                               window: Optional[int] = None,
                               v_dim: Optional[int] = None,
                               summary_rows=None,
                               block: Optional[int] = None):
    """Plain-XLA oracle with the same ragged semantics (full-softmax,
    gathered pages, jnp.repeat GQA — everything the kernel avoids)."""
    if v_pages is None:
        v_pages = k_pages[..., :v_dim]
    T, H, D = q.shape
    KV, total, psz, _ = k_pages.shape
    rep = H // KV
    S, nj = page_tables.shape
    if scale is None:
        scale = D ** -0.5
    ss = seq_start.astype(jnp.int32)
    nt = num_tokens.astype(jnp.int32)
    kvl = kv_lengths.astype(jnp.int32)
    tabs = jnp.clip(page_tables.astype(jnp.int32), 0, total - 1)
    Tk = nj * psz
    ks = k_pages[:, tabs].transpose(1, 0, 2, 3, 4).reshape(S, KV, Tk, D)
    vs = v_pages[:, tabs].transpose(1, 0, 2, 3, 4).reshape(S, KV, Tk, -1)
    kr = jnp.repeat(ks, rep, axis=1)                      # [S, H, Tk, D]
    vr = jnp.repeat(vs, rep, axis=1)
    logits = jnp.einsum("thd,shld->shtl", q.astype(jnp.float32),
                        kr.astype(jnp.float32)) * scale   # [S,H,T,Tk]
    t_idx = jnp.arange(T)
    rv = (t_idx[None, :] >= ss[:, None]) & \
        (t_idx[None, :] < (ss + nt)[:, None])             # [S, T]
    limit = (kvl - nt)[:, None] + (t_idx[None, :] - ss[:, None])
    if block is not None:
        limit = jnp.minimum(kvl[:, None] - 1,
                            (limit // block + 1) * block - 1)
    pos = jnp.arange(Tk)
    mask = rv[:, None, :, None] & \
        (pos[None, None, None, :] <= limit[:, None, :, None])
    if window is not None:
        mask &= pos[None, None, None, :] > limit[:, None, :, None] - window
    if summary_rows is not None:
        lo = summary_rows.astype(jnp.int32)[:, None]
        hole = (pos[None, :] >= lo) & (pos[None, :] < -(-lo // psz) * psz)
        mask &= ~hole[:, None, None, :]
    logits = jnp.where(mask, logits, _NEG)
    m = jnp.max(logits, -1, keepdims=True)
    p = jnp.where(mask, jnp.exp(logits - m), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    o = jnp.einsum("shtl,shld->shtd", p / jnp.where(l == 0.0, 1.0, l),
                   vr.astype(jnp.float32))                # [S, H, T, D]
    out = jnp.sum(jnp.where(rv[:, None, :, None], o, 0.0), axis=0)
    return out.transpose(1, 0, 2).astype(q.dtype)


# certification (ROADMAP item 5 / paddlelint PK105)
from .oracles import register_oracle  # noqa: E402

register_oracle(
    "ragged_paged_attention", kernel=ragged_paged_attention,
    reference=ragged_attention_reference,
    parity_test="tests/test_ragged_kernel.py::TestRaggedKernelParity")
