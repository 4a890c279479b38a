"""Ragged mixed prefill+decode paged-attention kernel
(ops/pallas_ragged.py) and the fused rope+append scatter kernels
(ops/fused.fused_rope_append / fused_append_rows). The plain-XLA
ragged_attention_reference is the correctness oracle. Runs in Pallas
interpret mode on CPU: same kernel logic as the TPU path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.fused import (append_run_count, append_run_table,
                                  append_slot_run_table, append_tile,
                                  fused_append_rows,
                                  fused_rope_append)
from paddle_tpu.ops import pallas_ragged
from paddle_tpu.ops.pallas_ragged import (_work_list,
                                          ragged_attention_reference,
                                          ragged_head_block,
                                          ragged_kernel_eligible,
                                          ragged_paged_attention,
                                          ragged_pages_visited,
                                          ragged_tile_block,
                                          ragged_tile_tokens)
from paddle_tpu.ops.references import (append_rows_reference,
                                       rope_append_reference)

# the run tables as the engine's step makes them, and the attention
# oracle: inside jitted programs (op by op, every primitive of every
# case's shapes compiles: 1.7 s an oracle call against 0.4)
append_run_table = jax.jit(append_run_table,
                           static_argnames=("tile", "max_runs"))
append_slot_run_table = jax.jit(append_slot_run_table,
                                static_argnames=("tile", "max_runs"))
ragged_attention_reference = jax.jit(
    ragged_attention_reference, static_argnames=("scale", "window", "v_dim"))


def _setup(T, S, H, KV, D, psz, pps, seed=0, dtype=jnp.float32):
    """Random pools + a ragged batch layout: sequence row spans are
    chosen disjoint inside [0, T); kv_lengths include the new tokens."""
    rng = np.random.RandomState(seed)
    total = S * pps + 1
    q = jnp.asarray(rng.randn(T, H, D), dtype)
    kp = jnp.asarray(rng.randn(KV, total, psz, D), dtype)
    vp = jnp.asarray(rng.randn(KV, total, psz, D), dtype)
    tab = jnp.asarray(1 + rng.permutation(total - 1)[:S * pps]
                      .reshape(S, pps), jnp.int32)
    # carve T rows into S disjoint spans (some possibly empty)
    cuts = np.sort(rng.choice(T + 1, S - 1, replace=False)) \
        if S > 1 else np.array([], np.int64)
    starts = np.concatenate([[0], cuts]).astype(np.int32)
    ends = np.concatenate([cuts, [T]]).astype(np.int32)
    nt = (ends - starts).astype(np.int32)
    kvl = np.zeros(S, np.int32)
    for i in range(S):
        lo = max(int(nt[i]), 1)
        kvl[i] = rng.randint(lo, pps * psz + 1)
    kvl = np.maximum(kvl, nt)
    return (q, kp, vp, jnp.asarray(starts), jnp.asarray(nt),
            jnp.asarray(kvl), tab)


def _check(q, kp, vp, ss, nt, kvl, tab, atol=2e-5, rtol=2e-5, window=None):
    out = ragged_paged_attention(q, kp, vp, ss, nt, kvl, tab, window=window)
    ref = ragged_attention_reference(q, kp, vp, ss, nt, kvl, tab,
                                     window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=atol, rtol=rtol)
    return out


def _engine_layout(kv_dec, chunk, kv_chunk, T=None, chunk_row=None,
                   share=False, H=8, KV=2, D=64, psz=8, pps=8, seed=0,
                   runs=None):
    """The engine's row tables in small: decode slot i owns row i (one
    token, or none where kv_dec[i] == 0), the prefill chunk owns `chunk`
    rows from `chunk_row` (default: right after the slots). rep 4 in
    float32 gives tiles of 32 tokens, so T > 32 spans several. With
    `runs` (speculative decoding) slot i owns runs[i] tokens from row
    i * max(runs)."""
    B = len(kv_dec)
    R = 1 if runs is None else max(runs)
    chunk_row = B * R if chunk_row is None else chunk_row
    T = chunk_row + chunk if T is None else T
    S = B + 1
    rng = np.random.RandomState(seed)
    total = S * pps + 1
    q = jnp.asarray(rng.randn(T, H, D), jnp.float32)
    kp = jnp.asarray(rng.randn(KV, total, psz, D), jnp.float32)
    vp = jnp.asarray(rng.randn(KV, total, psz, D), jnp.float32)
    tab = 1 + rng.permutation(total - 1)[:S * pps].reshape(S, pps)
    if share:
        # a shared prefix: slot 1 and the chunk read slot 0's first pages
        tab[1, :2] = tab[0, :2]
        tab[S - 1, :1] = tab[0, :1]
    ss = np.append(np.arange(B) * R, chunk_row)
    nt = np.append([int(k > 0) for k in kv_dec] if runs is None else runs,
                   chunk)
    kvl = np.append(kv_dec, kv_chunk)
    return (q, kp, vp, jnp.asarray(ss, jnp.int32),
            jnp.asarray(nt, jnp.int32), jnp.asarray(kvl, jnp.int32),
            jnp.asarray(tab, jnp.int32))


_LAYOUTS = {
    # 8 decode rows then a chunk that crosses the tile boundary at row
    # 32; contexts over several pages; T = 48 is not whole tiles
    "engine": dict(kv_dec=[17, 33, 9, 60, 1, 25, 40, 8], chunk=40,
                   kv_chunk=24 + 40),
    # the chunk starts and ends inside tile 0; tile 1 holds no row
    "chunk_inside_a_tile": dict(kv_dec=[5, 12, 30, 2], chunk=10,
                                kv_chunk=23, chunk_row=8, T=40),
    "T_not_whole_tiles": dict(kv_dec=[9, 3], chunk=35, kv_chunk=35,
                              T=44),
    # lengths and the chunk's start exactly on page boundaries
    "on_page_boundaries": dict(kv_dec=[8, 16, 64, 24], chunk=32,
                               kv_chunk=16 + 32),
    "idle_between_live": dict(kv_dec=[7, 0, 19, 0, 0, 33, 0, 4], chunk=30,
                              kv_chunk=41),
    "no_chunk_decode_only": dict(kv_dec=[7, 19, 0, 64, 33], chunk=0,
                                 kv_chunk=0, T=37),
    "shared_physical_pages": dict(kv_dec=[20, 27, 6], chunk=34,
                                  kv_chunk=50, share=True),
    # every context is pages_per_seq full pages
    "full_tables": dict(kv_dec=[64, 64, 64], chunk=36, kv_chunk=64),
    # a sliding window (the `window` key goes to the kernel, the rest to
    # the layout). rep 6 (tiles of 20 tokens in float32): the window is
    # not whole pages, the chunk straddles it (its first rows still see
    # keys from before the chunk, its last rows only the chunk)
    "window_rep6_chunk_straddles": dict(
        kv_dec=[17, 33, 9, 60, 1, 25], chunk=30, kv_chunk=22 + 30,
        H=12, window=21),
    # rep 9 (tiles of 8 tokens): contexts far past the window, so whole
    # leading pages are never walked; their table entries are dead
    "window_rep9_dead_pages": dict(
        kv_dec=[64, 40, 0, 57, 3], chunk=19, kv_chunk=64, H=18,
        window=13, dead=True),
    # window of one page exactly, starts on page boundaries
    "window_one_page": dict(kv_dec=[8, 16, 64, 24], chunk=32,
                            kv_chunk=16 + 32, window=8),
    # a window wider than every context is full causal attention
    "window_wider_than_context": dict(kv_dec=[20, 27, 6], chunk=34,
                                      kv_chunk=50, window=100),
    # ONE KV head under 16 query heads (tiles of 8 tokens, six of them):
    # a page visit serves a block of 4 tiles (`ragged_tile_block`, read
    # from the shapes); the chunk starts inside a cell
    "one_kv_head_a_block_of_tiles": dict(
        kv_dec=[17, 33, 0, 60, 1, 25], chunk=37, kv_chunk=20 + 37,
        chunk_row=11, T=48, H=16, KV=1),
    # the chunk's last two tokens are alone in tile 1: 8 rows, the
    # narrow window exactly (float32: 8 rows a packed row)
    "chunk_tail_of_two_tokens": dict(
        kv_dec=[17, 33, 9, 60, 1, 25, 40, 8], chunk=26, kv_chunk=20 + 26),
    # one query head a KV head (rep 1, tiles of 128 tokens): a decode
    # row is ONE row of its tile, the chunk's tail in tile 1 seven
    "rep1_decode_rows_and_a_chunk": dict(
        kv_dec=[17, 0, 9, 60, 1], chunk=130, kv_chunk=12 + 130, H=2,
        pps=20),
    # speculative runs of 1-3 tokens a slot (rep 4: 4-12 rows): two
    # tokens fit the 8-row window where they start on a packed row
    # (slots 0 and 2) and not where they start mid-way (slot 1: rows
    # 12-19), three never
    "speculative_runs_that_fit_and_not": dict(
        kv_dec=[17, 33, 9, 60, 0, 25], runs=[2, 2, 1, 3, 0, 2], chunk=21,
        kv_chunk=30 + 21),
    # rep 6 without a window: a token's 6 rows straddle a packed row
    # (the window is two of them, 16 rows); slots start 18 rows apart,
    # so a run of two (12 rows) fits from row 0 (slot 0) and not from
    # row 54 (slot 3: rows 54-65 against the window 48-63), three never
    "rep6_runs_of_two_and_three": dict(
        kv_dec=[17, 33, 9, 60, 5], runs=[2, 1, 3, 2, 1], chunk=9,
        kv_chunk=9 + 14, H=12),
}


def _layout(name):
    """(_engine_layout arrays, window) of a named case. With `dead` the
    table entries of pages wholly below every row's window point at a
    page of NaNs: the kernel must neither fetch nor read them."""
    spec = dict(_LAYOUTS[name])
    window, dead = spec.pop("window", None), spec.pop("dead", False)
    q, kp, vp, ss, nt, kvl, tab = _engine_layout(**spec)
    kvl = jnp.maximum(kvl, nt)      # a run's tokens are in its context
    if dead:
        psz = kp.shape[2]
        oldest = np.asarray(kvl) - np.asarray(nt) - window + 1
        tab = np.array(tab)
        for i, o in enumerate(oldest):
            tab[i, :max(int(o), 0) // psz] = 0
        kp, vp = kp.at[:, 0].set(jnp.nan), vp.at[:, 0].set(jnp.nan)
        tab = jnp.asarray(tab)
    return (q, kp, vp, ss, nt, kvl, tab), window


#: launches whose page visits serve a BLOCK of KV heads: the KV heads,
#: the block the kernel takes of them, the query heads a KV head, and
#: what else the launch has (`_engine_layout` keys; `idle` empties a
#: slot, `sentinel` marks its table and every dead tail -1, `summary`
#: gives pooled rows a sequence: chunk-summary attention)
_HEAD_BLOCKS = {
    "kv2_rep9_window": dict(KV=2, hb=2, rep=9, window=13),
    "kv8_rep4_idle_slot_sentinel": dict(KV=8, hb=8, rep=4, idle=True,
                                        sentinel=True),
    "kv16_rep6": dict(KV=16, hb=16, rep=6),
    # 32 heads go as two blocks of 16, the most a visit serves
    "kv32_rep1_summary": dict(KV=32, hb=16, rep=1,
                              summary=[8, 0, 13, 16]),
}


class TestRaggedKernelParity:
    @pytest.mark.parametrize("name", list(_HEAD_BLOCKS))
    def test_a_head_block_is_one_head_a_visit_bit_for_bit(
            self, name, monkeypatch):
        spec = dict(_HEAD_BLOCKS[name])
        KV, hb, rep = spec.pop("KV"), spec.pop("hb"), spec.pop("rep")
        kv_dec = [17, 0 if spec.pop("idle", False) else 9, 30]
        q, kp, vp, ss, nt, kvl, tab = _engine_layout(
            kv_dec=kv_dec, chunk=11, kv_chunk=16 + 11, H=KV * rep, KV=KV,
            pps=4)
        if spec.pop("sentinel", False):
            live = -(-np.asarray(kvl) // kp.shape[2])
            tab = jnp.where(np.arange(tab.shape[1])[None] < live[:, None],
                            tab, -1)
        kw = dict(window=spec.pop("window", None))
        if "summary" in spec:
            kw["summary_rows"] = jnp.asarray(spec.pop("summary"), jnp.int32)
        tq = ragged_tile_tokens(q.shape[0], rep, q.dtype)
        assert ragged_head_block(KV, tq * rep, 64, kp.shape[2], 4) == hb
        out = ragged_paged_attention(q, kp, vp, ss, nt, kvl, tab, **kw)
        ref = ragged_attention_reference(q, kp, vp, ss, nt, kvl, tab, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        monkeypatch.setattr(pallas_ragged, "ragged_head_block",
                            lambda *a, **k: 1)
        # (the launch is traced once for equal shapes: trace it again)
        pallas_ragged._launch_jit.clear_cache()
        one = ragged_paged_attention(q, kp, vp, ss, nt, kvl, tab, **kw)
        pallas_ragged._launch_jit.clear_cache()
        np.testing.assert_array_equal(np.asarray(out), np.asarray(one))

    @pytest.mark.parametrize("name,KV,rows,D,psz,latent,want", [
        # the six configurations' launches in bfloat16: T = 288 (Ouro
        # 272) rows, tiles of `ragged_tile_tokens` x rep query rows
        ("mistral", 8, 128, 128, 256, False, 8),
        ("laguna_full_rep6", 8, 96, 128, 256, False, 8),
        ("laguna_window_rep9", 8, 144, 128, 256, False, 8),
        ("axk1_latent", 1, 128, 640, 256, True, 1),
        ("evabyte", 32, 128, 128, 256, False, 16),
        ("ouro", 16, 128, 128, 64, False, 16),
        # no divisor of 7 but 7 and 1, and 7 heads of this tile do not
        # fit: one head a visit
        ("no_fitting_divisor", 7, 1024, 128, 256, False, 1),
        ("a_latent_cache_is_one_head", 8, 128, 128, 256, True, 1),
    ])
    def test_head_block_follows_the_shapes(self, name, KV, rows, D, psz,
                                           latent, want):
        hb = ragged_head_block(KV, rows, D, psz, 2, latent=latent)
        assert hb == want and KV % hb == 0
        assert hb <= pallas_ragged._HEAD_BLOCK_MAX
        # the cell's VMEM by hand: q and out tiles twice, f32 state (m
        # and l a 128-lane column each), two rings of K and V blocks
        block = hb * psz * D * 2
        slots = pallas_ragged._page_buffers(block)
        vmem = (4 * hb * rows * D * 2 + hb * rows * (D + 256) * 4
                + 2 * slots * block)
        assert vmem == pallas_ragged._block_vmem(hb, rows, D, psz, 2)
        if hb > 1:
            assert vmem <= pallas_ragged._VMEM_BUDGET < 16 * 2 ** 20
        bigger = [n for n in range(hb + 1, min(
            KV, pallas_ragged._HEAD_BLOCK_MAX) + 1) if KV % n == 0]
        assert latent or all(
            pallas_ragged._block_vmem(n, rows, D, psz, 2)
            > pallas_ragged._VMEM_BUDGET for n in bigger)

    @pytest.mark.parametrize("name", list(_LAYOUTS))
    def test_engine_layouts(self, name):
        arrays, window = _layout(name)
        out = ragged_paged_attention(*arrays, window=window)
        assert np.isfinite(np.asarray(out)).all()
        q, kp, vp, ss, nt, kvl, tab = arrays
        # the oracle gathers every table entry: give it finite pages
        _check(q, jnp.nan_to_num(kp), jnp.nan_to_num(vp), ss, nt, kvl,
               tab, window=window)
        if window is not None:
            full = ragged_attention_reference(*arrays[:3], ss, nt, kvl, tab)
            same = np.allclose(np.asarray(out), np.asarray(full), atol=1e-4)
            assert same == (window >= int(np.max(np.asarray(kvl))))

    @pytest.mark.parametrize("name", list(_LAYOUTS))
    def test_visit_count_follows_live_pages(self, name):
        # the exported count (the engine's pages_visited) is the kernel's
        # own work list; a decode-only launch fetches exactly its live
        # pages, any launch at most once for every tile
        (q, kp, _, ss, nt, kvl, tab), window = _layout(name)
        T, psz, pps = q.shape[0], kp.shape[2], tab.shape[1]
        rep = q.shape[1] // kp.shape[0]
        tq = ragged_tile_tokens(T, rep, q.dtype)
        # the tiles a grid cell owns: one, but under ONE KV head
        tb = ragged_tile_block(
            ragged_head_block(kp.shape[0], tq * rep, q.shape[2], psz, 4),
            -(-T // tq), tq * rep, q.shape[2], psz, 4)
        assert tb == (4 if name == "one_kv_head_a_block_of_tiles" else 1)
        tiling = dict(page_size=psz, pages_per_seq=pps)
        visited = ragged_pages_visited(ss, nt, kvl, T=T, rep=rep,
                                       dtype=q.dtype, window=window, tb=tb,
                                       **tiling)
        if window is not None:
            tiling["window"] = window
        tile_first, _, pair_first = _work_list(
            ss, nt, kvl, tq=tb * tq, n_tiles=-(-T // (tb * tq)), **tiling)
        assert int(pair_first[tile_first[-1]]) == visited
        # the (tile, page) softmax updates: the visits at one tile a
        # cell; a block of tiles fetches less and computes the same
        chains = ragged_pages_visited(ss, nt, kvl, T=T, rep=rep,
                                      dtype=q.dtype, window=window,
                                      **{k: v for k, v in tiling.items()
                                         if k != "window"})
        assert (visited < chains) if tb > 1 else (visited == chains)
        if window is not None:
            # the walk is the pages that hold a key some row of the
            # (tile, sequence) pair sees, counted row by row
            want = 0
            for t0 in range(0, T, tq):
                for i in range(len(ss)):
                    rows = [r for r in range(t0, min(t0 + tq, T))
                            if int(ss[i]) <= r < int(ss[i]) + int(nt[i])]
                    pos = [int(kvl[i]) - int(nt[i]) + r - int(ss[i])
                           for r in rows]
                    seen = {k // psz for p_ in pos
                            for k in range(max(p_ - window + 1, 0), p_ + 1)}
                    want += len(seen)
            assert visited == want
            assert visited <= ragged_pages_visited(
                ss, nt, kvl, T=T, rep=rep, dtype=q.dtype, page_size=psz,
                pages_per_seq=pps)
            return
        live = int(np.sum(-(-np.asarray(kvl)[np.asarray(nt) > 0] // psz)))
        assert live <= visited <= -(-T // tq) * live
        if int(nt[-1]) == 0:
            assert visited == live
        else:
            # without the chunk nothing is fetched twice
            dec = ragged_pages_visited(ss[:-1], nt[:-1], kvl[:-1], T=T,
                                       rep=rep, dtype=q.dtype, **tiling)
            assert dec == live - -(-int(kvl[-1]) // psz)

    @pytest.mark.parametrize("T,S,H,KV,D,psz,pps", [
        (12, 3, 8, 2, 128, 16, 4),   # GQA rep=4, mixed spans
        (9, 4, 4, 1, 64, 16, 2),     # MQA, D=64, non-128-multiple T
        (20, 2, 4, 4, 128, 8, 4),    # MHA rep=1, small pages
    ])
    def test_matches_reference(self, T, S, H, KV, D, psz, pps):
        _check(*_setup(T, S, H, KV, D, psz, pps))

    @pytest.mark.parametrize("window", [None, 512, 11])
    def test_a_padded_pair_is_two_unpadded_softmaxes(self, window):
        """Differential heads in the pair layout (Phi-4-flash: KV 10, D
        128, 4 query heads a KV head, no rotary; here the same group at
        D 32 over 2 pairs): a KV head of the pool is two published heads
        side by side, a query head is padded with zeros on the side of
        the K head it does not use, the scale is the unpadded head's.
        Head h of the output is then softmax(q_h K_{2 (h // 4) + h % 2}^T)
        [V_2j | V_2j+1] — computed here UNPADDED, a 16-wide softmax a
        query head against its own K head, once for each V head of the
        pair."""
        from paddle_tpu.models.phi4flash import pair_queries
        T, S, H, KV, D, psz, pps = 40, 3, 8, 2, 32, 8, 6
        q, kp, vp, ss, nt, kvl, tab = _setup(T, S, H, KV, D, psz, pps,
                                             seed=4)
        q16 = q[..., :D // 2]
        scale = (D // 2) ** -0.5
        out = ragged_paged_attention(pair_queries(q16), kp, vp, ss, nt, kvl,
                                     tab, scale=scale, window=window)
        h = np.arange(H)
        half = (h % 2)[:, None] * (D // 2) + np.arange(D // 2)[None]
        # query head h's own K head, 16 wide: a pool of H heads, rep 1
        k_own = jnp.take_along_axis(
            kp[h // 4], jnp.asarray(half)[:, None, None, :], -1)
        want = jnp.concatenate([
            ragged_attention_reference(
                q16, k_own, vp[h // 4][..., side], ss, nt, kvl, tab,
                scale=scale, window=window)
            for side in (slice(0, D // 2), slice(D // 2, D))], -1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_mixed_prefill_decode_batch(self):
        # the engine's exact shape: decode rows 0..B-1 (1 token each),
        # a prefill chunk on rows B.., kv_lengths include the new rows
        B, C, psz, pps, KV, H, D = 3, 5, 8, 4, 2, 4, 64
        T, S = B + C, B + 1
        rng = np.random.RandomState(1)
        total = S * pps + 1
        q = jnp.asarray(rng.randn(T, H, D), jnp.float32)
        kp = jnp.asarray(rng.randn(KV, total, psz, D), jnp.float32)
        vp = jnp.asarray(rng.randn(KV, total, psz, D), jnp.float32)
        tab = jnp.asarray(1 + rng.permutation(total - 1)[:S * pps]
                          .reshape(S, pps), jnp.int32)
        ss = jnp.asarray(list(range(B)) + [B], jnp.int32)
        nt = jnp.asarray([1, 1, 1, C], jnp.int32)
        kvl = jnp.asarray([7, 19, 1, 6 + C], jnp.int32)
        _check(q, kp, vp, ss, nt, kvl, tab)

    def test_empty_slots_emit_zeros(self):
        # num_tokens=0 rows (idle engine slots) must come back all-zero
        q, kp, vp, ss, nt, kvl, tab = _setup(10, 3, 4, 2, 128, 16, 2,
                                             seed=2)
        nt = nt.at[1].set(0)
        out = _check(q, kp, vp, ss, nt, kvl, tab)
        lo, hi = int(ss[1]), int(ss[1]) + 0
        covered = np.zeros(10, bool)
        ss_np, nt_np = np.asarray(ss), np.asarray(nt)
        for i in range(3):
            covered[ss_np[i]:ss_np[i] + nt_np[i]] = True
        np.testing.assert_array_equal(
            np.asarray(out)[~covered], 0.0)

    def test_sentinel_table_entries(self):
        # dead tail pages marked -1 (allocator sentinel): clamped, never
        # read (kv_length masks them), parity holds
        q, kp, vp, ss, nt, kvl, tab = _setup(8, 2, 4, 2, 64, 16, 4,
                                             seed=3)
        kvl = jnp.minimum(kvl, 16)      # only page 0 of each seq live
        tab = tab.at[:, 1:].set(-1)
        _check(q, kp, vp, ss, nt, kvl, tab)

    def test_single_sequence_whole_buffer(self):
        # degenerate batch: one sequence owns every row (pure prefill)
        T = 16
        q, kp, vp, _, _, _, tab = _setup(T, 1, 8, 2, 128, 16, 4, seed=4)
        ss = jnp.asarray([0], jnp.int32)
        nt = jnp.asarray([T], jnp.int32)
        kvl = jnp.asarray([T + 13], jnp.int32)
        _check(q, kp, vp, ss, nt, kvl, tab)

    def test_causality_within_chunk(self):
        # a token must NOT see later chunk rows: flipping a later row's
        # K/V leaves earlier rows' outputs unchanged
        T, psz, pps = 6, 8, 2
        rng = np.random.RandomState(5)
        q = jnp.asarray(rng.randn(T, 4, 64), jnp.float32)
        kp = jnp.asarray(rng.randn(2, pps + 1, psz, 64), jnp.float32)
        vp = jnp.asarray(rng.randn(2, pps + 1, psz, 64), jnp.float32)
        tab = jnp.asarray([[1, 2]], jnp.int32)
        ss = jnp.asarray([0], jnp.int32)
        nt = jnp.asarray([T], jnp.int32)
        kvl = jnp.asarray([T], jnp.int32)   # chunk starts the sequence
        out1 = ragged_paged_attention(q, kp, vp, ss, nt, kvl, tab)
        # last token's K/V row lives at position T-1 -> page tab[0, .]
        pg, off = (T - 1) // psz, (T - 1) % psz
        kp2 = kp.at[:, tab[0, pg], off].set(99.0)
        vp2 = vp.at[:, tab[0, pg], off].set(-99.0)
        out2 = ragged_paged_attention(q, kp2, vp2, ss, nt, kvl, tab)
        np.testing.assert_array_equal(np.asarray(out1)[:T - 1],
                                      np.asarray(out2)[:T - 1])

    def test_bf16(self):
        q, kp, vp, ss, nt, kvl, tab = _setup(12, 3, 8, 2, 128, 16, 4,
                                             seed=6, dtype=jnp.bfloat16)
        out = ragged_paged_attention(q, kp, vp, ss, nt, kvl, tab)
        ref = ragged_attention_reference(q, kp, vp, ss, nt, kvl, tab)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=3e-2, rtol=3e-2)

    def test_eligibility_mirrors_paged(self):
        assert ragged_kernel_eligible(8, 2, 128, 16)
        assert ragged_kernel_eligible(4, 1, 64, 16)
        assert not ragged_kernel_eligible(4, 1, 24, 16)   # tiny MLA D
        assert not ragged_kernel_eligible(3, 2, 128, 16)  # H % KV != 0


def _row_tables(B, R, C, psz, dec, chunk, pps=4):
    """The engine's row tables in small: decode slot s owns rows
    [s*R, (s+1)*R), the prefill chunk the rows from B*R; sequence s
    holds pages 1 + s*pps .. (page 0 is the trash page idle rows name).
    `dec` {slot: (first position, rows)}, `chunk` (first position, rows)
    or None. Returns seq_start, num_tokens, page, off, positions."""
    S, T = B + 1, B * R + C
    seq_start = np.append(np.arange(B) * R, B * R).astype(np.int32)
    num_tokens = np.zeros(S, np.int32)
    page, off = np.zeros(T, np.int32), np.zeros(T, np.int32)
    positions = np.zeros(T, np.int32)
    seqs = dict(dec)
    if chunk is not None:
        seqs[B] = chunk
    for s_, (p0, n) in seqs.items():
        pos = p0 + np.arange(n)
        assert pos[-1] < pps * psz
        rows = slice(seq_start[s_], seq_start[s_] + n)
        num_tokens[s_] = n
        positions[rows] = pos
        page[rows] = 1 + s_ * pps + pos // psz
        off[rows] = pos % psz
    return seq_start, num_tokens, page, off, positions


def _runs_by_hand(seq_start, num_tokens, page, off, tile):
    """The run rule as a loop: [(first row, rows, page, tile, offset)]."""
    runs = []
    for s0, n in zip(seq_start, num_tokens):
        for t in range(s0, s0 + n):
            key = (page[t], off[t] // tile)
            if t > s0 and key == (page[t - 1], off[t - 1] // tile):
                runs[-1][1] += 1
            else:
                runs.append([t, 1, *key, off[t] % tile])
    return sorted(map(tuple, runs))


#: name -> (B, R, C, page_size, dec, chunk); bfloat16 tiles hold 16
#: rows, float32 tiles 8
LAYOUTS = {
    "decode_rows_only": (6, 1, 16, 32,
                         {0: (5, 1), 2: (31, 1), 3: (32, 1), 5: (0, 1)},
                         None),
    "chunk_from_a_tile_boundary": (2, 1, 32, 32, {1: (9, 1)}, (32, 32)),
    "chunk_from_mid_tile": (2, 1, 32, 32, {0: (3, 1)}, (5, 30)),
    "chunk_across_a_page": (2, 1, 32, 32, {}, (20, 32)),
    "spec_rows_across_a_tile": (3, 4, 16, 32,
                                {0: (14, 4), 2: (30, 3)}, (0, 7)),
    "all_rows_idle": (4, 1, 16, 32, {}, None),
    # every decode row live, the chunk one tile more than its rows fill
    "run_table_full": (4, 1, 32, 32,
                       {0: (1, 1), 1: (2, 1), 2: (3, 1), 3: (4, 1)},
                       (7, 32)),
    "page_of_8_under_a_tile_of_16": (3, 1, 16, 8, {0: (7, 1), 1: (8, 1)},
                                     (12, 14)),
}


class TestFusedRopeAppend:
    """`fused_rope_append` against the plain `rope_append_reference`,
    bit for bit on q and on EVERY page of both pools: the pages and rows
    no live row names, the trash page's too, come back untouched."""

    @staticmethod
    def _check(layout, dtype, KV=2, Hq=4, D=64, identity=False, seed=0):
        B, R, C, psz, dec, chunk = layout
        seq_start, num_tokens, page, off, _ = _row_tables(
            B, R, C, psz, dec, chunk)
        T, total = B * R + C, 1 + (B + 1) * 4
        rng = np.random.RandomState(seed)

        def arr(*shape):
            return jnp.asarray(rng.randn(*shape), dtype)

        q, k, v = arr(T, Hq, D), arr(T, KV, D), arr(T, KV, D)
        if identity:
            cos = jnp.ones((T, D // 2), dtype)
            sin = jnp.zeros((T, D // 2), dtype)
        else:
            cos, sin = arr(T, D // 2), arr(T, D // 2)
        kp, vp = arr(KV, total, psz, D), arr(KV, total, psz, D)
        tile = append_tile(dtype, psz)
        G = B * R + -(-C // tile) + 1
        runs = append_run_table(
            jnp.asarray(seq_start), jnp.asarray(num_tokens),
            jnp.asarray(page), jnp.asarray(off), tile=tile, max_runs=G)
        live = np.zeros(T, bool)
        for s0, n in zip(seq_start, num_tokens):
            live[s0:s0 + n] = True
        # jitted as the kernel is: the compiler then rounds the rope of
        # both the same way (op by op it keeps a product it would fuse)
        want = jax.jit(rope_append_reference)(
            q, k, v, cos, sin, kp, vp, jnp.asarray(page),
            jnp.asarray(off), jnp.asarray(live))
        got = fused_rope_append(q, k, v, cos, sin, kp, vp, runs)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(
                np.asarray(g.astype(jnp.float32)),
                np.asarray(w.astype(jnp.float32)))
        # the reference itself wrote the live rows and nothing else
        changed = np.any(np.asarray(want[2] != vp), (0, 3))
        assert changed.sum() <= live.sum()
        assert not changed[0].any()
        return runs, G

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("name", sorted(LAYOUTS))
    def test_equals_the_reference_bit_for_bit(self, name, dtype):
        runs, G = self._check(LAYOUTS[name], dtype)
        n_runs = int((np.asarray(runs)[G:2 * G] > 0).sum())
        if name == "all_rows_idle":
            assert n_runs == 0
        if name == "run_table_full" and dtype == jnp.float32:
            assert n_runs == G          # no padded run at all

    @pytest.mark.parametrize("KV", [1, 8, 32])
    def test_kv_heads(self, KV):
        self._check(LAYOUTS["chunk_from_mid_tile"], jnp.bfloat16, KV=KV,
                    Hq=max(KV, 4), D=128, seed=KV)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    def test_identity_rope_is_a_pure_append(self, dtype):
        # cos=1 / sin=0 (the GPT family): q comes back as it went in
        self._check(LAYOUTS["spec_rows_across_a_tile"], dtype,
                    identity=True, seed=1)

    @pytest.mark.parametrize("seed", range(6))
    def test_run_table_is_the_hosts_rule(self, seed):
        """The device's table, the host's count of it (the step record's
        `append_runs`) and the rule written as a loop agree on seeded
        row tables."""
        rng = np.random.RandomState(seed)
        B, R, C, psz = 5, 1 + seed % 3, 24, 32
        tile = (8, 16)[seed % 2]
        dec = {s_: (int(rng.randint(0, 100)), int(rng.randint(1, R + 1)))
               for s_ in range(B) if rng.rand() < 0.7}
        chunk = (int(rng.randint(0, 100)), int(rng.randint(1, C + 1))) \
            if seed != 3 else None
        seq_start, num_tokens, page, off, _ = _row_tables(
            B, R, C, psz, dec, chunk)
        G = B * R + -(-C // tile) + 1
        table = np.asarray(append_run_table(
            jnp.asarray(seq_start), jnp.asarray(num_tokens),
            jnp.asarray(page), jnp.asarray(off), tile=tile,
            max_runs=G)).reshape(5, G)
        want = _runs_by_hand(seq_start, num_tokens, page, off, tile)
        n = len(want)
        assert n <= G
        assert sorted(map(tuple, table[:, :n].T)) == want
        live, first = np.zeros(len(page), bool), np.zeros(len(page), bool)
        for s0, k in zip(seq_start, num_tokens):
            live[s0:s0 + k], first[s0] = True, k > 0
        assert append_run_count(live, first, page, off, tile) == n
        # a run past the last live one: no rows, the last live tile
        assert not table[1, n:].any()
        assert (table[2:4, n:] == table[2:4, n - 1:n]).all()

    #: row tables of ONE geometry (3 slots of 2 rows, a chunk of 24,
    #: pages of 32: the cases of a dtype and pool count share a compile):
    #: name -> (dec, chunk) of `_row_tables`, or the (page, offset) of
    #: 30 pooling slots (`append_slot_run_table`: page 0 is idle)
    ROWS = {
        "chunk_across_tiles": ({0: (3, 1)}, (5, 24)),
        "chunk_across_a_page": ({}, (20, 24)),
        # slot 0's rows 15, 16 cross a tile; slot 1 idle; slot 2 one of 2
        "idle_rows_between_live": ({0: (15, 2), 2: (30, 1)}, (0, 7)),
        "nothing_live": ({}, None),
        # two decode slots' pooled rows, then a chunk's six neighbours
        # of a summary page from row 13 on (they cross a tile)
        "pooling_slots": (
            [3, 0, 0, 7, 0] + [5] * 6 + [0] * 19,
            [31, 0, 0, 16, 0] + list(range(13, 19)) + [0] * 19),
        "pooling_slots_idle": ([0] * 30, [0] * 30),
    }

    @pytest.mark.parametrize("name,dtype,pools,KV", [
        ("chunk_across_tiles", jnp.float32, 1, 1),
        ("chunk_across_tiles", jnp.bfloat16, 2, 2),
        ("chunk_across_a_page", jnp.bfloat16, 1, 1),
        ("chunk_across_a_page", jnp.float32, 2, 2),
        ("idle_rows_between_live", jnp.bfloat16, 1, 1),
        ("idle_rows_between_live", jnp.bfloat16, 2, 2),
        ("nothing_live", jnp.bfloat16, 1, 1),
        ("nothing_live", jnp.float32, 2, 2),
        ("pooling_slots", jnp.bfloat16, 2, 2),
        ("pooling_slots", jnp.float32, 1, 1),
        # 16 heads: whole sublane tiles of bfloat16, rows not widened
        ("pooling_slots", jnp.bfloat16, 1, 16),
        ("pooling_slots_idle", jnp.bfloat16, 2, 2),
    ], ids=lambda v: getattr(v, "__name__", str(v)))
    def test_append_rows(self, name, dtype, pools, KV):
        """`fused_append_rows` by the run table — ONE pool (the latent
        row) or a K / V pair (the pooled rows) — against the rows put by
        hand and `append_rows_reference`, bit for bit on EVERY tile of
        every pool; the device table's live runs are the host's count
        (the step records' `append_runs` / `pool_append_runs`)."""
        B, R, C, psz, D, total = 3, 2, 24, 32, 32, 17
        tile = append_tile(dtype, psz)
        G = B * R + -(-C // tile) + 1
        if name.startswith("pooling_slots"):
            page, off = (np.asarray(x, np.int32) for x in self.ROWS[name])
            live, first = page > 0, False
            runs = append_slot_run_table(
                jnp.asarray(page), jnp.asarray(off), tile=tile, max_runs=G)
        else:
            seq_start, num_tokens, page, off, _ = _row_tables(
                B, R, C, psz, *self.ROWS[name])
            live, first = (np.zeros(len(page), bool) for _ in range(2))
            for s0, k in zip(seq_start, num_tokens):
                live[s0:s0 + k], first[s0] = True, k > 0
            runs = append_run_table(
                jnp.asarray(seq_start), jnp.asarray(num_tokens),
                jnp.asarray(page), jnp.asarray(off), tile=tile, max_runs=G)
        rng = np.random.RandomState(2)
        pages = tuple(jnp.asarray(rng.randn(KV, total, psz, D), dtype)
                      for _ in range(pools))
        rows = tuple(jnp.asarray(rng.randn(len(page), KV, D), dtype)
                     for _ in range(pools))
        # by hand: the live rows where the row tables say, nothing else
        want = [np.array(p.astype(jnp.float32)) for p in pages]
        for w, r in zip(want, rows):
            for t in np.flatnonzero(live):
                w[:, page[t], off[t]] = np.asarray(r.astype(jnp.float32))[t]
        args = (pages, rows, runs) if pools > 1 else \
            (pages[0], rows[0], runs)
        ref, got = append_rows_reference(*args), fused_append_rows(*args)
        assert isinstance(got, tuple) == (pools > 1)
        if pools == 1:
            ref, got = (ref,), (got,)
        for g, r, w, p in zip(got, ref, want, pages):
            assert g.dtype == p.dtype and g.shape == p.shape
            for x in (g, r):
                np.testing.assert_array_equal(
                    np.asarray(x.astype(jnp.float32)), w)
        n_runs = int((np.asarray(runs)[G:2 * G] > 0).sum())
        assert n_runs == append_run_count(live, first, page, off, tile)
        assert n_runs <= G and (n_runs > 0) == bool(live.any())
        if name == "pooling_slots":
            # two decode slots' rows, and the tiles rows 13..18 touch
            assert n_runs == 2 + (18 // tile - 13 // tile + 1)
        if name == "idle_rows_between_live":
            assert not live[1:3].all() and live[0] and live[4]


class TestRaggedJit:
    def test_jit_no_retrace_on_data_change(self):
        # the engine's contract: joins/leaves are data changes only
        args1 = _setup(12, 3, 8, 2, 128, 16, 4, seed=7)
        args2 = _setup(12, 3, 8, 2, 128, 16, 4, seed=8)
        f = jax.jit(ragged_paged_attention)
        f(*args1)
        f(*args2)
        assert f._cache_size() == 1


# ------------------------------------------------- the block-causal rule
#: generation by diffusion over blocks: slots of B rows (their block is
#: the last of their sequence), a chunk of whole blocks; pages of 8 hold
#: whole blocks of 4 or 8
_BLOCK_LAYOUTS = {
    "a_chunk_across_a_page_border": lambda B: dict(
        kv_dec=[0, 0], runs=[0, 0], chunk=5 * B, kv_chunk=2 * B + 5 * B),
    "slots_of_a_block": lambda B: dict(
        kv_dec=[3 * B, 8 * B, B, 5 * B], runs=[B] * 4, chunk=0, kv_chunk=0),
    "mixed_in_one_tile": lambda B: dict(
        kv_dec=[4 * B, 0, 7 * B], runs=[B, 0, B], chunk=2 * B,
        kv_chunk=6 * B),
    "lengths_on_page_borders": lambda B: dict(
        kv_dec=[16, 8, 32], runs=[B] * 3, chunk=16, kv_chunk=8 + 16),
}


def _dense_block_rule(q, kp, vp, ss, nt, kvl, tab, block):
    """The rule written out in numpy: the row at position p of its
    sequence sees keys 0 .. min(kv - 1, (p // B + 1) B - 1)."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, kp, vp))
    T, H, D = q.shape
    KV, psz = kp.shape[0], kp.shape[2]
    out = np.zeros((T, H, D))
    for i in range(len(ss)):
        n, kv = int(nt[i]), int(kvl[i])
        pages = np.asarray(tab[i])
        k = kp[:, pages].reshape(KV, -1, D)[:, :kv]
        v = vp[:, pages].reshape(KV, -1, D)[:, :kv]
        for t in range(n):
            p = kv - n + t
            last = min(kv - 1, (p // block + 1) * block - 1)
            for h in range(H):
                g = h // (H // KV)
                s = k[g, :last + 1] @ q[int(ss[i]) + t, h] * D ** -0.5
                w = np.exp(s - s.max())
                out[int(ss[i]) + t, h] = (w / w.sum()) @ v[g, :last + 1]
    return out


class TestBlockCausal:
    @pytest.mark.parametrize("block", [4, 8])
    @pytest.mark.parametrize("name", list(_BLOCK_LAYOUTS))
    def test_kernel_reference_and_the_rule_by_hand(self, name, block):
        q, kp, vp, ss, nt, kvl, tab = _engine_layout(
            **_BLOCK_LAYOUTS[name](block))
        out = ragged_paged_attention(q, kp, vp, ss, nt, kvl, tab,
                                     block=block)
        ref = ragged_attention_reference(q, kp, vp, ss, nt, kvl, tab,
                                         block=block)
        want = _dense_block_rule(q, kp, vp, ss, nt, kvl, tab, block)
        # kernel and reference change alike: BOTH against the rule
        np.testing.assert_allclose(np.asarray(ref), want, atol=2e-5,
                                   rtol=2e-5)
        np.testing.assert_allclose(np.asarray(out), want, atol=2e-5,
                                   rtol=2e-5)
        # ... and the rule is not the causal one where a block is open
        causal = ragged_attention_reference(q, kp, vp, ss, nt, kvl, tab)
        assert not np.allclose(np.asarray(causal), want, atol=1e-3)

    def test_a_block_of_one_is_the_causal_rule_bit_for_bit(self):
        q, kp, vp, ss, nt, kvl, tab = _engine_layout(**_LAYOUTS["engine"])
        for fn in (ragged_paged_attention, ragged_attention_reference):
            np.testing.assert_array_equal(
                np.asarray(fn(q, kp, vp, ss, nt, kvl, tab, block=1)),
                np.asarray(fn(q, kp, vp, ss, nt, kvl, tab)))

    def test_refused_with_a_window_or_pages_of_broken_blocks(self):
        q, kp, vp, ss, nt, kvl, tab = _engine_layout(**_LAYOUTS["engine"])
        with pytest.raises(ValueError, match="block 4"):
            ragged_paged_attention(q, kp, vp, ss, nt, kvl, tab, block=4,
                                   window=16)
        with pytest.raises(ValueError, match="block 3"):
            ragged_paged_attention(q, kp, vp, ss, nt, kvl, tab, block=3)
