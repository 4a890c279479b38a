"""EvaByte (chunk-summary / EVA attention) through `ServingEngine`.

The engine — chunked prefill, then decode through the cache, ACROSS a
window's close and a page boundary, unlike sequences in one launch, a
slot reused after a finish — against the plain float32 reference's full
forward (`benchmarks/lib/reference_evabyte.py`) on seeded weights; the
model's own forward (all byte heads) against the same reference; the
allocator's two page lists; the pooling kernel against `jax.numpy`; the
ragged kernel's summary mask against its oracle. (The step programs'
pinned texts: `test_step_program_pins.py`, which builds its toy here.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import reference_evabyte as ref
from benchmarks.systems.evabyte_serving import model_layers
from paddle_tpu import resilience
from paddle_tpu.models.evabyte import (EvaByteForCausalLM,
                                       evabyte_tiny_config)
from paddle_tpu.ops.fused import (append_slot_run_table, fused_append_rows,
                                  fused_chunk_pool)
from paddle_tpu.ops.pallas_ragged import (ragged_attention_reference,
                                          ragged_paged_attention)
from paddle_tpu.ops.references import chunk_pool_reference
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.block_allocator import ChunkSummaryAllocator
from test_engine_programs import _spy_append_runs


# --------------------------------------------------------------- model
@pytest.fixture(scope="module")
def tiny():
    """A seeded toy EvaByte (window 16 = 4 chunks of 4, 3 byte heads)
    whose every mechanism carries signal, its reference weights and the
    reference's configuration."""
    paddle.seed(0)
    cfg = evabyte_tiny_config()
    m = EvaByteForCausalLM(cfg)
    m.eval()
    rng = np.random.default_rng(0)
    for n, p in m.named_parameters():
        if "layernorm" in n or n.endswith("norm.weight"):
            p._data = jnp.asarray(rng.normal(0, 0.3, p._data.shape),
                                  jnp.float32)
        if "q_proj" in n:
            p._data = p._data * 4
    w = {"embed": m.model.embed_tokens.weight._data,
         "norm": m.model.norm.weight._data, "head": m.lm_head.weight._data,
         "layers": model_layers(m)}
    c = {k: getattr(cfg, k) for k in (
        "num_attention_heads", "hidden_size", "rms_norm_eps", "window_size",
        "chunk_size", "rope_theta", "num_pred_heads", "vocab_size")}
    return m, w, c


def _engine(m, **kw):
    args = dict(max_slots=3, page_size=8, max_context=256, prefill_chunk=8,
                num_pages=64)
    args.update(kw)
    return ServingEngine(m, **args)


def _reference_head0(w, c, prompt, tokens):
    """Head 0's float32 logits at the positions the tokens were
    generated from, teacher-forced over prompt + tokens."""
    n0, n1 = len(prompt), len(tokens)
    ids = np.zeros(-(-(n0 + n1) // 16) * 16, np.int32)
    ids[:n0 + n1] = np.concatenate([prompt, tokens])
    full = np.asarray(ref.logits(jnp.asarray(ids), w, c))
    return full[n0 - 1:n0 - 1 + n1, 0]


class TestModelForward:
    def test_all_heads_match_the_reference(self, tiny):
        m, w, c = tiny
        ids = np.random.default_rng(1).integers(0, 64, 80).astype(np.int32)
        got = np.asarray(m(paddle.to_tensor(ids[None]))._data)[0]
        want = np.asarray(ref.logits(jnp.asarray(ids), w, c))
        assert got.shape == want.shape == (80, 3, 64)
        np.testing.assert_allclose(got, want, atol=2e-5)

    def test_blocks_are_for_memory_only(self, tiny):
        _, w, c = tiny
        ids = jnp.asarray(np.random.default_rng(2).integers(0, 64, 64),
                          jnp.int32)
        np.testing.assert_allclose(
            np.asarray(ref.logits(ids, w, c, q_block=8, head_block=1,
                                  ffn_block=32)),
            np.asarray(ref.logits(ids, w, c)), atol=2e-5)

    @pytest.mark.parametrize("fault", ref.ABLATIONS)
    def test_every_planted_fault_moves_the_logits(self, tiny, fault):
        _, w, c = tiny
        ids = jnp.asarray(np.random.default_rng(3).integers(0, 64, 64),
                          jnp.int32)
        want = np.asarray(ref.logits(ids, w, c))
        got = np.asarray(ref.logits(ids, w, c, ablate=frozenset([fault])))
        past = np.abs(got - want)[16:].max()   # beyond the first window
        assert past > (1e-3 if fault == "fp32_skip_add" else 0.3), past
        if fault in ("summaries", "phi", "mu", "tumbling"):
            # the first window is plain causal attention
            np.testing.assert_allclose(got[:16], want[:16], atol=2e-5)


class TestEngineAgainstReference:
    def test_prefill_decode_across_closes_unlike_sequences_slot_reuse(
            self, tiny):
        """Prompts of 37, 5 and 70 (chunks of 8 over pages of 8) then 30
        tokens each: every sequence crosses closes at multiples of 16 in
        prefill and in decode, the three ride one launch, and a fourth
        request takes the slot and the pages the short one leaves."""
        m, w, c = tiny
        eng = _engine(m)
        rows = {}
        eng.on_logits = lambda req, row: rows.setdefault(
            req.request_id, []).append(np.asarray(row))
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, 64, n).astype(np.int32)
                   for n in (37, 5, 70, 21)]
        new = (30, 6, 30, 12)
        reqs = [eng.add_request(p, max_new_tokens=k)
                for p, k in zip(prompts, new)]
        slots = {}
        while eng.has_work():
            eng.step()
            for r in reqs:
                if r.slot is not None:
                    slots.setdefault(r.request_id, r.slot)
        assert slots[reqs[3].request_id] == slots[reqs[1].request_id]
        for p, r, k in zip(prompts, reqs, new):
            got = np.stack(rows[r.request_id])
            assert got.shape == (k, 64) and len(r.tokens) == k
            want = _reference_head0(w, c, p, np.asarray(r.tokens, np.int32))
            np.testing.assert_allclose(got, want, atol=5e-5)
            np.testing.assert_array_equal(got.argmax(-1), r.tokens)
        assert eng.program_cache_sizes() == {
            "unified": 1, "feed": 1, "unified_nochunk": 1, "feed_nochunk": 1}
        assert eng.allocator.free_pages == eng.allocator.available_pages \
            == eng.num_pages - 1

    def test_the_step_record_counts_the_two_lists(self, tiny):
        from paddle_tpu.observability import tracing
        m, _, _ = tiny
        eng = _engine(m)
        eng.add_request(np.arange(40, dtype=np.int32) % 64,
                        max_new_tokens=10)
        launches = _spy_append_runs(eng)
        while eng.has_work():
            eng.step()
        recs = tracing.recorder().steps()[-eng.steps:]
        for k in tracing.STEP_COUNTS_EVA:
            assert all(k in r for r in recs), k
        # both work lists of the step's appends, counted on the host by
        # the rule the device makes them by: the rows' and — a chunk of
        # 8 closes two pooling chunks of 4, neighbours in a summary page
        # (one run), a decode row one at most — the pooled rows'
        for counts, on_device, pooled in launches:
            assert counts["append_runs"] == on_device
            assert counts["pool_append_runs"] == pooled \
                <= counts["summaries_written"] <= 2 * pooled
        assert sum(r["pool_append_runs"] for r in recs) \
            == sum(p for _, _, p in launches) == 5 + 2
        # 49 rows cached (the tenth token is never fed): 12 whole chunks
        # pooled, closes at 16, 32 and 48
        assert sum(r["summaries_written"] for r in recs) == 12
        assert sum(r["windows_closed"] for r in recs) == 3
        assert sum(r["window_pages_freed"] for r in recs) == 6
        assert {r["cache_row_bytes"] for r in recs if r["decode_rows"]
                or r["prefill_rows"]} == {2 * 2 * 32 * 4}
        last = [r for r in recs if r["decode_rows"]][-1]
        # the query at position 48: 3 closed windows x 4 pooled rows and
        # its own row, the first of the fourth window
        assert (last["summary_rows_live"], last["window_rows_live"]) \
            == (12, 1)

    def test_refusals_are_loud(self, tiny):
        m, _, _ = tiny
        for kw in (dict(enable_prefix_cache=True), dict(spec_decode=2),
                   dict(role="prefill"), dict(prefill_chunk=6)):
            with pytest.raises(ValueError):
                _engine(m, **kw)
        eng = _engine(m)
        assert eng.prefix_cache is None and not eng.preemption \
            and not eng.prefix_sharing
        req = eng.add_request(np.arange(9, dtype=np.int32),
                              max_new_tokens=4)
        eng.step()
        with pytest.raises(NotImplementedError, match="chunk-summary"):
            eng.export_request(req)
        with pytest.raises(NotImplementedError, match="chunk-summary"):
            eng.import_request(None)
        with pytest.raises(ValueError, match="whole pooling chunks"):
            eng.reconfigure(prefill_chunk=6)
        with pytest.raises(NotImplementedError, match="EvaByte"):
            from paddle_tpu.generation import generate_cached
            generate_cached(m, paddle.to_tensor(
                np.arange(4, dtype=np.int32)[None]), max_new_tokens=2)

    def test_admission_waits_on_pages(self, tiny):
        """Three slots but pages for two: the third request waits until
        a finish returns BOTH lists' pages, then runs exactly."""
        m, w, c = tiny
        eng = _engine(m, num_pages=9)      # 8 usable: 2 x (2 + 2)
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 64, n).astype(np.int32)
                   for n in (50, 52, 40)]
        rows = {}
        eng.on_logits = lambda req, row: rows.setdefault(
            req.request_id, []).append(np.asarray(row))
        reqs = [eng.add_request(p, max_new_tokens=8) for p in prompts]
        eng.step()
        assert reqs[2].slot is None and eng.scheduler.inflight == 2
        while eng.has_work():
            eng.step()
        got = np.stack(rows[reqs[2].request_id])
        want = _reference_head0(w, c, prompts[2],
                                np.asarray(reqs[2].tokens, np.int32))
        np.testing.assert_allclose(got, want, atol=5e-5)


# ----------------------------------------------------------- allocator
class TestTwoLists:
    def _alloc(self, pages=40):
        # page 8, window 16 (2 pages), chunk 4: 4 pooled rows a window
        return ChunkSummaryAllocator(pages, 8, 16, 4, 128)

    @pytest.mark.parametrize("total,need", [
        (5, 1 + 1), (16, 2 + 1), (33, 2 + 2), (128, 2 + 4)])
    def test_reservation_is_window_pages_plus_summary_pages(self, total,
                                                            need):
        a = self._alloc()
        assert a.pages_needed(total) == need
        a.allocate("s", total)
        assert a.available_pages == 39 - need and a.free_pages == 39

    def test_window_pages_back_at_the_close_summaries_to_the_end(self):
        a = self._alloc()
        a.allocate("s", 50)
        a.extend("s", 7)
        assert a.release_window("s") == 0          # not at a close
        a.extend("s", 9)                           # length 16: the close
        table, pooled, kv = a.attention_view("s")
        assert (pooled, kv) == (0, 16)             # its own queries: exact
        window_pages = list(table[:2])
        assert a.pages_by_list() == (1, 2) and a.free_pages == 36
        assert a.release_window("s") == 2          # both, together
        assert a.pages_by_list() == (1, 0) and a.free_pages == 38
        assert a.available_pages == 39 - 4         # owed again: 2 + 1
        a.extend("s", 1)
        table, pooled, kv = a.attention_view("s")
        # 4 pooled rows in page 0 of the table (rows 4-7 a hole), then
        # the new window's one row
        assert (pooled, kv) == (4, 8 + 1)
        assert table[0] not in window_pages and table[1] in window_pages
        a.extend("s", 15)
        a.release_window("s")
        a.extend("s", 3)
        table, pooled, kv = a.attention_view("s")
        assert (pooled, kv) == (8, 8 + 3)
        a.free("s")                                # both lists
        assert a.free_pages == a.available_pages == 39
        assert not a._total and a._reserved_total == 0

    def test_a_launch_may_not_straddle_or_skip_a_release(self):
        a = self._alloc()
        a.allocate("s", 50)
        with pytest.raises(ValueError, match="straddle"):
            a.extend("s", 17)
        a.extend("s", 16)
        with pytest.raises(RuntimeError, match="not released"):
            a.extend("s", 1)
        a.release_window("s")
        a.extend("s", 16)
        a.release_window("s")
        a.extend("s", 16)
        a.release_window("s")
        with pytest.raises(ValueError, match="overflows"):
            a.extend("s", 3)

    def test_closing_chunks_name_source_and_destination(self):
        a = self._alloc()
        a.allocate("s", 64)
        a.extend("s", 16)
        got = a.closing_chunks("s", 5, 11)         # tokens 5..15
        wp, sp = a._seqs["s"].wpages, a._seqs["s"].pages
        np.testing.assert_array_equal(got, [
            [wp[0], 1, sp[0], 1], [wp[1], 0, sp[0], 2],
            [wp[1], 1, sp[0], 3]])
        assert a.closing_chunks("s", 4, 3).shape == (0, 4)

    def test_nothing_is_shared_moved_or_rolled_back(self):
        a = self._alloc()
        a.allocate("s", 20)
        for call in (lambda: a.fork("s", "t", 4, 20),
                     lambda: a.adopt("t", [1], 8, 20),
                     lambda: a.export_seq("s"),
                     lambda: a.import_seq("t", 4, 20),
                     lambda: a.shrink("s", 1)):
            with pytest.raises(NotImplementedError):
                call()

    def test_a_full_pool_refuses_at_admission(self):
        a = self._alloc(pages=6)                   # 5 usable
        a.allocate("s", 33)                        # 4
        with pytest.raises(resilience.Overloaded):
            a.allocate("t", 16)                    # 3 > 1
        assert not a.has_seq("t") and a.available_pages == 1


class TestQueuedLaunch:
    def test_pages_freed_at_a_close_are_rewritten_only_by_a_later_launch(
            self, tiny):
        """Two sequences in a pool so small that the pages one window
        returns are the next ones handed out, with a launch queued
        ahead throughout: the logits stay the reference's, so no launch
        read a page after a later owner wrote it."""
        m, w, c = tiny
        eng = _engine(m, max_slots=2, num_pages=9)
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, 64, n).astype(np.int32) for n in (30, 9)]
        rows, ahead, reused = {}, [], set()
        eng.on_logits = lambda req, row: rows.setdefault(
            req.request_id, []).append(np.asarray(row))
        reqs = [eng.add_request(p, max_new_tokens=26) for p in prompts]
        freed = set()
        while eng.has_work():
            before = set(eng.allocator._free)
            eng.step()
            ahead.append(eng._inflight is not None)
            now = set(eng.allocator._free)
            reused |= (before & freed) - now
            freed |= now - before
        assert sum(ahead) >= len(ahead) - 3 and reused
        for p, r in zip(prompts, reqs):
            np.testing.assert_allclose(
                np.stack(rows[r.request_id]),
                _reference_head0(w, c, p, np.asarray(r.tokens, np.int32)),
                atol=5e-5)


# ------------------------------------------------------------- kernels
class TestChunkPool:
    @pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-6),
                                            (jnp.bfloat16, 2e-2)])
    def test_matches_jnp(self, dtype, atol):
        rng = np.random.default_rng(7)
        KV, total, psz, D, c = 2, 6, 8, 32, 4
        kp, vp = (jnp.asarray(rng.normal(size=(KV, total, psz, D)), dtype)
                  for _ in range(2))
        phi, mu = (jnp.asarray(rng.normal(size=(KV, D)), dtype)
                   for _ in range(2))
        pg = jnp.asarray([1, 1, 3, 0, 5], jnp.int32)
        ck = jnp.asarray([0, 1, 1, 0, 0], jnp.int32)
        got = fused_chunk_pool(kp, vp, phi, mu, pg, ck, chunk=c, scale=0.3)
        want = chunk_pool_reference(kp, vp, phi, mu, pg, ck, chunk=c,
                                    scale=0.3)
        for g, x in zip(got, want):
            assert g.shape == (5, KV, D) and g.dtype == dtype
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(x, np.float32), atol=atol)

    def test_pooled_rows_land_where_attention_reads_them(self):
        rng = np.random.default_rng(8)
        KV, total, psz, D, c = 2, 6, 8, 32, 4
        kp, vp = (jnp.asarray(rng.normal(size=(KV, total, psz, D)),
                              jnp.float32) for _ in range(2))
        phi = jnp.zeros((KV, D))                   # mean pooling
        kt, _ = fused_chunk_pool(kp, vp, phi, phi, jnp.asarray([2]),
                                 jnp.asarray([1]), chunk=c, scale=1.0)
        np.testing.assert_allclose(kt[0], kp[:, 2, 4:8].mean(1), atol=1e-6)
        # a pooling slot whose summary page is the trash page is idle
        runs = append_slot_run_table(jnp.asarray([0, 4]), jnp.asarray([0, 5]),
                                     tile=8, max_runs=2)
        rows = jnp.concatenate([jnp.full_like(kt, 7.0), kt])
        out, same = fused_append_rows((kp, vp), (rows, vp[:, 0, :2].swapaxes(
            0, 1)), runs)
        np.testing.assert_allclose(out[:, 4, 5], kt[0], atol=0)
        keep = np.ones(kp.shape[1:3], bool)
        keep[4, 5] = False
        np.testing.assert_array_equal(np.asarray(out)[:, keep],
                                      np.asarray(kp)[:, keep])
        np.testing.assert_array_equal(np.asarray(same)[:, keep],
                                      np.asarray(vp)[:, keep])

    def test_a_page_is_whole_chunks(self):
        z = jnp.zeros((1, 2, 8, 32))
        with pytest.raises(ValueError, match="whole chunks"):
            fused_chunk_pool(z, z, z[0, 0, :1], z[0, 0, :1],
                             jnp.zeros(1, jnp.int32),
                             jnp.zeros(1, jnp.int32), chunk=3, scale=1.0)


class TestSummaryMask:
    """`ragged_paged_attention(summary_rows=)`: a sequence's KV starts
    with pooled rows, all visible; the tail of their last page is a hole;
    exact rows follow from the next page, causal."""

    def _case(self, rng, dtype=jnp.float32):
        KV, H, D, ps, total, T = 2, 4, 32, 8, 40, 24
        kp, vp = (jnp.asarray(rng.normal(size=(KV, total, ps, D)), dtype)
                  for _ in range(2))
        q = jnp.asarray(rng.normal(size=(T, H, D)), dtype)
        ss = jnp.asarray([0, 1, 2, 3], jnp.int32)
        nt = jnp.asarray([1, 1, 0, 12], jnp.int32)
        # 12 pooled rows (2 pages, rows 12-15 a hole) + 5 exact; none +
        # 7; idle; 8 pooled (a whole page, no hole) + 13 exact
        kvl = jnp.asarray([16 + 5, 7, 0, 8 + 13], jnp.int32)
        sr = jnp.asarray([12, 0, 0, 8], jnp.int32)
        tab = jnp.asarray(rng.permutation(np.arange(1, total))[:24]
                          .reshape(4, 6), jnp.int32)
        return q, kp, vp, ss, nt, kvl, tab, sr

    @pytest.mark.parametrize("seed", [0, 1])
    def test_kernel_matches_oracle(self, seed, monkeypatch):
        *args, sr = self._case(np.random.default_rng(seed))
        got = ragged_paged_attention(*args, summary_rows=sr)
        want = ragged_attention_reference(*args, summary_rows=sr)
        np.testing.assert_allclose(got, want, atol=2e-5)
        if seed:
            # a page visit served both KV heads: one head a visit gives
            # the same bits
            from paddle_tpu.ops import pallas_ragged
            monkeypatch.setattr(pallas_ragged, "ragged_head_block",
                                lambda *a, **k: 1)
            pallas_ragged._launch_jit.clear_cache()     # trace it again
            np.testing.assert_array_equal(
                got, ragged_paged_attention(*args, summary_rows=sr))
            pallas_ragged._launch_jit.clear_cache()
        # and the hole matters: without the mask the answer differs
        plain = ragged_attention_reference(*args)
        assert np.abs(np.asarray(plain - want))[0].max() > 1e-2
        # a sequence with no pooled rows is untouched by the argument
        np.testing.assert_allclose(want[1], plain[1], atol=1e-6)

    def test_pooled_rows_are_seen_by_every_query_of_the_chunk(self):
        """The oracle itself against the definition: query t of the
        12-row chunk sees the 8 pooled rows and exact rows 0..t + 1."""
        q, kp, vp, ss, nt, kvl, tab, sr = self._case(
            np.random.default_rng(2))
        got = np.asarray(ragged_attention_reference(
            q, kp, vp, ss, nt, kvl, tab, summary_rows=sr))
        rows_k = np.asarray(kp)[:, np.asarray(tab[3])].reshape(2, -1, 32)
        rows_v = np.asarray(vp)[:, np.asarray(tab[3])].reshape(2, -1, 32)
        for t in (0, 5, 11):
            keep = np.r_[0:8, 8:8 + 13 - 12 + t + 1]
            for h in range(4):
                s = rows_k[h // 2, keep] @ np.asarray(q)[3 + t, h] \
                    * 32 ** -0.5
                p = np.exp(s - s.max())
                np.testing.assert_allclose(
                    got[3 + t, h], p @ rows_v[h // 2, keep] / p.sum(),
                    atol=2e-5)


def _pin_engine():
    """The toy engine whose step programs `test_step_program_pins` pins
    (an unseeded toy: a program's text reads shapes, not values)."""
    paddle.seed(0)
    m = EvaByteForCausalLM(evabyte_tiny_config())
    m.eval()
    return ServingEngine(m, max_slots=2, page_size=8, max_context=64,
                         prefill_chunk=8)


def test_the_eva_step_takes_the_nine_inputs(tiny):
    """What `benchmarks/tests` lower it with: three operands are pairs."""
    m, _, _ = tiny
    eng = _engine(m)
    B, C = eng.max_slots, eng.prefill_chunk
    P = B + C // 4

    def i32(*d):
        return jax.ShapeDtypeStruct(d, jnp.int32)

    low = eng._jit_unified.lower(
        eng._w, i32(B + C), eng._pools, i32(B + C), i32(B + 1),
        (i32(B + 1), i32(B + 1)), i32(B + 1, eng.pages_per_seq),
        (i32(B + C), i32(2, P)), (i32(B + C), i32(2, P)))
    logits, pools, tokens = low.out_info
    assert logits.shape == (B + 1, 64) and logits.dtype == jnp.float32
    assert tokens.shape == (B + 1,)
    text = low.as_text(debug_info=True)
    for here in ("eva_pool", "eva_attention", "fused_chunk_pool",
                 "fused_rope_append", "ragged_paged_attention"):
        assert here in text, here
