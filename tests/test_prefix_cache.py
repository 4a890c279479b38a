"""Global radix prefix cache (serving.prefix_cache): trie insert/
lookup/evict unit behavior over pinned allocator pages, engine-level
multi-tenant prefill skip with exactness, pool-pressure eviction, the
enable_prefix_cache knob, and the no-leaked-pins regression on
admission-refusal / queue-expiry paths."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import resilience as res
from paddle_tpu import serving as srv
from paddle_tpu.generation import generate_cached
from paddle_tpu.inference import Config
from paddle_tpu.serving import PageBlockAllocator, PrefixCache, ServingEngine


def _metric(name):
    fam = srv.metrics().get(name)
    if not fam or not fam["series"]:
        return 0.0
    return fam["series"][0]["value"]


def _solo(model, prompt, max_new):
    out, _ = generate_cached(model, paddle.to_tensor(prompt[None]),
                             max_new_tokens=max_new,
                             decode_strategy="greedy_search")
    return out.numpy()[0]


def _prefill(a, cache, sid, prompt):
    """Simulate engine prefill: allocate, extend to the full prompt,
    insert the full pages into the trie."""
    a.allocate(sid, len(prompt))
    a.extend(sid, len(prompt))
    cache.insert(prompt, a.seq_pages(sid))


class TestTrieUnit:
    def test_insert_lookup_roundtrip_page_granular(self):
        a = PageBlockAllocator(num_pages=17, page_size=4, pages_per_seq=4)
        cache = PrefixCache(a)
        prompt = list(range(100, 111))            # 11 tokens: 2 full pages
        _prefill(a, cache, "s", prompt)
        assert cache.pages == 2                   # 11 // 4
        a.free("s")
        m = cache.lookup(prompt)                  # cap (11-1)//4 = 2
        assert m.tokens == 8 and len(m.pages) == 2
        # an extension matches the same prefix; a divergence stops early
        m2 = cache.lookup(prompt + [7, 7, 7, 7, 7])
        assert m2.tokens == 8
        m3 = cache.lookup([100, 101, 102, 103, 9, 9, 9, 9, 9])
        assert m3.tokens == 4 and m3.pages == m.pages[:1]
        for mm in (m, m2, m3):
            mm.release()
        cache.flush()
        assert a.free_pages == 16

    def test_last_prompt_token_never_matched(self):
        # an exactly-page-aligned prompt still recomputes its last token
        a = PageBlockAllocator(num_pages=9, page_size=4, pages_per_seq=4)
        cache = PrefixCache(a)
        prompt = list(range(8))
        _prefill(a, cache, "s", prompt)
        assert cache.pages == 2
        a.free("s")
        m = cache.lookup(prompt)                  # cap (8-1)//4 = 1
        assert m.tokens == 4 and len(m.pages) == 1
        m.release()
        cache.flush()

    def test_first_writer_wins(self):
        a = PageBlockAllocator(num_pages=17, page_size=4, pages_per_seq=4)
        cache = PrefixCache(a)
        prompt = list(range(8))
        _prefill(a, cache, "s1", prompt)
        m1 = cache.lookup(prompt + [1, 2, 3, 4])
        _prefill(a, cache, "s2", prompt)          # same prefix again
        assert cache.pages == 2                   # nothing re-inserted
        m2 = cache.lookup(prompt + [1, 2, 3, 4])
        assert m2.pages == m1.pages               # s1's physical pages
        m1.release()
        m2.release()
        a.free("s1")
        a.free("s2")
        cache.flush()
        assert a.free_pages == 16

    def test_match_pin_protects_lookup_to_adopt_window(self):
        a = PageBlockAllocator(num_pages=9, page_size=4, pages_per_seq=4)
        cache = PrefixCache(a)
        prompt = list(range(12))
        _prefill(a, cache, "s", prompt)
        a.free("s")
        m = cache.lookup(prompt)
        assert m.tokens == 8
        # a flush between lookup and adopt evicts the trie NODES but the
        # match pin keeps the physical pages alive for the adopter
        cache.flush()
        assert cache.pages == 0
        for pg in m.pages:
            assert a.refcount(pg) >= 1
        a.adopt("c", m.pages, share_tokens=8, total_tokens=12)
        m.release()
        assert a.seq_length("c") == 8
        a.free("c")
        assert a.free_pages == 8

    def test_lru_eviction_order_and_cascade(self):
        a = PageBlockAllocator(num_pages=17, page_size=4, pages_per_seq=4)
        cache = PrefixCache(a)
        pa = list(range(0, 12))                   # chain of 3 pages
        pb = list(range(100, 108))                # separate 2-page chain
        _prefill(a, cache, "a", pa)
        _prefill(a, cache, "b", pb)
        a.free("a")
        a.free("b")
        assert cache.pages == 5
        # touch ALL of A's pages (lookup caps one token short of the
        # prompt, so probe with an extension): A becomes the warmest
        cache.lookup(pa + [1]).release()
        assert cache.evict(1) == 1                # evicts B's cold leaf
        assert cache.match_length(pb) == 4        # B's root page remains
        assert cache.match_length(pa + [1]) == 12
        # cascade: draining the rest walks leaf -> parent -> root child
        assert cache.evict(10) == 4
        assert cache.pages == 0
        assert a.free_pages == 16

    def test_eviction_skips_pages_shared_by_live_sequences(self):
        a = PageBlockAllocator(num_pages=9, page_size=4, pages_per_seq=4)
        cache = PrefixCache(a)
        prompt = list(range(8))
        _prefill(a, cache, "s", prompt)           # "s" still live
        assert cache.evictable_pages() == 0
        assert cache.evict(8) == 0
        assert cache.pages == 2
        a.free("s")
        assert cache.evictable_pages() == 1       # the leaf
        assert cache.evict(8) == 2                # leaf, then its parent
        assert a.free_pages == 8

    def test_metrics_roundtrip(self):
        a = PageBlockAllocator(num_pages=9, page_size=4, pages_per_seq=4)
        cache = PrefixCache(a)
        base = {k: _metric(f"serving.prefix_cache.{k}")
                for k in ("hits", "misses", "evicted_pages",
                          "shared_tokens")}
        prompt = list(range(12))
        cache.lookup(prompt).release()            # miss: trie empty
        _prefill(a, cache, "s", prompt)
        a.free("s")
        m = cache.lookup(prompt)                  # hit: 2 pages
        cache.note_adopted(m.tokens)
        m.release()
        cache.flush()
        assert _metric("serving.prefix_cache.hits") == base["hits"] + 1
        assert _metric("serving.prefix_cache.misses") == base["misses"] + 1
        assert _metric("serving.prefix_cache.evicted_pages") \
            == base["evicted_pages"] + 3
        assert _metric("serving.prefix_cache.shared_tokens") \
            == base["shared_tokens"] + 8
        assert _metric("serving.prefix_cache.pages") == 0


class TestEnginePrefixCache:
    @pytest.fixture(scope="class")
    def model(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        paddle.seed(0)
        m = LlamaForCausalLM(llama_tiny_config(num_hidden_layers=1))
        m.eval()
        return m

    def test_multitenant_shared_system_prompt_skip(self, model):
        # acceptance: 16 tenants, one shared system prompt — >= 80% of
        # prompt tokens skip prefill via the trie, outputs stay exact.
        # prefix_sharing (live-donor fork) is OFF so the cache is the
        # only sharing mechanism under test.
        V = model.config.vocab_size
        rng = np.random.RandomState(42)
        system = rng.randint(0, V, 24).astype(np.int32)   # 6 full pages
        eng = ServingEngine(model, max_slots=2, page_size=4,
                            prefill_chunk=8, prefix_sharing=False)
        shared = hits = 0
        hits0 = _metric("serving.prefix_cache.hits")
        total_prompt = 0
        for t in range(16):
            tail = rng.randint(0, V, 3).astype(np.int32)
            prompt = np.concatenate([system, tail])
            total_prompt += prompt.size
            r = eng.add_request(prompt, max_new_tokens=3,
                                tenant=f"tenant{t}")
            out = eng.run_to_completion()[r.request_id]
            np.testing.assert_array_equal(out, _solo(model, prompt, 3))
            shared += r.shared_tokens
            if r.shared_tokens:
                assert r._share_source == "cache"
        assert shared / total_prompt >= 0.80
        assert shared == 15 * 24                  # all but the first
        assert _metric("serving.prefix_cache.hits") - hits0 >= 15
        assert all(v == 1 for v in eng.program_cache_sizes().values())
        # teardown leaves only trie pins; flush returns the whole pool
        eng.prefix_cache.flush()
        assert eng.allocator.free_pages == eng.allocator.num_pages - 1

    def test_cache_off_knob(self, model):
        V = model.config.vocab_size
        rng = np.random.RandomState(3)
        prompt = rng.randint(0, V, 12).astype(np.int32)
        eng = ServingEngine(model, max_slots=2, page_size=4,
                            prefill_chunk=4, prefix_sharing=False,
                            enable_prefix_cache=False)
        assert eng.prefix_cache is None
        r1 = eng.add_request(prompt, max_new_tokens=3)
        out = eng.run_to_completion()
        r2 = eng.add_request(prompt.copy(), max_new_tokens=3)
        out.update(eng.run_to_completion())
        assert r2.shared_tokens == 0
        np.testing.assert_array_equal(out[r1.request_id],
                                      out[r2.request_id])
        np.testing.assert_array_equal(out[r2.request_id],
                                      _solo(model, prompt, 3))

    @pytest.mark.parametrize("cache", [True, False])
    def test_step_record_counts_the_admissions(self, model, cache):
        # a DENSE engine with the cache on counts what it adopted and
        # evicted in its step records (tracing.STEP_COUNTS_PREFIX), as
        # every family that keeps the cache does; without the cache the
        # records have no such counts
        from paddle_tpu.observability import tracing
        V = model.config.vocab_size
        rng = np.random.RandomState(5)
        system = rng.randint(0, V, 12).astype(np.int32)   # 3 full pages
        eng = ServingEngine(model, max_slots=1, page_size=4,
                            prefill_chunk=4, num_pages=8, max_context=20,
                            prefix_sharing=False, enable_prefix_cache=cache)
        ev0 = _metric("serving.prefix_cache.evicted_pages")
        at = eng.steps
        for _ in range(4):
            prompt = np.concatenate(
                [system, rng.randint(0, V, 6).astype(np.int32)])
            eng.add_request(prompt, max_new_tokens=2)
            eng.run_to_completion()
        recs = tracing.recorder().steps()[-(eng.steps - at):]
        names = tracing.STEP_COUNTS_PREFIX
        assert all((k in r) == cache for r in recs for k in names)
        if cache:
            tokens, pages, evicted = (sum(r[k] for r in recs)
                                      for k in names)
            assert (tokens, pages) == (3 * 12, 3 * 3)
            assert evicted == _metric(
                "serving.prefix_cache.evicted_pages") - ev0 > 0

    def test_config_set_prefix_cache(self, model):
        cfg = Config()
        cfg.set_prefix_cache(False)
        eng = ServingEngine(model, max_slots=1, page_size=4, config=cfg)
        assert eng.prefix_cache is None
        cfg2 = Config()
        cfg2.set_prefix_cache(True)
        eng2 = ServingEngine(model, max_slots=1, page_size=4, config=cfg2)
        assert eng2.prefix_cache is not None

    def test_pool_pressure_evicts_cold_prefixes_exactly(self, model):
        # pool too small to keep every tenant's prefix cached: admission
        # evicts cold trie pages and retries; outputs stay exact
        V = model.config.vocab_size
        rng = np.random.RandomState(11)
        eng = ServingEngine(model, max_slots=1, page_size=4,
                            prefill_chunk=4, num_pages=10,
                            max_context=16, prefix_sharing=False)
        ev0 = _metric("serving.prefix_cache.evicted_pages")
        for i in range(4):
            prompt = rng.randint(0, V, 12).astype(np.int32)
            r = eng.add_request(prompt, max_new_tokens=3)
            out = eng.run_to_completion()[r.request_id]
            np.testing.assert_array_equal(out, _solo(model, prompt, 3))
        assert _metric("serving.prefix_cache.evicted_pages") > ev0
        eng.prefix_cache.flush()
        assert eng.allocator.free_pages == 9

    def test_refusal_paths_release_pins(self, model):
        # regression (ISSUE 10 small fix): pool-exhaustion refusals and
        # queue expiry must release the admission lookup's trie pins —
        # after the trace drains, only trie nodes hold pages and a
        # flush returns the ENTIRE pool to the free list
        V = model.config.vocab_size
        rng = np.random.RandomState(13)
        cfg = Config()
        cfg.set_admission(3, queue_timeout_s=0.05)
        base = rng.randint(0, V, 8).astype(np.int32)
        eng = ServingEngine(model, max_slots=2, page_size=4,
                            prefill_chunk=4, num_pages=7,
                            max_context=16, config=cfg)
        results = {}
        reqs = []
        for i in range(3):
            tail = rng.randint(0, V, 3).astype(np.int32)
            prompt = np.concatenate([base[:8 - i], tail])
            reqs.append(eng.add_request(prompt, max_new_tokens=3))
        results.update(eng.run_to_completion())
        outcomes = [type(results[r.request_id]).__name__ for r in reqs]
        assert not eng.scheduler.has_work()
        a = eng.allocator
        assert a.stats()["sequences"] == 0
        # every live page is held by the trie alone (refcount == pins)
        for pg in range(1, a.num_pages):
            assert a.refcount(pg) == a.pinned(pg), (pg, outcomes)
        eng.prefix_cache.flush()
        assert a.free_pages == a.num_pages - 1
