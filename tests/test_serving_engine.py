"""Continuous-batching ServingEngine: exact-match vs solo
generate_cached under seeded join/leave traces (llama, gpt, mla),
compile-once decode (no retrace per join/leave), prefix-sharing
exactness, and the Config-driven deadline/backpressure paths."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import resilience as res
from paddle_tpu.generation import generate_cached
from paddle_tpu.inference import Config
from paddle_tpu.serving import ServingEngine


def _solo(model, prompt, max_new):
    out, _ = generate_cached(model, paddle.to_tensor(prompt[None]),
                             max_new_tokens=max_new,
                             decode_strategy="greedy_search")
    return out.numpy()[0]


def _trace(V, n, seed, smin=2, smax=11, mmin=2, mmax=7):
    """Seeded request trace: (prompt, max_new, submit_at_step)."""
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, V, rng.randint(smin, smax)).astype(np.int32),
             int(rng.randint(mmin, mmax)), int(rng.randint(0, 4)))
            for _ in range(n)]


def _run_trace(model, V, n, seed, **engine_kw):
    """Drive a seeded join/leave trace; return ({rid: result},
    {rid: solo_reference}, engine)."""
    trace = _trace(V, n, seed)
    eng = ServingEngine(model, **engine_kw)
    ref, pending = {}, list(enumerate(trace))
    results, step = {}, 0
    while pending or eng.has_work():
        still = []
        for i, (prompt, max_new, at) in pending:
            if at <= step:
                eng.add_request(prompt, max_new_tokens=max_new,
                                request_id=i)
                ref[i] = _solo(model, prompt, max_new)
            else:
                still.append((i, (prompt, max_new, at)))
        pending = still
        eng.step()
        results.update(eng.collect())
        step += 1
    return results, ref, eng


class TestExactMatch:
    """Acceptance: every request's engine output equals its solo
    generate_cached greedy output, with requests joining and leaving
    mid-decode."""

    def test_llama_seeded_trace(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        paddle.seed(0)
        c = llama_tiny_config(num_hidden_layers=2)
        m = LlamaForCausalLM(c)
        m.eval()
        results, ref, eng = _run_trace(m, c.vocab_size, 5, seed=1,
                                       max_slots=2, page_size=4,
                                       prefill_chunk=4)
        assert set(results) == set(ref)
        for rid in ref:
            np.testing.assert_array_equal(results[rid], ref[rid])
        # no retrace per join/leave: every program compiled exactly once
        assert all(v == 1 for v in eng.program_cache_sizes().values())

    def test_gpt_seeded_trace(self):
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny_config
        paddle.seed(0)
        c = gpt_tiny_config(max_position_embeddings=64)
        m = GPTForCausalLM(c)
        m.eval()
        results, ref, eng = _run_trace(m, c.vocab_size, 4, seed=2,
                                       max_slots=2, page_size=4,
                                       prefill_chunk=4)
        for rid in ref:
            np.testing.assert_array_equal(results[rid], ref[rid])
        assert all(v == 1 for v in eng.program_cache_sizes().values())

    def test_mla_seeded_trace(self):
        from paddle_tpu.models.deepseek import (DeepSeekV2ForCausalLM,
                                                deepseek_v2_tiny_config)
        paddle.seed(0)
        c = deepseek_v2_tiny_config(moe_dropless=True, num_hidden_layers=2)
        m = DeepSeekV2ForCausalLM(c)
        m.eval()
        results, ref, eng = _run_trace(m, c.vocab_size, 4, seed=3,
                                       max_slots=2, page_size=4,
                                       prefill_chunk=4)
        for rid in ref:
            np.testing.assert_array_equal(results[rid], ref[rid])
        assert all(v == 1 for v in eng.program_cache_sizes().values())

    def test_moe_seeded_trace(self):
        from paddle_tpu.models.moe_llm import (MoEForCausalLM,
                                               qwen2_moe_tiny_config)
        paddle.seed(0)
        c = qwen2_moe_tiny_config(moe_dropless=True,
                                  first_k_dense_replace=1,
                                  max_position_embeddings=64)
        m = MoEForCausalLM(c)
        m.eval()
        results, ref, eng = _run_trace(m, c.vocab_size, 4, seed=4,
                                       max_slots=2, page_size=4,
                                       prefill_chunk=4)
        for rid in ref:
            np.testing.assert_array_equal(results[rid], ref[rid])
        assert all(v == 1 for v in eng.program_cache_sizes().values())

    def test_trace_deterministic_across_runs(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        paddle.seed(0)
        c = llama_tiny_config(num_hidden_layers=1)
        m = LlamaForCausalLM(c)
        m.eval()
        r1, _, _ = _run_trace(m, c.vocab_size, 4, seed=9, max_slots=2,
                              page_size=4, prefill_chunk=4)
        r2, _, _ = _run_trace(m, c.vocab_size, 4, seed=9, max_slots=2,
                              page_size=4, prefill_chunk=4)
        assert set(r1) == set(r2)
        for rid in r1:
            np.testing.assert_array_equal(r1[rid], r2[rid])


class TestEngineSemantics:
    @pytest.fixture(scope="class")
    def model(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        paddle.seed(0)
        m = LlamaForCausalLM(llama_tiny_config(num_hidden_layers=1))
        m.eval()
        return m

    def test_eos_stops_and_pads(self, model):
        V = model.config.vocab_size
        rng = np.random.RandomState(5)
        prompt = rng.randint(0, V, 5).astype(np.int32)
        first = _solo(model, prompt, 1)
        eos = int(first[0])
        eng = ServingEngine(model, max_slots=2, page_size=4,
                            prefill_chunk=4)
        r = eng.add_request(prompt, max_new_tokens=5, eos_token_id=eos)
        out = eng.run_to_completion()[r.request_id]
        assert out[0] == eos
        np.testing.assert_array_equal(out[1:], 0)

    def test_prefix_sharing_exact(self, model):
        # same long prefix, different tails: the fork rides the donor's
        # pages (COW) and every stream still exact-matches its solo run
        V = model.config.vocab_size
        rng = np.random.RandomState(6)
        base = rng.randint(0, V, 10).astype(np.int32)
        p1 = base.copy()
        p2 = np.concatenate([base[:8], rng.randint(0, V, 3)
                             .astype(np.int32)])
        eng = ServingEngine(model, max_slots=2, page_size=4,
                            prefill_chunk=4, prefix_sharing=True)
        r1 = eng.add_request(p1, max_new_tokens=4)
        eng.step()            # admit + start prefill of r1
        r2 = eng.add_request(p2, max_new_tokens=4)
        out = eng.run_to_completion()
        np.testing.assert_array_equal(out[r1.request_id],
                                      _solo(model, p1, 4))
        np.testing.assert_array_equal(out[r2.request_id],
                                      _solo(model, p2, 4))
        assert r2.shared_tokens > 0
        assert all(v == 1 for v in eng.program_cache_sizes().values())

    def test_backpressure_overloaded_at_door(self, model):
        cfg = Config()
        cfg.set_admission(1, queue_timeout_s=0.0)
        eng = ServingEngine(model, max_slots=2, page_size=4,
                            prefill_chunk=4, config=cfg)
        V = model.config.vocab_size
        p = np.arange(4, dtype=np.int32) % V
        eng.add_request(p, max_new_tokens=3)
        with pytest.raises(res.Overloaded):
            eng.add_request(p, max_new_tokens=3)

    def test_queue_timeout_expires_waiting(self, model):
        cfg = Config()
        cfg.set_admission(1, queue_timeout_s=0.02)
        eng = ServingEngine(model, max_slots=2, page_size=4,
                            prefill_chunk=4, config=cfg)
        V = model.config.vocab_size
        p = np.arange(4, dtype=np.int32) % V
        r1 = eng.add_request(p, max_new_tokens=8)
        r2 = eng.add_request(p, max_new_tokens=8)   # queues behind r1
        out = eng.run_to_completion()
        assert isinstance(out[r2.request_id], res.Overloaded)
        assert out[r1.request_id].shape == (8,)

    def test_deadline_partial_result(self, model):
        cfg = Config()
        cfg.set_deadline(1e-6)
        eng = ServingEngine(model, max_slots=2, page_size=4,
                            prefill_chunk=4, config=cfg)
        V = model.config.vocab_size
        p = np.arange(4, dtype=np.int32) % V
        r = eng.add_request(p, max_new_tokens=4)
        out = eng.run_to_completion()[r.request_id]
        assert isinstance(out, res.TimeoutResult) and not out
        assert out.kind == "serving_engine"
        assert out.partial.shape == (4,)

    def test_pool_exhaustion_waits_not_corrupts(self, model):
        # pool sized for ~one sequence: the second request waits for the
        # first to free its pages, then completes exactly
        V = model.config.vocab_size
        rng = np.random.RandomState(8)
        p1 = rng.randint(0, V, 6).astype(np.int32)
        p2 = rng.randint(0, V, 6).astype(np.int32)
        eng = ServingEngine(model, max_slots=2, page_size=4,
                            prefill_chunk=4, num_pages=4,
                            max_context=12, prefix_sharing=False)
        r1 = eng.add_request(p1, max_new_tokens=3)
        r2 = eng.add_request(p2, max_new_tokens=3)
        out = eng.run_to_completion()
        np.testing.assert_array_equal(out[r1.request_id],
                                      _solo(model, p1, 3))
        np.testing.assert_array_equal(out[r2.request_id],
                                      _solo(model, p2, 3))

    def test_context_overflow_rejected(self, model):
        eng = ServingEngine(model, max_slots=1, page_size=4,
                            max_context=8)
        with pytest.raises(ValueError, match="max_context"):
            eng.add_request(np.arange(6, dtype=np.int32),
                            max_new_tokens=6)

    def test_metrics_slice(self, model):
        from paddle_tpu import serving as srv
        V = model.config.vocab_size
        eng = ServingEngine(model, max_slots=2, page_size=4,
                            prefill_chunk=4)
        r = eng.add_request(np.arange(5, dtype=np.int32) % V,
                            max_new_tokens=3)
        eng.run_to_completion()
        m = srv.metrics()
        toks = {s["labels"]["phase"]: s["value"]
                for s in m["serving.engine.tokens"]["series"]}
        assert toks["prefill"] >= 5 and toks["decode"] >= 2
        outcomes = {s["labels"]["outcome"]: s["value"]
                    for s in m["serving.engine.requests"]["series"]}
        assert outcomes.get("completed", 0) >= 1


def _full_trace(V, n, seed):
    """Seeded multi-tenant trace: (prompt, max_new, submit_at, priority,
    tenant). Even requests share a base prefix (exercises the prefix
    cache); the last request gets top priority (exercises preemption
    when slots are busy at its submit step)."""
    rng = np.random.RandomState(seed)
    base = rng.randint(0, V, 8).astype(np.int32)
    out = []
    for i in range(n):
        if i % 2 == 0:
            tail = rng.randint(0, V, rng.randint(2, 5)).astype(np.int32)
            prompt = np.concatenate([base, tail])
        else:
            prompt = rng.randint(0, V, rng.randint(4, 11)).astype(np.int32)
        prio = 5 if i == n - 1 else int(rng.randint(0, 2))
        out.append((prompt, int(rng.randint(3, 7)),
                    int(rng.randint(0, 4)), prio,
                    f"tenant{int(rng.randint(0, 2))}"))
    return out


def _run_full_trace(model, V, n, seed, **engine_kw):
    """Drive a seeded join/leave/preempt trace with prefix cache,
    priority scheduling and speculative decoding ALL enabled."""
    trace = _full_trace(V, n, seed)
    eng = ServingEngine(model, spec_decode=2, **engine_kw)
    ref, pending, results, step = {}, list(enumerate(trace)), {}, 0
    while pending or eng.has_work():
        still = []
        for i, (prompt, max_new, at, prio, tenant) in pending:
            if at <= step:
                eng.add_request(prompt, max_new_tokens=max_new,
                                request_id=i, priority=prio,
                                tenant=tenant)
                ref[i] = _solo(model, prompt, max_new)
            else:
                still.append((i, (prompt, max_new, at, prio, tenant)))
        pending = still
        eng.step()
        results.update(eng.collect())
        step += 1
    return results, ref, eng


class TestAllFeaturesExact:
    """ISSUE 10 acceptance: with prefix cache + priority scheduling +
    speculative decoding ALL enabled, greedy engine output exact-matches
    solo generate_cached for every model family under seeded
    multi-tenant join/leave/preempt traces."""

    def _check(self, model, V, n, seed):
        results, ref, eng = _run_full_trace(
            model, V, n, seed, max_slots=2, page_size=4, prefill_chunk=4)
        assert set(results) == set(ref)
        for rid in ref:
            np.testing.assert_array_equal(results[rid], ref[rid])
        assert all(v == 1 for v in eng.program_cache_sizes().values())
        # fair-share bookkeeping drains to zero with the pool
        assert all(v == 0 for v in eng.scheduler._tenant_tokens.values())
        eng.prefix_cache.flush()
        assert eng.allocator.free_pages == eng.allocator.num_pages - 1

    def test_llama_all_features(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        paddle.seed(0)
        c = llama_tiny_config(num_hidden_layers=2)
        m = LlamaForCausalLM(c)
        m.eval()
        self._check(m, c.vocab_size, 5, seed=31)

    def test_gpt_all_features(self):
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny_config
        paddle.seed(0)
        c = gpt_tiny_config(max_position_embeddings=64)
        m = GPTForCausalLM(c)
        m.eval()
        self._check(m, c.vocab_size, 4, seed=32)

    def test_mla_all_features(self):
        from paddle_tpu.models.deepseek import (DeepSeekV2ForCausalLM,
                                                deepseek_v2_tiny_config)
        paddle.seed(0)
        c = deepseek_v2_tiny_config(moe_dropless=True, num_hidden_layers=2)
        m = DeepSeekV2ForCausalLM(c)
        m.eval()
        self._check(m, c.vocab_size, 4, seed=33)

    def test_moe_all_features(self):
        from paddle_tpu.models.moe_llm import (MoEForCausalLM,
                                               qwen2_moe_tiny_config)
        paddle.seed(0)
        c = qwen2_moe_tiny_config(moe_dropless=True,
                                  first_k_dense_replace=1,
                                  max_position_embeddings=64)
        m = MoEForCausalLM(c)
        m.eval()
        self._check(m, c.vocab_size, 4, seed=34)


class TestPriorityScheduling:
    """Scheduler-level priority / fair-share semantics (no model) and
    the engine's page-intact preemption path."""

    def test_priority_order_fcfs_within_class(self):
        from paddle_tpu.serving.scheduler import Scheduler, Request
        s = Scheduler(max_slots=1)
        lo1 = s.submit(Request([1], 4, priority=0))
        hi = s.submit(Request([1], 4, priority=2))
        lo2 = s.submit(Request([1], 4, priority=0))
        assert s.next_admittable() is hi
        s.admit(hi)
        s.release(hi)
        assert s.next_admittable() is lo1      # FCFS within class
        s.admit(lo1)
        s.release(lo1)
        assert s.next_admittable() is lo2

    def test_defaults_reduce_to_fcfs(self):
        from paddle_tpu.serving.scheduler import Scheduler, Request
        s = Scheduler(max_slots=2)
        reqs = [s.submit(Request([1], 4)) for _ in range(4)]
        order = []
        while s.has_work():
            r = s.next_admittable()
            if r is None:
                for _, a in s.active():
                    s.release(a)
                    order.append(a)
                continue
            s.admit(r)
        for _, a in s.active():
            s.release(a)
            order.append(a)
        assert order == reqs

    def test_tenant_budget_shapes_not_starves(self):
        from paddle_tpu.serving.scheduler import Scheduler, Request
        s = Scheduler(max_slots=4, tenant_budgets={"a": 10})
        a1 = s.submit(Request([1, 2], 4, tenant="a"))   # 6 tokens
        a2 = s.submit(Request([1, 2], 4, tenant="a"))   # would be 12 > 10
        b1 = s.submit(Request([1, 2], 4, tenant="b"))   # no budget: free
        assert s.next_admittable() is a1
        s.admit(a1)
        assert s.next_admittable() is b1       # a2 over budget, b flows
        s.admit(b1)
        assert s.next_admittable() is None
        s.release(a1)                          # budget drains with usage
        assert s.next_admittable() is a2
        s.admit(a2)
        # progress guarantee: a zero-usage tenant admits even a request
        # bigger than its whole budget
        s2 = Scheduler(max_slots=1, tenant_budgets={"c": 2})
        c1 = s2.submit(Request([1, 2, 3], 8, tenant="c"))
        assert s2.next_admittable() is c1

    def test_pick_victim_strictly_lower_youngest(self):
        from paddle_tpu.serving.scheduler import (Scheduler, Request,
                                                  DECODE)
        s = Scheduler(max_slots=3)
        r0 = s.submit(Request([1], 4, priority=0))
        r1 = s.submit(Request([1], 4, priority=0))
        r2 = s.submit(Request([1], 4, priority=1))
        for r in (r0, r1, r2):
            s.admit(r)
            r.state = DECODE
        assert s.pick_victim(2) is r1          # lowest class, youngest
        assert s.pick_victim(1) is r1
        assert s.pick_victim(0) is None        # nothing strictly lower
        r1.state = "prefill"
        assert s.pick_victim(2) is r0          # PREFILL never preempted

    def test_engine_preemption_no_reprefill(self, ):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        from paddle_tpu.serving.scheduler import DECODE
        paddle.seed(0)
        m = LlamaForCausalLM(llama_tiny_config(num_hidden_layers=1))
        m.eval()
        V = m.config.vocab_size
        rng = np.random.RandomState(17)
        p1 = rng.randint(0, V, 6).astype(np.int32)
        p2 = rng.randint(0, V, 5).astype(np.int32)
        # sharing off so prefill-token accounting is exact
        eng = ServingEngine(m, max_slots=1, page_size=4, prefill_chunk=4,
                            prefix_sharing=False,
                            enable_prefix_cache=False)
        r1 = eng.add_request(p1, max_new_tokens=10, priority=0)
        prefill = 0
        while r1.state != DECODE or len(r1.tokens) < 2:
            prefill += eng.step()["prefill_tokens"]
        r2 = eng.add_request(p2, max_new_tokens=3, priority=1)
        results = {}
        while eng.has_work():
            prefill += eng.step()["prefill_tokens"]
            results.update(eng.collect())
        # the high-priority arrival preempted r1 and finished first...
        assert r1.preempted is False and r1.state == "finished"
        np.testing.assert_array_equal(results[r2.request_id],
                                      _solo(m, p2, 3))
        # ...and r1 resumed with pages intact: its output is exact and
        # NO prompt token was ever prefilled twice
        np.testing.assert_array_equal(results[r1.request_id],
                                      _solo(m, p1, 10))
        assert prefill == p1.size + p2.size
        from paddle_tpu import serving as srv
        fam = srv.metrics().get("serving.engine.preemptions")
        assert fam and fam["series"][0]["value"] >= 1

    def test_preemption_off_knob(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        from paddle_tpu.serving.scheduler import DECODE
        paddle.seed(0)
        m = LlamaForCausalLM(llama_tiny_config(num_hidden_layers=1))
        m.eval()
        V = m.config.vocab_size
        rng = np.random.RandomState(18)
        p1 = rng.randint(0, V, 5).astype(np.int32)
        p2 = rng.randint(0, V, 5).astype(np.int32)
        eng = ServingEngine(m, max_slots=1, page_size=4, prefill_chunk=4,
                            preemption=False)
        r1 = eng.add_request(p1, max_new_tokens=6, priority=0)
        while r1.state != DECODE or len(r1.tokens) < 1:
            eng.step()
        r2 = eng.add_request(p2, max_new_tokens=3, priority=9)
        finish_order = []
        while eng.has_work():
            eng.step()
            finish_order.extend(eng.collect().keys())
        assert finish_order == [r1.request_id, r2.request_id]


def _run_fleet_trace(model, V, n, seed, roles, **engine_kw):
    """The `_run_trace` join/leave trace driven through a FleetRouter
    over role-split replicas; returns ({rid: result},
    {rid: solo_reference}, router, {name: engine})."""
    from paddle_tpu.serving import FleetRouter
    trace = _trace(V, n, seed)
    engines = {name: ServingEngine(model, role=role, **engine_kw)
               for name, role in roles.items()}
    router = FleetRouter(engines)
    ref, pending = {}, list(enumerate(trace))
    results, step = {}, 0
    while pending or router.has_work():
        still = []
        for i, (prompt, max_new, at) in pending:
            if at <= step:
                router.submit(prompt, max_new_tokens=max_new,
                              request_id=i)
                ref[i] = _solo(model, prompt, max_new)
            else:
                still.append((i, (prompt, max_new, at)))
        pending = still
        router.step()
        results.update(router.collect())
        step += 1
    return results, ref, router, engines


class TestDisaggregated:
    """Acceptance (ISSUE 15): a request prefilled on replica A and
    decoded on replica B after a KV-page handoff produces BIT-IDENTICAL
    greedy output to the colocated engine — across all four families,
    and with speculative decoding and the prefix cache on."""

    ROLES = {"pf0": "prefill", "dec0": "decode"}

    def _check(self, model, V, n, seed, **kw):
        results, ref, router, engines = _run_fleet_trace(
            model, V, n, seed, self.ROLES, max_slots=2, page_size=4,
            prefill_chunk=4, **kw)
        assert set(results) == set(ref)
        for rid in ref:
            np.testing.assert_array_equal(results[rid], ref[rid])
        # every request crossed the prefill→decode boundary exactly once
        assert router.handoff_count == len(ref)
        # a prefill replica's every launch carries a chunk and a decode
        # replica's none: each ran ONE of the step's two row counts
        for name, eng in engines.items():
            role, sizes = self.ROLES[name], eng.program_cache_sizes()
            assert sizes.pop("unified" if role == "decode"
                             else "unified_nochunk") == 0, (role, sizes)
            assert all(v == 1 for v in sizes.values()), (role, sizes)

    def test_llama_disaggregated_exact(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        paddle.seed(0)
        c = llama_tiny_config(num_hidden_layers=2)
        m = LlamaForCausalLM(c)
        m.eval()
        self._check(m, c.vocab_size, 5, seed=1)

    def test_gpt_disaggregated_exact(self):
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny_config
        paddle.seed(0)
        c = gpt_tiny_config(max_position_embeddings=64)
        m = GPTForCausalLM(c)
        m.eval()
        self._check(m, c.vocab_size, 4, seed=2)

    def test_mla_disaggregated_exact(self):
        from paddle_tpu.models.deepseek import (DeepSeekV2ForCausalLM,
                                                deepseek_v2_tiny_config)
        paddle.seed(0)
        c = deepseek_v2_tiny_config(moe_dropless=True,
                                    num_hidden_layers=2)
        m = DeepSeekV2ForCausalLM(c)
        m.eval()
        self._check(m, c.vocab_size, 4, seed=3)

    def test_moe_disaggregated_exact(self):
        from paddle_tpu.models.moe_llm import (MoEForCausalLM,
                                               qwen2_moe_tiny_config)
        paddle.seed(0)
        c = qwen2_moe_tiny_config(moe_dropless=True,
                                  first_k_dense_replace=1,
                                  max_position_embeddings=64)
        m = MoEForCausalLM(c)
        m.eval()
        self._check(m, c.vocab_size, 4, seed=4)

    def test_llama_disaggregated_spec_decode_exact(self):
        # handoff carries the sampler/spec-decode state: the n-gram
        # drafter on the decode replica sees prompt+tokens exactly as
        # the colocated engine would
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        paddle.seed(0)
        c = llama_tiny_config(num_hidden_layers=2)
        m = LlamaForCausalLM(c)
        m.eval()
        self._check(m, c.vocab_size, 5, seed=5, spec_decode=2)

    def test_decode_role_refuses_fresh_requests(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        paddle.seed(0)
        m = LlamaForCausalLM(llama_tiny_config(num_hidden_layers=1))
        m.eval()
        eng = ServingEngine(m, max_slots=2, page_size=4, role="decode")
        with pytest.raises(ValueError, match="decode-role"):
            eng.add_request(np.arange(4, dtype=np.int32), 2)
        with pytest.raises(ValueError):
            ServingEngine(m, max_slots=2, page_size=4, role="bogus")

    def test_export_shape_and_import_guards(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        paddle.seed(0)
        c = llama_tiny_config(num_hidden_layers=1)
        m = LlamaForCausalLM(c)
        m.eval()
        pf = ServingEngine(m, max_slots=2, page_size=4, role="prefill")
        prompt = np.arange(1, 7, dtype=np.int32)
        pf.add_request(prompt, max_new_tokens=4, request_id="r")
        while not pf.handoff_ready:
            pf.step()
        req = pf.handoff_ready[0]
        handoff = pf.export_request(req)
        # KV-length invariant right after prefill: length == prompt
        # tokens, one emitted token staged as pending
        assert handoff.kv_length == prompt.size
        assert handoff.tokens == [handoff.pending]
        assert handoff.n_pages == 2 and handoff.page_size == 4
        assert handoff.payload_bytes > 0
        # a prefill-role replica refuses imports
        with pytest.raises(ValueError, match="prefill"):
            pf.import_request(handoff)
        # geometry mismatch refused before any mutation
        other = ServingEngine(m, max_slots=2, page_size=8)
        with pytest.raises(ValueError, match="page_size"):
            other.import_request(handoff)
        assert not other.allocator.has_seq("r")
        handoff.release()
        assert pf.allocator.free_pages == pf.allocator.available_pages


class TestFleetLocality:
    """Acceptance (ISSUE 15): with 2+ replicas and a 16-tenant shared-
    system-prompt trace, >= 90% of warm-tenant requests land on the
    replica already holding their prefix, and the fleet-wide
    prefill-skip rate stays within 2 points of a single replica's."""

    def _warm_trace(self, V, n_tenants=16, sys_len=8, tail_len=4,
                    ext_len=4):
        rng = np.random.RandomState(7)
        system = rng.randint(0, V, sys_len).astype(np.int32)
        cold, warm = [], []
        for _ in range(n_tenants):
            tail = rng.randint(0, V, tail_len).astype(np.int32)
            ext = rng.randint(0, V, ext_len).astype(np.int32)
            cold.append(np.concatenate([system, tail]))
            # the warm request extends the tenant's own prior prompt
            # (multi-turn), so its full cold prompt is matchable
            warm.append(np.concatenate([system, tail, ext]))
        return cold, warm

    def _drive(self, submit, run, cold, warm):
        skipped = prompt_toks = 0
        for t, p in enumerate(cold):
            submit(p, f"cold{t}", f"t{t}")
        run()
        reqs = [submit(p, f"warm{t}", f"t{t}")
                for t, p in enumerate(warm)]
        run()
        for p, r in zip(warm, reqs):
            skipped += r.shared_tokens
            prompt_toks += p.size
        return skipped / prompt_toks

    def test_warm_tenants_route_to_prefix_holder(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        from paddle_tpu.serving import FleetRouter
        paddle.seed(0)
        c = llama_tiny_config(num_hidden_layers=1)
        m = LlamaForCausalLM(c)
        m.eval()
        V = c.vocab_size
        cold, warm = self._warm_trace(V)
        kw = dict(max_slots=2, page_size=4, prefill_chunk=4)
        engines = {"a": ServingEngine(m, **kw),
                   "b": ServingEngine(m, **kw)}
        router = FleetRouter(engines)
        for t, p in enumerate(cold):
            router.submit(p, 3, request_id=f"cold{t}", tenant=f"t{t}")
        router.run_to_completion()
        # the cold round spread tenants over both replicas
        homes = {}
        for t, p in enumerate(warm):
            hits = {n: e.prefix_cache.match_length(p)
                    for n, e in engines.items()}
            homes[t] = max(hits, key=lambda n: (hits[n], n))
        assert len(set(homes.values())) == 2
        on_home = 0
        fleet_skip = prompt_toks = 0
        for t, p in enumerate(warm):
            r = router.submit(p, 3, request_id=f"warm{t}",
                              tenant=f"t{t}")
            if router.place_of(f"warm{t}") == homes[t]:
                on_home += 1
            router.run_to_completion()
            fleet_skip += r.shared_tokens
            prompt_toks += p.size
        assert on_home >= 0.9 * len(warm), (on_home, homes)
        fleet_rate = fleet_skip / prompt_toks

        # same trace on ONE colocated replica
        solo = ServingEngine(m, **kw)

        def submit(p, rid, tenant):
            return solo.add_request(p, 3, request_id=rid, tenant=tenant)
        solo_rate = self._drive(submit, solo.run_to_completion, cold,
                                warm)
        assert abs(fleet_rate - solo_rate) <= 0.02, (fleet_rate,
                                                     solo_rate)
