"""Laguna through `ServingEngine` against the plain reference
(`benchmarks/lib/reference_laguna.py`, imported, not copied), at a toy
size with every mechanism on: 2 full + 6 sliding layers, 6 / 8 query
heads over 2 KV heads, a window shorter than the prompts, partial rotary
with yarn on the full layers, the head gate, a dense layer 0, 16 experts
top-4 of which 4 are held, routed scale 2.5.  Float32 weights under
`default_matmul_precision("highest")` (conftest), kernels in interpret
mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import reference_laguna as ref
from benchmarks.systems.laguna_serving import model_layers, reference_config
from paddle_tpu import resilience
from paddle_tpu.generation import _ffn_apply, _mlp_params
from paddle_tpu.incubate.moe import (MoELayer, dense_expert_ffn,
                                     dropless_expert_ffn)
from paddle_tpu.models.laguna import LagunaForCausalLM, laguna_tiny_config
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.block_allocator import PageBlockAllocator
from paddle_tpu.serving.scheduler import DECODE

#: Engine logits against the float32 reference's, both in float32 at the
#: highest matmul precision: what is left is the order of float32 sums
#: (paged online softmax against a full one, grouped GEMM against a loop
#: over experts) through 8 layers.  Measured here: 2e-6 (logits of
#: magnitude ~0.5).  The negative controls move logits by 1e-2 or more.
ATOL = 2e-5


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LagunaForCausalLM(laguna_tiny_config(experts_held=(4, 4)))
    m.eval()
    return m


def _weights(m):
    return {"embed": m.model.embed_tokens.weight._data,
            "norm": m.model.norm.weight._data,
            "head": m.lm_head.weight._data, "layers": model_layers(m)}


def _serve(m, prompts, max_new, **engine):
    """Run the prompts through an engine; returns (requests, the logits
    row that produced each of a request's tokens, the engine)."""
    args = dict(max_slots=3, page_size=8, max_context=256, prefill_chunk=16,
                num_pages=70)
    args.update(engine)
    eng = ServingEngine(m, **args)
    rows = {}
    eng.on_logits = lambda req, row: rows.setdefault(
        req.request_id, []).append(row.copy())
    reqs = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
    return reqs, rows, eng


def _prompts(m, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, m.config.vocab_size, n, dtype=np.int32)
            for n in lens]


def _reference_rows(m, prompt, tokens, **kw):
    ids = jnp.asarray(np.concatenate([prompt, tokens]).astype(np.int32))
    logits = np.asarray(ref.logits(ids, _weights(m),
                                   reference_config(m.config), **kw))
    return logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]


@pytest.fixture(scope="module")
def served(model):
    """One run on the engine's one chain, the one the chip runs: prompts
    past the window (24), past several chunks (16) and pages
    (8), one shorter than the window; (prompts, requests, logits rows,
    engine, the run's step records)."""
    from paddle_tpu.observability import tracing
    prompts = _prompts(model, (70, 20, 41))
    reqs, rows, eng = _serve(model, prompts, 6)
    eng.run_to_completion()
    return prompts, reqs, rows, eng, \
        list(tracing.recorder().steps()[-eng.steps:])


class TestEngineAgainstReference:
    def test_prefill_in_chunks_then_decode_matches_in_logits(
            self, model, served):
        prompts, reqs, rows, eng, _ = served
        assert eng.ragged and eng._window == 24
        assert eng.program_cache_sizes() == {
            "unified": 1, "feed": 1, "unified_nochunk": 1, "feed_nochunk": 1}
        for r, p in zip(reqs, prompts):
            got = np.stack(rows[r.request_id])
            want = _reference_rows(model, p, np.asarray(r.tokens))
            np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
            # blocked attention is the same reference
            blocked = _reference_rows(model, p, np.asarray(r.tokens),
                                      q_block=16, head_block=1)
            np.testing.assert_allclose(blocked, want, atol=ATOL, rtol=0)
        st = eng.allocator.stats()
        assert st["pages_used"] == 0 and st["window_pages_used"] == 0

    @pytest.mark.parametrize("ablate", ["window", "gate", "scale"])
    def test_negative_controls_lie_outside_the_tolerance(self, model,
                                                         served, ablate):
        """The reference with one mechanism off is NOT what the engine
        computes: by 500 x the tolerance at least."""
        prompts, reqs, rows, _, _ = served
        got = np.stack(rows[reqs[0].request_id])
        off = _reference_rows(model, prompts[0], np.asarray(reqs[0].tokens),
                              ablate=frozenset([ablate]))
        assert np.abs(got - off).max() > 500 * ATOL

    def test_step_counts_of_the_two_kinds_and_of_the_routed_layers(
            self, model, served):
        _, _, _, eng, records = served
        recs = [r for r in records
                if r["prefill_rows"] or r["decode_rows"]]
        assert recs
        k = model.config.num_experts_per_tok
        sparse = 7
        for r in recs:
            # the rows that requests own, not the flat buffer's padding
            live = r["prefill_rows"] + r["decode_rows"]
            assert live < eng.max_slots + eng.prefill_chunk
            assert r["moe_pairs_routed"] == live * k * sparse
            assert 0 < r["moe_pairs_held"] < r["moe_pairs_routed"]
            assert r["moe_expert_rows_max"] >= r["moe_expert_rows_mean"] > 0
            assert 0 < r["moe_experts_hit"] <= 4 * sparse
            assert r["pages_live"] == r["pages_live.full"] \
                + r["pages_live.window"]
            assert r["pages_visited"] == r["pages_visited.full"] \
                + r["pages_visited.window"]
            # both kinds' visits bring a page for all KV heads at once
            acct = eng.hbm_accounting()
            kv = eng._kv_geom[0]
            assert acct["attn_head_block"] == kv \
                == acct["attn_head_block.window"]
            assert r["attn_block_visits"] == r["pages_visited"]
            # a decode row's visits, of either kind, run on a few rows
            assert acct["attn_narrow_rows"] > 0 \
                and acct["attn_narrow_rows.window"] > 0
            assert r["attn_narrow_updates"] <= r["pages_visited"]
            if not r["prefill_rows"]:
                assert r["attn_narrow_updates"] == r["pages_visited"]
            assert r["pages_live.window"] <= r["pages_live.full"]
            assert r["pool_pages_total.full"] == eng.num_pages - 1
            assert r["pool_pages_total.window"] == eng.num_window_pages - 1
            assert r["pool_pages_used"] == r["pool_pages_used.full"] \
                + r["pool_pages_used.window"]
        # the 70-token prompt outran the window: pages went back
        assert sum(r["window_pages_freed"] for r in recs) > 0
        assert max(r["pool_pages_used.window"] for r in recs) < \
            max(r["pool_pages_used.full"] for r in recs)

    def test_preemption_and_resume_keep_the_window_pages(self, model):
        """A low-priority decode is preempted with both kinds of pages
        intact and resumes without re-prefill: its logits still match
        the reference."""
        p1, p2 = _prompts(model, (50, 30), seed=3)
        eng = ServingEngine(model, max_slots=1, page_size=8,
                            max_context=256, prefill_chunk=16,
                            num_pages=40)
        r1 = eng.add_request(p1, max_new_tokens=8, priority=0)
        prefill = 0
        while r1.state != DECODE or len(r1.tokens) < 3:
            prefill += eng.step()["prefill_tokens"]
        r2 = eng.add_request(p2, max_new_tokens=3, priority=1)
        while eng.has_work():
            prefill += eng.step()["prefill_tokens"]
        assert prefill == p1.size + p2.size          # nothing re-prefilled
        for r, p in ((r1, p1), (r2, p2)):
            want = _reference_rows(model, p, np.asarray(r.tokens))
            np.testing.assert_array_equal(np.asarray(r.tokens),
                                          want.argmax(-1))
        assert eng.allocator.stats()["window_pages_used"] == 0


class TestWhatAWindowedModelRefuses:
    @pytest.mark.parametrize("kw,word", [
        (dict(), "unified ragged step"),
        (dict(enable_prefix_cache=True), "enable_prefix_cache"),
        (dict(spec_decode=2), "spec_decode"),
        (dict(role="prefill"), "export_request")])
    def test_construction_names_the_reason(self, model, kw, word,
                                           monkeypatch):
        if not kw:      # where the ragged kernel does not tile
            from paddle_tpu.serving import engine as engine_mod
            monkeypatch.setattr(engine_mod, "_ragged_step_eligible",
                                lambda *a: False)
        with pytest.raises(ValueError, match=word):
            ServingEngine(model, max_slots=2, page_size=8, max_context=64,
                          **kw)

    def test_handoff_split_paths_and_cached_generate(self, model, served):
        eng = served[3]
        assert eng.prefix_cache is None and not eng.prefix_sharing
        r = eng.add_request(_prompts(model, (9,))[0], max_new_tokens=4)
        while len(r.tokens) < 1:
            eng.step()
        with pytest.raises(NotImplementedError, match="sliding-window"):
            eng.export_request(r)
        with pytest.raises(ValueError, match="only shrink"):
            eng.reconfigure(prefill_chunk=eng.prefill_chunk * 2)
        from paddle_tpu.generation import generate_cached
        with pytest.raises(NotImplementedError, match="ServingEngine"):
            generate_cached(model, paddle.to_tensor(np.zeros((1, 4), "int32")),
                            max_new_tokens=2)

    def test_the_window_pool_follows_from_slots_and_chunk(self, model):
        """No option sizes it: every slot and one more sequence at
        ceil((window - 1 + chunk) / page) + 1 pages, plus the trash
        page; a plain model has none."""
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        m = LlamaForCausalLM(llama_tiny_config(num_hidden_layers=1))
        assert ServingEngine(m, max_slots=2, page_size=8).num_window_pages \
            == 0
        eng = ServingEngine(model, max_slots=2, page_size=8, max_context=64,
                            prefill_chunk=16)
        cap = -(-(model.config.sliding_window - 1 + 16) // 8) + 1
        assert eng.num_window_pages == 3 * cap + 1
        assert eng.allocator.window_pages == eng.num_window_pages


# ---------------------------------------------------------------- the share
def _moe_layer(held=None, scale=2.5, seed=0):
    paddle.seed(seed)
    return MoELayer(32, 16, 16, top_k=4, dropless=True, renormalize=True,
                    shared_expert_hidden=16, experts_held=held,
                    routed_scale=scale)


class TestExpertShare:
    @pytest.mark.parametrize("T", [8, 40], ids=["dense_path", "grouped_path"])
    def test_shares_add_up_to_the_uncut_layer(self, T):
        """model-configs guide, section 4: over all shares the routed
        parts, with what every chip computes alike (the shared expert)
        counted once, equal the uncut layer — here also the reference's
        loop over experts."""
        whole = _moe_layer()

        class Lyr:
            mlp = whole
        Lw, st = _mlp_params(Lyr)
        assert st == dict(top_k=4, renorm=True, held=None, scale=2.5)
        x = jnp.asarray(np.random.default_rng(1).normal(size=(1, T, 32)),
                        jnp.float32)
        full = _ffn_apply(Lw, x, st)
        sh = Lw["moe"]["shared"]
        shared = (jax.nn.silu(x @ sh["sg"]) * (x @ sh["su"])) @ sh["sd"]
        total = jnp.zeros_like(full)
        for first in range(0, 16, 4):
            mo = dict(Lw["moe"])
            for k in ("wge", "wup", "wdn"):
                mo[k] = Lw["moe"][k][first:first + 4]
            total += _ffn_apply(dict(moe=mo), x,
                                dict(st, held=(first, 4))) - shared
        np.testing.assert_allclose(np.asarray(total + shared),
                                   np.asarray(full), atol=2e-6, rtol=0)
        spec = ref.LayerSpec(nq=1, nkv=1, d=1, eps=1e-6, window=None,
                             gate=False, top_k=4, renorm=True, scale=2.5,
                             held=None, q_block=0, head_block=0)
        routed, _ = ref._experts(
            x[0], {"router": Lw["moe"]["gate"], "eg": Lw["moe"]["wge"],
                   "eu": Lw["moe"]["wup"], "ed": Lw["moe"]["wdn"]}, spec)
        np.testing.assert_allclose(np.asarray(routed + shared[0]),
                                   np.asarray(full[0]), atol=2e-6, rtol=0)

    @pytest.mark.parametrize("ffn", [dense_expert_ffn, dropless_expert_ffn])
    def test_held_all_and_scale_one_is_todays_layer_exactly(self, ffn):
        rng = np.random.default_rng(2)
        T, H, I_, E = (8 if ffn is dense_expert_ffn else 48), 32, 16, 8
        xt = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
        gates = jax.nn.softmax(jnp.asarray(rng.normal(size=(T, E)),
                                           jnp.float32))
        wg, wu = (jnp.asarray(rng.normal(size=(E, H, I_)) * 0.2, jnp.float32)
                  for _ in range(2))
        wd = jnp.asarray(rng.normal(size=(E, I_, H)) * 0.2, jnp.float32)
        kw = dict(top_k=2, renormalize=True)
        today, topi = ffn(xt, gates, wg, wu, wd, **kw)
        same, topi2 = ffn(xt, gates, wg, wu, wd, held=(0, E), scale=1.0, **kw)
        np.testing.assert_array_equal(np.asarray(today), np.asarray(same))
        np.testing.assert_array_equal(np.asarray(topi), np.asarray(topi2))
        # the two paths agree on a share as they do on the whole
        a, _ = dense_expert_ffn(xt, gates, wg[2:5], wu[2:5], wd[2:5],
                                held=(2, 3), scale=2.5, **kw)
        b, _ = dropless_expert_ffn(xt, gates, wg[2:5], wu[2:5], wd[2:5],
                                   held=(2, 3), scale=2.5, **kw)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    def test_layer_forward_of_a_share(self):
        whole, part = _moe_layer(), _moe_layer(held=(4, 4))
        assert part.w_up.shape == [4, 32, 16] and \
            part.gate_weight.shape == [32, 16]
        with pytest.raises(NotImplementedError, match="dropless"):
            MoELayer(32, 16, 16, top_k=4, experts_held=(0, 4))
        with pytest.raises(ValueError, match="outside"):
            MoELayer(32, 16, 16, top_k=4, dropless=True,
                     experts_held=(14, 4))
        del whole


# ------------------------------------------------------------ the allocator
class TestWindowPages:
    def _alloc(self, **kw):
        args = dict(num_pages=40, page_size=8, pages_per_seq=32, window=20,
                    window_pages=12, window_span=16)
        args.update(kw)
        return PageBlockAllocator(**args)

    def _check(self, a, sid):
        """Every key a FUTURE query can see is on a live page; nothing
        more than the cap is held; no page is lost."""
        ln, ps = a.seq_length(sid), a.page_size
        table = a.window_table(sid)
        oldest = max(ln - a.window + 1, 0)      # the next query's oldest
        for pos in range(oldest, ln):
            assert table[pos // ps] != 0, (pos, ln)
        held = int((table != 0).sum())
        assert held <= a.window_cap
        assert len(set(table[table != 0])) == held
        return held

    def test_pages_return_as_the_window_passes_under_chunked_prefill(self):
        a = self._alloc()
        assert a.window_cap == -(-(20 - 1 + 16) // 8) + 1 == 6
        a.allocate("s", 200)
        assert a.available_window_pages == 11 - 6
        freed = 0
        for n in [16] * 6 + [1] * 60:           # six chunks, then decode
            assert a.extend("s", n) == []
            # the pages the step just wrote are there
            t, ln = a.window_table("s"), a.seq_length("s")
            assert all(t[p // 8] for p in range(max(ln - n - 19, 0), ln))
            freed += a.release_window("s")
            held = self._check(a, "s")
            assert a.free_window_pages == 11 - held
        assert freed == (156 - 20 + 1) // 8     # all wholly below 137
        full = a.table("s")
        assert (full[:-(-156 // 8)] != 0).all()  # the full kind keeps all
        a.free("s")
        assert a.free_window_pages == 11 and a.available_window_pages == 11
        assert a.free_pages == 39

    def test_admission_reckons_with_both_pools(self):
        a = self._alloc(window_pages=1 + 6 + 3)
        a.allocate("a", 100)
        assert a.can_admit(16) and not a.can_admit(100)
        with pytest.raises(resilience.Overloaded, match="window page pool"):
            a.allocate("b", 100)
        a.allocate("b", 20)                      # 3 pages: a short one fits
        assert a.available_window_pages == 0
        a.free("a")
        a.allocate("c", 100)
        with pytest.raises(NotImplementedError, match="never shared"):
            a.fork("c", "d", 4, 50)
        with pytest.raises(NotImplementedError, match="never shared"):
            a.export_seq("c")

    def test_a_preempted_sequence_keeps_its_window_pages(self):
        a = self._alloc()
        a.allocate("v", 120)
        for n in [16] * 3 + [1] * 5:
            a.extend("v", n)
            a.release_window("v")
        before = a.window_table("v").copy()
        # another sequence comes and goes while "v" waits for a slot
        a.allocate("w", 40)
        a.extend("w", 16)
        a.release_window("w")
        a.free("w")
        np.testing.assert_array_equal(a.window_table("v"), before)
        a.extend("v", 1)
        a.release_window("v")
        self._check(a, "v")

    def test_without_a_window_nothing_changes(self):
        a = PageBlockAllocator(10, 4, 4)
        assert a.window is None and a.window_pages == 0
        a.allocate("s", 8)
        a.extend("s", 8)
        assert a.stats()["window_pages_used"] == 0
        a.free("s")
