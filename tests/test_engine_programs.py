"""`ServingEngine` has ONE step program and runs ONE layer chain: no
constructor argument selects an implementation; a shape for which
`_ragged_step_eligible` says the kernel does not tile is refused by name
(a test answers for that one function); what is lowered calls neither
fused half and reads the model's own projections; and the names
`benchmarks/` reads of the engine are there."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.generation import (_decode_params, _kvb_heads, _mm_heads,
                                   generate_cached)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as engine_mod
from test_serving_engine import _run_trace


def _tiny(family):
    """A seeded toy model of one of the engine's families."""
    paddle.seed(0)
    if family == "gpt":
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny_config
        m = GPTForCausalLM(gpt_tiny_config(max_position_embeddings=64))
    elif family in ("mla", "mla_no_q_lora"):
        from paddle_tpu.models.deepseek import (DeepSeekV2ForCausalLM,
                                                deepseek_v2_tiny_config)
        kw = dict(q_lora_rank=None) if family == "mla_no_q_lora" else {}
        m = DeepSeekV2ForCausalLM(deepseek_v2_tiny_config(
            moe_dropless=True, num_hidden_layers=2, **kw))
    elif family == "moe":
        from paddle_tpu.models.moe_llm import (MoEForCausalLM,
                                               qwen2_moe_tiny_config)
        m = MoEForCausalLM(qwen2_moe_tiny_config(
            moe_dropless=True, first_k_dense_replace=1,
            max_position_embeddings=64))
    else:
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        m = LlamaForCausalLM(llama_tiny_config(num_hidden_layers=2))
    m.eval()
    return m


def _quantized_trace_exact(m, seed, quant, n=3):
    """Engine greedy tokens under weight-only `quant` equal the solo
    quantized run's, request by request."""
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, m.config.vocab_size, rng.randint(3, 9))
               .astype(np.int32) for _ in range(n)]
    eng = ServingEngine(m, max_slots=2, page_size=4, prefill_chunk=4,
                        weight_only_quant=quant)
    assert eng.ragged
    for i, p in enumerate(prompts):
        eng.add_request(p, max_new_tokens=4, request_id=i)
    out = eng.run_to_completion()
    for i, p in enumerate(prompts):
        want, _ = generate_cached(m, paddle.to_tensor(p[None]),
                                  max_new_tokens=4,
                                  decode_strategy="greedy_search",
                                  weight_only_quant=quant)
        np.testing.assert_array_equal(out[i], want.numpy()[0])


class TestRaggedPath:
    """The engine has one step program, the unified ragged step
    (`_ragged_step_eligible` is asked once at construction; no
    constructor argument): a shape the kernel does not tile is refused
    by name, a draft length is never dropped, and the quantized
    families run on the one chain."""

    ARGS = dict(max_slots=2, page_size=4, prefill_chunk=4)

    @pytest.fixture(scope="class")
    def model(self):
        return _tiny("llama")

    @pytest.mark.parametrize("family", ["llama", "gpt", "mla", "moe"])
    def test_an_untileable_shape_is_refused(self, family, monkeypatch):
        # what a chip answers for a head width that is not 64 or a
        # multiple of 128: the test steers the one function the engine
        # consults, not an option of the program
        m = _tiny(family)
        monkeypatch.setattr(engine_mod, "_ragged_step_eligible",
                            lambda *a: False)
        cfg = m.config
        if family == "gpt":
            kv, d = cfg.num_attention_heads, cfg.head_dim
        elif family == "mla":   # one latent row: toy ranks are not padded
            kv, d = 1, cfg.kv_lora_rank + cfg.qk_rope_head_dim
        else:
            kv, d = cfg.num_key_value_heads, cfg.head_dim
        with pytest.raises(ValueError) as e:
            ServingEngine(m, **self.ARGS)
        said = str(e.value)
        assert "unified ragged step only" in said
        for part in (f"heads [{cfg.num_attention_heads}]",
                     f"{kv} KV heads", f"width {d}", "pages of 4"):
            assert part in said, (part, said)

    def test_a_nonzero_spec_decode_is_kept_or_refused(self, model,
                                                      monkeypatch):
        # whatever the kernel answers and whatever the family: a
        # constructor that returns has the draft length it was given
        models = (model, _laguna())
        for eligible in (True, False):
            monkeypatch.setattr(engine_mod, "_ragged_step_eligible",
                                lambda *a, _e=eligible: _e)
            for m in models:
                try:
                    eng = ServingEngine(m, spec_decode=2, **self.ARGS)
                except ValueError:
                    continue
                assert eng.spec_k == 2

    @pytest.fixture(scope="class")
    def traced_run(self, model):
        """ONE engine built and run to completion for the cases that
        read what it left behind: the set-up ledger as it stood after
        the run, and where the run began on the spans' clock."""
        import time
        from paddle_tpu.observability import tracing
        t0 = time.perf_counter_ns()
        _, _, eng = _run_trace(model, model.config.vocab_size, 3, seed=10,
                               **self.ARGS)
        ledger = tracing.recorder().setup()
        return {"eng": eng, "t0": t0,
                "spans": [sp for sp in ledger["spans"]
                          if sp["start_ns"] >= t0],
                "programs": [r for r in ledger["programs"]
                             if r["start_ns"] >= t0]}

    def test_launches_metric_series(self, traced_run):
        from paddle_tpu import serving as srv
        m = srv.metrics()
        paths = {s["labels"]["path"]: s["value"]
                 for s in m["serving.engine.launches"]["series"]}
        assert paths.get("unified", 0) >= 1
        assert set(paths) == {"unified"}

    def test_the_constructor_is_sections_of_the_set_up_ledger(
            self, traced_run):
        # ISSUE 68: `serving.engine.construct` over the constructor, its
        # sections disjoint children that cover it
        name = "serving.engine.construct"
        (whole,) = [sp for sp in traced_run["spans"] if sp["name"] == name]
        assert whole["parent"] is None and whole["step"] is None
        kids = [sp for sp in traced_run["spans"] if sp["parent"] == name]
        assert [sp["name"][len(name):] for sp in kids] == [
            ".weights", ".layout", ".pools", ".accounting", ".programs"]
        edges = [whole["start_ns"]] + [t for sp in kids for t in (
            sp["start_ns"], sp["end_ns"])] + [whole["end_ns"]]
        assert edges == sorted(edges)       # in order, disjoint, inside
        covered = sum(sp["end_ns"] - sp["start_ns"] for sp in kids)
        assert covered >= 0.95 * (whole["end_ns"] - whole["start_ns"])
        # what the constructor compiled says which section it was under
        under = {r["span"] for r in traced_run["programs"]
                 if whole["start_ns"] <= r["start_ns"] < whole["end_ns"]}
        assert name + ".programs" in under
        assert under <= {sp["name"] for sp in kids}

    def test_a_first_launch_is_found_from_its_program_record(
            self, traced_run):
        # no span and no test on the hot path: the record of a step
        # program says the launch phase and the step's seq, and that
        # step is in the ledger with its phases
        recs = [r for r in traced_run["programs"] if r["step"] is not None]
        assert recs and {r["span"] for r in recs} == {
            "serving.engine.launch"}
        firsts = [r for r in recs if r["cache"] is not None]
        steps = sorted({r["step"] for r in firsts})
        # the program with the chunk's rows, then the decode rows alone
        assert len(steps) == 2 and steps[0] < steps[1]
        copied = {sp["step"]: sp for sp in traced_run["spans"]
                  if sp["step"] is not None}
        assert sorted(copied) == steps
        for r in firsts:
            st = copied[r["step"]]
            assert st["name"] == "serving.engine.step"
            (launch,) = [ph for ph in st["phases"]
                         if ph[0] == "serving.engine.launch"]
            assert launch[1] <= r["start_ns"] < r["end_ns"] <= launch[2]
        # every launch after them compiled nothing
        assert traced_run["eng"].steps > steps[1]
        assert all(n == 1 for n in
                   traced_run["eng"].program_cache_sizes().values())

    @pytest.mark.parametrize("switch", ["ragged", "megafront",
                                        "megadecode"])
    def test_no_implementation_switch_on_the_constructor(self, model,
                                                         switch):
        # a removed argument is refused, not ignored
        with pytest.raises(TypeError, match=switch):
            ServingEngine(model, **{switch: False}, **self.ARGS)

    @pytest.mark.parametrize("quant", ["int8", "int4"])
    def test_llama_quantized_seeded_trace(self, model, quant):
        _quantized_trace_exact(model, 35, quant, n=2)

    def test_moe_int4_seeded_trace(self):
        # int4 end to end INCLUDING the 3-D packed expert stacks
        _quantized_trace_exact(_tiny("moe"), 22, "int4")

    def test_mla_no_q_lora_seeded_trace(self):
        # the one-stage q projection (`wq`, no `wqa` / `wqb`)
        m = _tiny("mla_no_q_lora")
        results, ref, eng = _run_trace(m, m.config.vocab_size, 3, seed=34,
                                       **self.ARGS)
        assert eng.ragged and eng.front_half_launches == 5
        for rid in ref:
            np.testing.assert_array_equal(results[rid], ref[rid])

    def test_mla_int4_seeded_trace(self):
        # VERDICT item 6 tail: packed-int4 absorbed projections inside
        # the engine's MLA body exact-match the int4 solo run
        _quantized_trace_exact(_tiny("mla"), 12, "int4")


def _laguna():
    from paddle_tpu.models.laguna import (LagunaForCausalLM,
                                          laguna_tiny_config)
    paddle.seed(0)
    m = LagunaForCausalLM(laguna_tiny_config(experts_held=(4, 4)))
    m.eval()
    return m


def _lower_unified(eng):
    """Lower the unified step from shapes, with the nine positional
    arguments `benchmarks/tests` pass it: (w, tok, pools, positions,
    num_tokens, kv_lengths, tables, tok_page, tok_off)."""
    B, C = eng.max_slots, eng.prefill_chunk

    def i32(*d):
        return jax.ShapeDtypeStruct(d, jnp.int32)

    table, page = i32(B + 1, eng.pages_per_seq), i32(B + C)
    if eng.num_window_pages:    # a table and a page column a layer kind
        table, page = (table, table), (page, page)
    return eng._jit_unified.lower(
        eng._w, i32(B + C), eng._pools, i32(B + C), i32(B + 1), i32(B + 1),
        table, page, i32(B + C))


@functools.lru_cache(maxsize=None)
def _lowered_toy(family):
    """(the model, its engine at the pins' sizes, its unified step
    lowered from shapes), made once a process for the tests that only
    READ them: nothing steps this engine."""
    m = _laguna() if family == "laguna" else _tiny(family)
    eng = ServingEngine(m, max_slots=2, page_size=8, max_context=64,
                        prefill_chunk=8)
    return m, eng, _lower_unified(eng)


def _spy_append_runs(eng):
    """Wrap `eng._build_unified`: beside the counts every launch's step
    record takes on the host, the live runs of the work lists the jitted
    step makes of the same row tables on the device -> the list, one
    (counts, the rows' live runs, the pooling slots' or None) a launch."""
    from paddle_tpu.serving.engine import _seq_starts
    seen, build = [], eng._build_unified
    run_table = jax.jit(eng._run_table(
        _seq_starts(eng.max_slots, 1 + eng.spec_k)))
    slot_table = jax.jit(eng._slot_run_table)

    def live_runs(table):
        return int((np.asarray(table).reshape(5, -1)[1] > 0).sum())

    def spy(*a):
        out = build(*a)
        _, _, num_tokens, _, _, tok_page, tok_off = out[0]
        pooled = None
        if eng._eva:
            (tok_page, pool_page), (tok_off, pool_off) = tok_page, tok_off
            pooled = live_runs(slot_table(pool_page[1], pool_off[1]))
        seen.append((out[-1], live_runs(run_table(
            num_tokens, tok_page, tok_off)), pooled))
        return out

    eng._build_unified = spy
    return seen


#: family -> (the model's own projections of layer 0, the append its
#: front half ends in)
CHAIN = {
    "llama": ({"wq", "wk", "wv", "wo", "wg", "wu", "wd"},
              "fused_rope_append"),
    "moe": ({"wq", "wk", "wv", "wo"}, "fused_rope_append"),
    "mla": ({"wqa", "wqb", "wkva", "wkvb", "wo"}, "fused_append_rows"),
    "gpt": ({"wqkv", "wo", "wi", "wf"}, "fused_rope_append"),
    "laguna": ({"wq", "wk", "wv", "wgate", "wo"}, "fused_rope_append"),
}


class TestOneChain:
    """By what was lowered: every family's unified step is norm ->
    projections -> rope/append -> ragged attention -> o-proj -> norm ->
    FFN, over the model's own weight tree."""

    @pytest.mark.parametrize("family", sorted(CHAIN))
    def test_lowered_step_calls_no_fused_half(self, family):
        _, eng, lowered = _lowered_toy(family)
        assert eng.ragged
        own, append = CHAIN[family]
        keys = set(eng._w["layers"][0])
        assert own <= keys, sorted(keys)
        # GPT's checkpoint ships `wqkv`; nobody else has a slab
        assert ("wqkv" in keys) == (family == "gpt")
        assert "wqkva" not in keys
        # locations name the python functions the step was traced through
        text = lowered.as_text(debug_info=True)
        for gone in ("fused_qkv_rope_append", "fused_oproj_norm",
                     "fused_ffn", "pallas_megafront", "pallas_megadecode"):
            assert gone not in text, gone
        for here in (append, "ragged_paged_attention"):
            assert here in text, here
        # launches before attention: GPT's one qkv dot, MLA's q-lora pair
        assert eng.front_half_launches == {"gpt": 3, "mla": 7}.get(family, 5)


def _family_model(family):
    """`_tiny` / `_laguna`, and a seeded toy of the four later families."""
    if family == "laguna":
        return _laguna()
    if family not in ("eva", "looped", "hybrid", "bailing"):
        return _tiny(family)
    from paddle_tpu.models import bailing_hybrid, evabyte, nemotron_h, ouro
    paddle.seed(0)
    m = {"eva": lambda: evabyte.EvaByteForCausalLM(
            evabyte.evabyte_tiny_config()),
         "looped": lambda: ouro.OuroForCausalLM(ouro.ouro_tiny_config()),
         "hybrid": lambda: nemotron_h.NemotronHForCausalLM(
            nemotron_h.nemotron_h_tiny_config()),
         "bailing": lambda: bailing_hybrid.BailingHybridForCausalLM(
            bailing_hybrid.bailing_hybrid_tiny_config())}[family]()
    m.eval()
    return m


#: the leaves whose output is split into heads for a kernel, and of
#: those the ones `_decode_params` stores with their columns reordered
#: (a partial or an interleaved rope): the rest are the module's own
HEAD_SPLIT = ("wq", "wk", "wv", "wqb", "wkvb")
REORDERED = {"laguna": ("wq", "wk"), "bailing": ("wq",)}


class TestHeadSplitWeightsStoredAsRead:
    """ISSUE 48: a float q / k / v (latent: q_b or the one-stage q, and
    kv_b) leaf of every family's decode tree is stored [heads, head_dim,
    in] — the module's parameter transposed, once, at load — and
    `_mm_heads` contracts its last axis: `h @ W`. A quantized pair keeps
    [K, N]. `generate` and `ServingEngine` build the tree through the
    same `_decode_params` (the families' engine-against-`generate_cached`
    and engine-against-reference tests hold them to the same logits)."""

    @pytest.mark.parametrize("family", [
        "llama", "moe", "mla", "mla_no_q_lora", "laguna", "eva", "looped",
        "hybrid", "bailing"])
    def test_stored_form_applied_equals_h_at_w(self, family):
        m = _family_model(family)
        p = _decode_params(m)
        own = [np.asarray(prm._data) for _, prm in m.named_parameters()
               if prm._data.ndim == 2]
        rng = np.random.RandomState(0)
        seen = set()
        for i, L in enumerate(p["layers"]):
            # a hybrid names its KDA mixer's in-projections `wq` too:
            # their output feeds a convolution, not heads, and they stay
            kda = "conv_w" in L and "wq" in L
            for key in HEAD_SPLIT:
                if key not in L:
                    continue
                stored = np.asarray(L[key])
                if kda:
                    assert any(stored.shape == w.shape
                               and np.array_equal(stored, w) for w in own)
                    continue
                seen.add(key)
                assert stored.ndim == 3
                # [in, out], as the parent stored it
                w = stored.reshape(-1, stored.shape[2]).T
                if key not in REORDERED.get(family, ()):
                    assert any(w.shape == o.shape and np.array_equal(w, o)
                               for o in own), (i, key)
                if key == "wkvb":
                    # read for the absorbed form as [heads, dn + dv, r]:
                    # the leaf itself, head a's columns of the parameter
                    # as its rows
                    nh = m.config.num_attention_heads
                    np.testing.assert_array_equal(
                        np.asarray(_kvb_heads(L, nh, L[key].dtype)),
                        w.reshape(w.shape[0], nh, -1).transpose(1, 2, 0))
                # the same contraction: equal bit for bit where the
                # arithmetic is exact (whole numbers: no order of
                # summation shows), and to float32's rounding on the
                # parameter itself (the CPU sums a transposed operand
                # in another order)
                h = jnp.asarray(rng.randint(-4, 5, (1, 5, w.shape[0])),
                                L[key].dtype)
                whole = {key: jnp.round(L[key] * 64)}
                np.testing.assert_array_equal(
                    np.asarray(_mm_heads(h, whole, key)),
                    np.asarray(h @ jnp.round(jnp.asarray(w) * 64)))
                h = jnp.asarray(rng.randn(1, 5, w.shape[0]), L[key].dtype)
                np.testing.assert_allclose(
                    np.asarray(_mm_heads(h, L, key)),
                    np.asarray(h @ jnp.asarray(w)), rtol=1e-5, atol=1e-6)
        want = {"mla": {"wqb", "wkvb"}, "mla_no_q_lora": {"wq", "wkvb"},
                "bailing": {"wq", "wkvb"}}.get(family, {"wq", "wk", "wv"})
        assert seen == want
        # the engine reads the same tree
        eng = ServingEngine(m, max_slots=2, page_size=8, max_context=64,
                            prefill_chunk=8)
        for L, Le in zip(p["layers"], eng._w["layers"]):
            for key in HEAD_SPLIT:
                if key in L:
                    np.testing.assert_array_equal(np.asarray(L[key]),
                                                  np.asarray(Le[key]))

    @pytest.mark.parametrize("family,quant", [
        ("llama", "int8"), ("llama", "int4"), ("moe", "int8"),
        ("mla", "int8"), ("mla", "int4")])
    def test_quantized_leaves_keep_k_n(self, family, quant):
        m = _family_model(family)
        fp, q = _decode_params(m), _decode_params(m, weight_only_quant=quant)
        sfx, rows = ("_q4", 2) if quant == "int4" else ("_q", 1)
        n = 0
        for Lf, Lq in zip(fp["layers"], q["layers"]):
            for key in HEAD_SPLIT:
                if key not in Lf:
                    continue
                heads, hd, k = Lf[key].shape    # the float leaf
                out = heads * hd
                assert key not in Lq
                assert Lq[key + sfx].shape == (k // rows, out)
                assert Lq[key + "_s"].shape == (out,)
                h = jnp.ones((1, 3, k), jnp.float32)
                assert _mm_heads(h, Lq, key).shape == (1, 3, out)
                n += 1
        assert n == {"mla": 2}.get(family, 3) * len(fp["layers"])


class TestTheNamesTheBenchmarkReads:
    """`benchmarks/systems/*_serving.py` print `eng.ragged / .megafront /
    .megadecode / .front_half_launches / .back_half_launches` in every
    run's `paths` line, and `benchmarks/tests` lower `_jit_unified` from
    `_w`, `_pools`, `_p` (ROADMAP D11 / D12 retire both)."""

    @pytest.mark.parametrize("family", ["llama", "laguna"])
    def test_the_names_the_benchmark_reads(self, family):
        m, eng, lowered = _lowered_toy(family)
        assert eng.ragged is True
        assert eng.megafront is False and eng.megadecode is False
        assert (eng.front_half_launches, eng.back_half_launches) == (5, 6)
        assert eng.spec_k == 0 and eng.on_logits is None
        assert bool(eng.num_window_pages) == (family == "laguna")
        assert len(eng._attn_static) == len(eng._p["layers"]) \
            == len(eng._w["layers"]) == len(eng._pools)
        logits, pools, *_ = lowered.out_info
        assert logits.shape == (eng.max_slots + 1, m.config.vocab_size)
        assert jax.tree.structure(pools) == jax.tree.structure(eng._pools)
