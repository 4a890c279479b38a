"""A.X-K1 through `ServingEngine` against the plain reference
(`benchmarks/lib/reference_axk1.py`, imported, not copied: the
UNABSORBED equations), at a toy size with every mechanism on: q-lora, a
latent of one whole 128-lane register (so the cache row is stored
padded, 144 -> 256 columns, as 576 -> 640 at the published widths), ONE
shared rope key under 4 heads, yarn with m^2 = 1.63, a dense layer 0,
16 sigmoid-scored experts in 4 groups of which 2 stay, top-4, 4 held,
a shared expert, routed scale 2.5.  Float32 weights under
`default_matmul_precision("highest")` (conftest), kernels in interpret
mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import reference_axk1 as ref
from benchmarks.systems.axk1_serving import (model_kwargs, model_layers,
                                              reader_config)
from paddle_tpu.incubate.moe import MoELayer, _route
from paddle_tpu.models.axk1 import AXK1ForCausalLM, axk1_tiny_config
from paddle_tpu.ops.pallas_ragged import (ragged_attention_reference,
                                          ragged_paged_attention)
from paddle_tpu.serving import ServingEngine
from test_engine_programs import _spy_append_runs

#: Engine logits against the float32 reference's, both in float32 at the
#: highest matmul precision: what is left is the order of float32 sums
#: (absorbed against unabsorbed attention, paged online softmax against
#: a full one, grouped GEMM against a loop over experts) through 3
#: layers.  Measured here: 3e-6 (logits of magnitude ~0.6).  The
#: negative controls move logits by 4e-3 or more.
ATOL = 2e-5

#: what `reference_axk1` reads, in the published names
TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=3, num_attention_heads=4, q_lora_rank=48,
    kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=32, rms_norm_eps=1e-6, rope_theta=10000.0,
    rope_scaling={"type": "yarn", "factor": 16, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 64},
    first_k_dense_replace=1, n_routed_experts=16, num_experts_per_tok=4,
    n_group=4, topk_group=2, norm_topk_prob=True,
    routed_scaling_factor=2.5, experts_held=(4, 4))


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = AXK1ForCausalLM(axk1_tiny_config(experts_held=(4, 4)))
    m.eval()
    # the default init draws every score near zero; widen the query
    # and the router so that attention and routing discriminate
    for lyr in m.model.layers:
        w = lyr.self_attn.q_b_proj.weight
        w._data = w._data * 4.0
        if hasattr(lyr.mlp, "gate_weight"):
            g = lyr.mlp.gate_weight
            g._data = g._data * 20.0
    return m


def _weights(m):
    return {"embed": m.model.embed_tokens.weight._data,
            "norm": m.model.norm.weight._data,
            "head": m.lm_head.weight._data, "layers": model_layers(m)}


def _reference_rows(m, prompt, tokens, **kw):
    ids = jnp.asarray(np.concatenate([prompt, tokens]).astype(np.int32))
    logits = np.asarray(ref.logits(ids, _weights(m), TINY, **kw))
    return logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]


@pytest.fixture(scope="module")
def served(model):
    """One run on the unified ragged step: prompts over several chunks
    (16) and pages (8), decode across a page boundary (29 + 6 crosses
    32; 61 + 6 crosses 64); (prompts, requests, logits rows, engine, the
    run's step records)."""
    from paddle_tpu.observability import tracing
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n, dtype=np.int32)
               for n in (61, 29, 9)]
    eng = ServingEngine(model, max_slots=3, page_size=8, max_context=128,
                        prefill_chunk=16, num_pages=40,
                        enable_prefix_cache=False)
    rows = {}
    eng.on_logits = lambda req, row: rows.setdefault(
        req.request_id, []).append(row.copy())
    reqs = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    eng.append_launches = _spy_append_runs(eng)
    eng.run_to_completion()
    return prompts, reqs, rows, eng, \
        list(tracing.recorder().steps()[-eng.steps:])


class TestEngineAgainstReference:
    def test_prefill_in_chunks_then_paged_decode_matches_in_logits(
            self, model, served):
        prompts, reqs, rows, eng, _ = served
        assert eng.ragged and eng._family == "mla"
        # the row is stored padded to whole 128-lane registers
        assert eng._pools[0].shape == (1, 40, 8, 256)
        assert eng.program_cache_sizes() == {
            "unified": 1, "feed": 1, "unified_nochunk": 1, "feed_nochunk": 1}
        for r, p in zip(reqs, prompts):
            got = np.stack(rows[r.request_id])
            want = _reference_rows(model, p, np.asarray(r.tokens))
            np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
        assert eng.allocator.stats()["pages_used"] == 0

    def test_the_blocked_reference_is_the_same_reference(self, model,
                                                         served):
        prompts, reqs, _, _, _ = served
        toks = np.asarray(reqs[0].tokens)
        want = _reference_rows(model, prompts[0], toks)
        blocked = _reference_rows(model, prompts[0], toks, q_block=16,
                                  head_block=2, ffn_block=32)
        np.testing.assert_allclose(blocked, want, atol=ATOL, rtol=0)

    @pytest.mark.parametrize("ablate", ref.ABLATIONS)
    def test_negative_controls_lie_outside_the_tolerance(self, model,
                                                         served, ablate):
        """The reference with ONE mechanism off — among them yarn's m^2
        ("mscale") and the group limit — is NOT what the engine
        computes: by 100 x the tolerance at least."""
        prompts, reqs, rows, _, _ = served
        got = np.stack(rows[reqs[0].request_id])
        off = _reference_rows(model, prompts[0], np.asarray(reqs[0].tokens),
                              ablate=frozenset([ablate]))
        assert np.abs(got - off).max() > 100 * ATOL

    def test_the_softmax_scale_carries_yarns_mscale_squared(self, model):
        c = model.config
        m = 0.1 * np.log(16.0) + 1.0
        assert c.softmax_scale == pytest.approx(48 ** -0.5 * m * m)
        assert ref.softmax_scale(TINY) == pytest.approx(c.softmax_scale)
        # and the published one: 192^-1/2 x 1.3466^2
        from paddle_tpu.models.axk1 import axk1_config
        assert axk1_config().softmax_scale == pytest.approx(
            192 ** -0.5 * 1.3465735902799727 ** 2)

    def test_step_counts_of_the_latent_cache_and_the_routed_layers(
            self, model, served):
        _, _, _, eng, records = served
        recs = [r for r in records
                if r["prefill_rows"] or r["decode_rows"]]
        assert recs and any(r["prefill_rows"] for r in recs)
        for r in recs:
            live = r["prefill_rows"] + r["decode_rows"]
            assert r["moe_pairs_routed"] == live * 4 * 2    # k, layers
            assert 0 < r["moe_pairs_held"] < r["moe_pairs_routed"]
            assert 0 < r["moe_experts_hit"] <= 4 * 2
            assert r["latent_row_bytes"] == 256 * 4         # float32
            assert (r["chunk_kv_len"] > 0) == (r["prefill_rows"] > 0)
            assert r["pages_visited"] >= r["pages_live"] > 0
            # a latent cache is ONE KV head: a visit serves it alone
            assert r["attn_block_visits"] == r["pages_visited"]
            # ... and, the launch being one tile here, one tile of it
            assert r["attn_tile_chains"] == r["pages_visited"]
            # ... on the 8 rows of the 40 that hold a decode row's 4;
            # the chunk's visits on the tile's
            assert r["attn_narrow_updates"] <= r["pages_visited"]
            if not r["prefill_rows"]:
                assert r["attn_narrow_updates"] == r["pages_visited"]
        assert any(0 < r["attn_narrow_updates"] < r["pages_visited"]
                   for r in recs)
        assert eng.hbm_accounting()["attn_head_block"] == 1 \
            == eng.hbm_accounting()["attn_tile_block"]
        assert eng.hbm_accounting()["attn_narrow_rows"] == 8
        # a chunk's context grows by the chunk until the prompt ends
        ctx = [r["chunk_kv_len"] for r in recs if r["prefill_rows"]]
        assert ctx[:4] == [16, 32, 48, 61]

    def test_append_runs_is_the_devices_table(self, served):
        """The latent engine's step record carries `append_runs`: every
        launch's count is the live runs of the table the jitted step
        makes of the same row tables (`fused_append_rows` walks it)."""
        _, _, _, eng, records = served
        assert len(eng.append_launches) > 10
        for counts, on_device, _ in eng.append_launches:
            assert counts["append_runs"] == on_device
            rows = counts["decode_rows"] + counts["prefill_rows"]
            # pages of 8 under float32: a tile is a page, a chunk of 16
            # touches two or three
            assert on_device <= rows <= on_device * 8
        assert any(c["prefill_rows"] == 16 and n in (2, 3)
                   for c, n, _ in eng.append_launches)
        assert sum(r["append_runs"] for r in records) \
            == sum(n for _, n, _ in eng.append_launches)

    def test_a_launch_of_several_tiles_visits_a_page_for_a_block_of_them(
            self, model, served):
        """A 64-row chunk under 4 query heads is three tiles of 32
        tokens: the launch takes them two a cell, makes the tokens the
        one-tile launches made, fetches a chunk's pages once a CELL and
        still computes a decode row's pages against ONE tile."""
        from paddle_tpu.observability import tracing
        prompts, reqs, _, _, _ = served
        eng = ServingEngine(model, max_slots=3, page_size=8, max_context=128,
                            prefill_chunk=64, num_pages=40,
                            enable_prefix_cache=False)
        assert eng.hbm_accounting()["attn_tile_block"] == 2
        # a cell that is a block of tiles takes no narrow visit
        assert eng.hbm_accounting()["attn_narrow_rows"] == 0
        again = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        eng.run_to_completion()
        assert [r.tokens for r in again] == [r.tokens for r in reqs]
        recs = [r for r in tracing.recorder().steps()[-eng.steps:]
                if r["prefill_rows"] or r["decode_rows"]]
        for r in recs:
            assert r["pages_live"] <= r["pages_visited"] \
                == r["attn_block_visits"] <= r["attn_tile_chains"]
            if r["prefill_rows"]:
                assert r["attn_narrow_updates"] == 0
            else:
                # decode rows: their pages, once — and, the launch being
                # those rows alone, ONE tile, on the narrow window again
                assert r["attn_tile_chains"] == r["pages_live"] \
                    == r["attn_narrow_updates"]
        # the 61-token prompt's chunk (rows 3..63) has rows in tiles 0
        # and 1, which are ONE cell: 4 + 8 pages by tile, its 8 by cell
        first = recs[0]
        assert (first["prefill_rows"], first["decode_rows"]) == (61, 0)
        assert (first["attn_tile_chains"], first["pages_visited"],
                first["pages_live"]) == (12, 8, 8)

    def test_the_benchmarks_rehearsal_table_builds_this_family(self):
        from benchmarks.lib.harness import as_run, load_json
        src = as_run(load_json(
            "benchmarks/configs/a.x-k1-serve-ep16-d6.json"), True)
        kw = model_kwargs(src)
        assert kw["n_routed_experts"] == 16 and kw["experts_held"] == (4, 4)
        cfg = reader_config(kw)
        assert cfg["num_experts"] == 16 and "rope_positions" not in cfg


class TestLatentKernel:
    """`ragged_paged_attention(v_pages=None, v_dim=)`: pages that hold
    K and V in one row, fetched once."""

    @pytest.mark.parametrize("rep,D,V", [(4, 256, 128), (8, 384, 256)])
    def test_matches_the_oracle_on_a_mixed_launch(self, rep, D, V):
        rng = np.random.default_rng(1)
        T, P, psz, nj = 24, 14, 8, 4
        q = jnp.asarray(rng.normal(size=(T, rep, D)), jnp.float32)
        pool = jnp.asarray(rng.normal(size=(1, P, psz, D)), jnp.float32)
        ss, nt = jnp.asarray([0, 1, 2, 8]), jnp.asarray([1, 1, 0, 13])
        kvl = jnp.asarray([9, 17, 0, 29])
        tab = jnp.asarray(rng.integers(1, P, (4, nj)), jnp.int32)
        got = ragged_paged_attention(q, pool, None, ss, nt, kvl, tab,
                                     scale=0.05, v_dim=V)
        want = ragged_attention_reference(q, pool, None, ss, nt, kvl, tab,
                                          scale=0.05, v_dim=V)
        assert got.shape == (T, rep, V)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
        # one row serves every query head: one KV head a page visit,
        # whatever a cell of that size could hold
        from paddle_tpu.ops.pallas_ragged import ragged_head_block
        assert ragged_head_block(1, 8 * rep, D, psz, 4, latent=True) == 1 \
            == ragged_head_block(8, 8 * rep, D, psz, 4, latent=True)
        # the same numbers as two pools, V a copy of K's first columns
        two = ragged_paged_attention(
            q, pool, jnp.pad(pool[..., :V], ((0, 0),) * 3 + ((0, D - V),)),
            ss, nt, kvl, tab, scale=0.05)[..., :V]
        np.testing.assert_allclose(got, two, atol=2e-6, rtol=0)

    def test_refuses_both_or_neither(self):
        z = jnp.zeros((8, 1, 128))
        pool = jnp.zeros((1, 2, 8, 128))
        i = jnp.zeros(1, jnp.int32)
        with pytest.raises(ValueError):
            ragged_paged_attention(z, pool, None, i, i, i, i[None])
        with pytest.raises(ValueError):
            ragged_paged_attention(z, pool, pool, i, i, i, i[None],
                                   v_dim=64)

    def test_absorbed_attention_is_the_unabsorbed_one(self):
        """One layer's attention, no engine: (q_nope W_k | q_pe) against
        rows (c | k_pe | 0), then W_v, equals per-head keys and values
        built from the latent."""
        rng = np.random.default_rng(2)
        S, nh, r, dn, dr, dv = 12, 4, 128, 16, 8, 16
        c = jnp.asarray(rng.normal(size=(S, r)), jnp.float32)
        k_pe = jnp.asarray(rng.normal(size=(S, dr)), jnp.float32)
        q_nope = jnp.asarray(rng.normal(size=(S, nh, dn)), jnp.float32)
        q_pe = jnp.asarray(rng.normal(size=(S, nh, dr)), jnp.float32)
        wkb = jnp.asarray(rng.normal(size=(r, nh, dn + dv)) * r ** -0.5,
                          jnp.float32)
        w_k, w_v = wkb[..., :dn], wkb[..., dn:]
        # unabsorbed
        k_nope = jnp.einsum("sr,rnd->snd", c, w_k)
        v = jnp.einsum("sr,rnd->snd", c, w_v)
        s = (jnp.einsum("qnd,knd->nqk", q_nope, k_nope)
             + jnp.einsum("qnd,kd->nqk", q_pe, k_pe)) * 0.2
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        want = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, -1), v)
        # absorbed, through the paged kernel: one sequence, S new rows
        width = 256
        rows = jnp.pad(jnp.concatenate([c, k_pe], -1),
                       ((0, 0), (0, width - r - dr)))
        pool = jnp.zeros((1, 4, 8, width)).at[0, 1:3].set(
            jnp.pad(rows, ((0, 4), (0, 0))).reshape(2, 8, width))
        q_cat = jnp.pad(jnp.concatenate(
            [jnp.einsum("snd,rnd->snr", q_nope, w_k), q_pe], -1),
            ((0, 0), (0, 0), (0, width - r - dr)))
        o_lat = ragged_paged_attention(
            q_cat, pool, None, jnp.asarray([0]), jnp.asarray([S]),
            jnp.asarray([S]), jnp.asarray([[1, 2]]), scale=0.2, v_dim=r)
        got = jnp.einsum("tnr,rnv->tnv", o_lat, w_v)
        np.testing.assert_allclose(got, want, atol=5e-6, rtol=0)


def _hand_router(sc, n_group, topk_group, top_k, scale):
    """Sigmoid scores [E] of ONE token -> {expert: weight}, written out
    with loops."""
    E = len(sc)
    per = E // n_group
    gscore = []
    for g in range(n_group):
        best = sorted(sc[g * per:(g + 1) * per], reverse=True)[:2]
        gscore.append(sum(best))
    stay = sorted(range(n_group), key=lambda g: -gscore[g])[:topk_group]
    pool = [e for e in range(E) if e // per in stay]
    chosen = sorted(pool, key=lambda e: -sc[e])[:top_k]
    total = sum(sc[e] for e in chosen)
    return {e: sc[e] / total * scale for e in chosen}


class TestRouter:
    def test_group_limited_sigmoid_routing_matches_a_hand_written_one(self):
        rng = np.random.default_rng(3)
        sc = 1 / (1 + np.exp(-rng.normal(size=(40, 16)) * 2))
        gv, topi, _, _ = _route(jnp.asarray(sc, jnp.float32), 4, True,
                                None, 2.5, (4, 2))
        for t in range(40):
            want = _hand_router(list(sc[t]), 4, 2, 4, 2.5)
            got = dict(zip(np.asarray(topi[t]).tolist(),
                           np.asarray(gv[t]).tolist()))
            assert set(got) == set(want)
            for e in want:
                assert got[e] == pytest.approx(want[e], rel=1e-5)

    def test_a_case_the_group_limit_decides(self):
        """Group 0 holds the single best expert and nothing else of
        note; groups 1 and 2 hold two good ones each.  Plain top-4 takes
        expert 0; with 2 of 4 groups kept, group 0 (score 0.9 + 0.1)
        loses to groups 1 and 2 (0.8 + 0.7, 0.75 + 0.6) and expert 0 is
        NOT chosen."""
        sc = np.full((1, 16), 0.1, np.float32)
        sc[0, 0] = 0.9
        sc[0, [4, 5]] = (0.8, 0.7)
        sc[0, [8, 9]] = (0.75, 0.6)
        plain = _route(jnp.asarray(sc), 4, True, None, 1.0)[1]
        limited = _route(jnp.asarray(sc), 4, True, None, 1.0, (4, 2))[1]
        assert sorted(np.asarray(plain[0]).tolist()) == [0, 4, 5, 8]
        assert sorted(np.asarray(limited[0]).tolist()) == [4, 5, 8, 9]
        assert _hand_router(list(sc[0]), 4, 2, 4, 1.0).keys() \
            == {4, 5, 8, 9}

    def test_the_default_router_is_untouched(self):
        """No group, softmax scores: `_route` returns what it returned
        before the knob existed (top-k of the gates, renormalised)."""
        rng = np.random.default_rng(4)
        gates = jax.nn.softmax(jnp.asarray(rng.normal(size=(9, 8)),
                                           jnp.float32), -1)
        gv, topi, local, mine = _route(gates, 2, True, None, 1.0)
        tv, ti = jax.lax.top_k(gates, 2)
        assert mine is None and local is topi
        np.testing.assert_array_equal(topi, ti)
        np.testing.assert_array_equal(
            gv, tv / jnp.maximum(tv.sum(-1, keepdims=True), 1e-9))

    def test_reference_routing_is_the_programs(self):
        rng = np.random.default_rng(5)
        h2 = jnp.asarray(rng.normal(size=(50, 64)), jnp.float32)
        router = jnp.asarray(rng.normal(size=(64, 16)) * 0.3, jnp.float32)
        spec = ref.layer_specs(TINY)[1]
        w_ref, e_ref = ref.routing(h2, router, spec)
        gv, topi, _, _ = _route(jax.nn.sigmoid(h2 @ router), 4, True,
                                None, 2.5, (4, 2))
        np.testing.assert_array_equal(np.sort(e_ref, -1), np.sort(topi, -1))
        np.testing.assert_allclose(np.sort(w_ref, -1), np.sort(gv, -1),
                                   rtol=1e-5)


class TestShare:
    def test_the_shares_routed_parts_and_the_shared_expert_once_add_up(
            self):
        """8 chips hold 2 experts each of 16 (a routing group of 4 spans
        two chips, as 24 spans two at the published sizes): the routed
        parts of the 8 shares plus the shared expert counted ONCE are
        the uncut layer."""
        kw = dict(d_model=32, d_hidden=16, num_experts=16, top_k=4,
                  dropless=True, shared_expert_hidden=16,
                  routed_scale=2.5, score="sigmoid", n_group=4,
                  topk_group=2)
        paddle.seed(1)
        whole = MoELayer(**kw)
        whole.gate_weight._data = whole.gate_weight._data * 30.0
        x = paddle.to_tensor(np.random.default_rng(6).normal(
            size=(2, 20, 32)).astype(np.float32))
        want = np.asarray(whole(x)._data)
        shared = np.asarray((whole.shared_down(
            paddle.nn.functional.silu(whole.shared_gate(x))
            * whole.shared_up(x)))._data)
        total = np.zeros_like(want)
        for chip in range(8):
            first = 2 * chip
            part = MoELayer(experts_held=(first, 2), **kw)
            part.gate_weight._data = whole.gate_weight._data
            for name in ("w_gate", "w_up", "w_down"):
                getattr(part, name)._data = \
                    getattr(whole, name)._data[first:first + 2]
            for name in ("shared_gate", "shared_up", "shared_down"):
                getattr(part, name).weight._data = \
                    getattr(whole, name).weight._data
            # every chip computes the shared expert alike: take it off
            total += np.asarray(part(x)._data) - shared
        np.testing.assert_allclose(total + shared, want, atol=2e-6, rtol=0)
        # and the routed part is not nothing
        assert np.abs(want - shared).max() > 1e-2
