"""The Ling 3.0 hybrid (`paddle_tpu.models.bailing_hybrid`) through
`ServingEngine`.

The model's own whole-sequence forward, and the engine — chunked prefill
whose chunks carry the delta-rule state across chunk and sub-chunk
borders, then decode through the state pool and the latent block's
pages, unlike sequences in one launch, a slot handed from a finished
request to a new one (which starts from ZERO state by a flag in the row
tables) — against the plain float32 reference's full forward
(`benchmarks/lib/reference_ling.py`) on seeded weights, in logits; the
planted faults, which have to show; idle slots' state bit-unchanged; the
bytes the engine says it holds; what it refuses, by name; the step
record's counts; the share test — eight chips' routed addends plus the
shared expert ONCE add up to the uncut layer. (The step programs'
pinned texts: `test_step_program_pins.py`.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import reference_ling as ref
from benchmarks.systems.ling_serving import model_layers
from paddle_tpu.generation import (_cached_step_body, _decode_params,
                                   _ffn_apply)
from paddle_tpu.models.bailing_hybrid import (BailingHybridForCausalLM,
                                              arrays,
                                              bailing_hybrid_config,
                                              bailing_hybrid_tiny_config)
from paddle_tpu.observability import tracing
from test_nemotron_h_serving import PAGE, _engine, _prompts, _run

# (PAGE 8, CHUNK 16: a prefill chunk is two sub-chunks of 8)
CFG_KEYS = ("layers_held", "layer_group_size", "first_k_dense_replace",
            "num_attention_heads", "head_dim", "kda_lower_bound",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "num_experts_per_tok", "n_group",
            "topk_group", "norm_topk_prob", "routed_scaling_factor",
            "rms_norm_eps", "experts_held")


def seeded(**kw):
    """A seeded toy Ling whose every mechanism carries signal (gains
    N(1, 0.3), an expert bias of the scores' own spread, a sharp
    softmax), its reference weights and the reference's configuration."""
    paddle.seed(0)
    cfg = bailing_hybrid_tiny_config(**kw)
    m = BailingHybridForCausalLM(cfg)
    m.eval()
    rng = np.random.default_rng(0)

    def draw(p, mean, std):
        p._data = jnp.asarray(rng.normal(mean, std, p._data.shape),
                              jnp.float32)

    for n, p in m.named_parameters():
        if n.endswith("norm.weight"):
            draw(p, 1, 0.3)
        elif n.endswith("expert_bias"):
            draw(p, 0, 0.2)
        elif n.endswith("gate_weight"):
            draw(p, 0, 0.3)
        elif "q_proj" in n:
            p._data = p._data * 4
    w = {"embed": m.model.embed_tokens.weight._data,
         "norm": m.model.norm.weight._data, "head": m.lm_head.weight._data,
         "layers": model_layers(m)}
    c = {k: getattr(cfg, k) for k in CFG_KEYS}
    c.update(num_hidden_layers=cfg.published_layers,
             short_conv_kernel_size=cfg.conv_kernel)
    return m, w, c


@pytest.fixture(scope="module")
def tiny():
    return seeded()


def _reference(w, c, prompt, tokens, **kw):
    """The float32 logits at the positions the tokens were generated
    from, teacher-forced over prompt + tokens."""
    fed = jnp.asarray(np.concatenate([prompt, tokens[:-1]]), jnp.int32)
    return np.asarray(ref.logits(fed, w, c, **kw))[len(prompt) - 1:]


def _lower(eng):
    """The hybrid step lowered from shapes (`kv_lengths` is a pair)."""
    B, C = eng.max_slots, eng.prefill_chunk

    def i32(*d):
        return jax.ShapeDtypeStruct(d, jnp.int32)

    return eng._jit_unified.lower(
        eng._w, i32(B + C), eng._pools, i32(B + C), i32(B + 1),
        (i32(B + 1), i32(B + 3)), i32(B + 1, eng.pages_per_seq),
        i32(B + C), i32(B + C))


# ------------------------------------------------------------ the model
def test_the_pattern_reads_the_published_indices(tiny):
    m, _, c = tiny
    assert m.config.pattern == ref.pattern(c) == "KDKEKEKELEKEKE"
    full = bailing_hybrid_config()
    assert full.pattern.count("L") == 7 and full.pattern.count("K") == 35
    assert full.pattern[:4] == "KDKD" and full.pattern.count("D") == 2
    assert [i for i in range(42) if full.pattern[2 * i] == "L"] \
        == [5, 11, 17, 23, 29, 35, 41]


def test_model_forward_matches_the_reference(tiny):
    m, w, c = tiny
    ids = np.random.default_rng(1).integers(0, 96, 37).astype(np.int32)
    got = np.asarray(m(paddle.to_tensor(ids[None]))._data)[0]
    np.testing.assert_allclose(
        got, np.asarray(ref.logits(jnp.asarray(ids), w, c)), atol=2e-4)


@pytest.mark.parametrize("name, limits", [
    ("expert_swiglu_limit_list", [0] * 7 + [4]),
    ("share_expert_swiglu_limit_list", [0, 0, 5] + [0] * 5)])
def test_a_nonzero_swiglu_limit_is_refused_by_name(name, limits):
    with pytest.raises(NotImplementedError, match=name):
        bailing_hybrid_tiny_config(**{name: limits})
    # ... and a limit of a layer that is not held is none of ours
    held = [i for i in (0, 2, 3, 4, 5, 6, 7) if not limits[i]]
    bailing_hybrid_tiny_config(**{name: limits}, layers_held=held)


# ----------------------------------------------------------- the engine
#: prompts of several chunks (the state crosses chunk borders at 16, 32
#: and sub-chunk borders at every 8) and of less than one, decode across
#: page borders, three unlike sequences in one launch
CASES = {"chunks_then_decode": ([37], [14]),
         "unlike_lengths": ([19, 5, 33], [9, 12, 7]),
         "one_token_prompt": ([1, 30], [10, 4]),
         "whole_chunks": ([32, 16], [5, 9])}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_logits_match_the_reference(tiny, case):
    m, w, c = tiny
    lens, new = CASES[case]
    prompts = _prompts(3, lens)
    eng = _engine(m)
    assert eng.ragged and eng._family == "hybrid" and eng._latent
    for p, (tokens, got) in zip(prompts, _run(eng, prompts, new)):
        want = _reference(w, c, p, tokens)
        assert got.shape == want.shape == (len(tokens), 96)
        np.testing.assert_allclose(got, want, atol=3e-4)
        np.testing.assert_array_equal(tokens, want.argmax(-1))
    assert eng.program_cache_sizes() == {
        "unified": 1, "feed": 1, "unified_nochunk": 1, "feed_nochunk": 1}
    assert eng.launches == eng.steps - 1    # ONE launch a step, one ahead


@pytest.fixture(scope="module")
def served(tiny):
    """One request through the engine: (prompt, tokens, logits)."""
    p, = _prompts(5, [37])
    (tokens, got), = _run(_engine(tiny[0]), [p], [14])
    return p, tokens, got


@pytest.mark.parametrize("fault", ref.ABLATIONS)
def test_a_planted_fault_shows(tiny, served, fault):
    """The engine's logits against the reference WITH one fault: what an
    engine with that fault would read, far outside the tolerance."""
    _, w, c = tiny
    p, tokens, got = served
    off = _reference(w, c, p, tokens, ablate=frozenset([fault]))
    far = np.abs(got - off).max()
    assert far > (3e-3 if fault == "state_bf16" else 3e-2), far


def test_a_slot_goes_from_a_finished_request_to_a_new_one(tiny):
    """Two slots, four requests: the third and fourth take over the
    slots (and the state, which a flag in the row tables zeroes on the
    device) of the first two — staggered, so that a slot is reused
    while the other is mid-decode — and every logit matches."""
    m, w, c = tiny
    prompts = _prompts(4, [20, 9, 13, 27])
    new = [6, 11, 9, 5]
    eng = _engine(m, max_slots=2)
    slots = {}
    handles = [eng.add_request(p, max_new_tokens=n)
               for p, n in zip(prompts, new)]
    rows = {}
    eng.on_logits = lambda req, row: rows.setdefault(
        req.request_id, []).append(np.asarray(row, np.float32))
    while eng.has_work():
        eng.step()
        for h in handles:
            if h.slot is not None:
                slots[h.request_id] = h.slot
    assert sorted(slots.values()) == [0, 0, 1, 1]
    resets = [r["ssm_state_resets"] for r in
              tracing.recorder().steps()[-eng.steps:]]
    assert sum(resets) == 4
    for p, h in zip(prompts, handles):
        tokens = np.asarray(h.tokens, np.int32)
        np.testing.assert_allclose(np.stack(rows[h.request_id]),
                                   _reference(w, c, p, tokens), atol=3e-4)


def test_idle_slots_state_is_bit_unchanged(tiny):
    """One request in slot 0 of three: the other slots' state and tails
    (set to a pattern first) come back bit for bit, launch after
    launch; the live slot's do not."""
    m, _, _ = tiny
    eng = _engine(m)
    mark = lambda a: jnp.full(a.shape, 3.25, a.dtype)     # noqa: E731
    eng._pools = dict(eng._pools, ssm=[
        (mark(s), mark(t)) for s, t in eng._pools["ssm"]])
    eng.add_request(_prompts(5, [21])[0], max_new_tokens=6)
    while eng.has_work():
        eng.step()
        for s, t in eng._pools["ssm"]:
            assert bool((s[1:3] == 3.25).all()), "an idle slot's state"
            assert bool((t[1:3] == 3.25).all()), "an idle slot's tail"
    assert len(eng._pools["ssm"]) == 6
    for s, t in eng._pools["ssm"]:
        assert not bool((s[0] == 3.25).any())


# ------------------------------------------------- bytes and the record
def test_the_bytes_the_engine_says_it_holds(tiny):
    m, _, _ = tiny
    eng = _engine(m)
    acct = eng.hbm_accounting()
    # (the engine's tree: the model's arrays, the rope columns of the
    # latent block's W_q and W_kva in the kernel's order, a rope table)
    held = sum(int(np.prod(p._data.shape)) * 4
               for _, p in m.named_parameters())
    assert acct["weights_bytes"] == held + 2 * 128 * 4 * 4
    state = 4 * 16 * 16 * 4 + 3 * 3 * 4 * 16 * 4
    assert acct["state_pool_bytes"] == 6 * 4 * state
    pages = 1 * 1 * 40 * PAGE * (32 + 8) * 4    # ONE latent block, a plane
    assert acct["page_pool_bytes"] == pages + 6 * 4 * state
    assert [tuple(a.shape) for a in eng._pools["ssm"][0]] \
        == [(4, 4, 16, 16), (4, 3, 192)]
    assert [tuple(a.shape) for a in eng._pools["kv"]] == [(1, 40, PAGE, 40)]


def test_the_step_record_counts_the_state(tiny):
    m, _, _ = tiny
    eng = _engine(m)
    _run(eng, _prompts(7, [21, 3]), [5, 7], stagger=1)
    recs = [r for r in tracing.recorder().steps()[-eng.steps:]
            if r.get("ssm_slots_live")]
    assert recs and all(k in recs[-1] for k in tracing.STEP_COUNTS_SSM)
    assert all(k in recs[-1] for k in tracing.STEP_COUNTS_MOE)
    assert all(k in recs[-1] for k in tracing.STEP_COUNTS_LATENT)
    one = 4 * 16 * 16 * 4
    for r in recs:
        assert r["ssm_state_bytes"] == one + 3 * 192 * 4
        assert r["state_pool_slots_total"] == 3
        live, starts = r["ssm_slots_live"], r["ssm_state_resets"]
        assert r["ssm_state_bytes_moved"] == 6 * one * (2 * live - starts)
        assert type(r["ssm_state_bytes_moved"]) is int
        assert r["ssm_scan_rows"] == r["prefill_rows"]
        assert r["latent_row_bytes"] == 40 * 4
    assert sum(r["ssm_state_resets"] for r in recs) == 2
    assert max(r["state_pool_slots_used"] for r in recs) == 2
    # every launch wrote its pools in place (the last call launches none)
    assert all(r["pools_in_place"] == 1 for r in recs[:-1])


def test_the_step_runs_under_the_mixers_own_scopes(tiny):
    m, _, _ = tiny
    eng = _engine(m)
    B, C = eng.max_slots, eng.prefill_chunk
    low = _lower(eng)
    logits, pools, tokens, moe = low.out_info
    assert logits.shape == (B + 1, 96) and tokens.shape == (B + 1,)
    assert moe.shape == (5,)
    text = low.as_text(debug_info=True)
    for here in ("kda_in_proj", "kda_conv", "kda_state_update",
                 "kda_chunk_scan", "kda_out", "mla_q", "mla_kv",
                 "mla_attention", "mla_out", "routed_ffn", "shared_expert",
                 "ffn"):
        assert here in text, here
    from paddle_tpu.observability.attribution import SCOPE_ALIASES
    assert SCOPE_ALIASES["kda_state_update"] == "attention"
    assert SCOPE_ALIASES["kda_chunk_scan"] == "attention"


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("kw, why", [
    (dict(enable_prefix_cache=True), "enable_prefix_cache must be off"),
    (dict(spec_decode=2), "spec_decode must be 0"),
    (dict(role="prefill"), "role must be 'colocated'"),
    (dict(role="decode"), "role must be 'colocated'")])
def test_what_would_snapshot_a_state_is_refused_at_construction(tiny, kw,
                                                                why):
    m, _, _ = tiny
    with pytest.raises(ValueError,
                       match="6 linear-attention .delta-rule. blocks") as e:
        _engine(m, **kw)
    assert why in str(e.value)


def test_sharing_and_preemption_are_off_and_a_handoff_raises(tiny):
    m, _, _ = tiny
    eng = _engine(m)
    assert eng.prefix_sharing is False and eng.prefix_cache is None
    assert eng.preemption is False
    a = eng.add_request(_prompts(8, [17])[0], max_new_tokens=4)
    while eng.has_work():
        eng.step()
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng.export_request(a)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng.import_request(None)
    with pytest.raises(ValueError, match="spec_decode stays 0"):
        eng.reconfigure(spec_decode=2)


def test_the_cached_generate_path_and_quantisation_refuse_the_family(tiny):
    m, _, _ = tiny
    p = _decode_params(m)
    assert p["family"] == "hybrid" and p["pattern"] == "KDKEKEKELEKEKE"
    with pytest.raises(NotImplementedError, match="ServingEngine"):
        _cached_step_body(p, 32)
    with pytest.raises(NotImplementedError, match="bailing_hybrid"):
        _decode_params(m, weight_only_int8=True)


def test_a_shape_the_kernels_cannot_tile_is_refused(tiny, monkeypatch):
    from paddle_tpu.serving import engine as eng_mod
    m, _, _ = tiny
    monkeypatch.setattr(eng_mod, "_kda_step_eligible", lambda *a: False)
    with pytest.raises(ValueError, match="KDA kernels do not tile"):
        _engine(m)
    monkeypatch.undo()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert eng_mod._kda_step_eligible(32, 128, 256, 64)
    assert not eng_mod._kda_step_eligible(32, 128, 96, 64)
    assert not eng_mod._kda_step_eligible(4, 16, 16, 8)


# ------------------------------------------------------------ the share
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One chip's routed addend is linear in what its experts give, so
    the eight shares' addends plus the shared expert counted ONCE are
    the uncut layer — with the group limit, under which a share is one
    routing group and some tokens bring it no pair at all."""
    m, w, c = seeded(num_experts=32, n_group=8, topk_group=4,
                     layers_held=(2,))
    mix = m.model.layers[1].mixer
    a = jnp.asarray(np.random.default_rng(2).normal(0, 1, (1, 40, 32)),
                    jnp.float32)
    tree = arrays(mix.weights())
    whole = _ffn_apply(dict(moe=tree), a, mix.static())
    sh = tree["shared"]
    shared = (jax.nn.silu(a @ sh["sg"]) * (a @ sh["su"])) @ sh["sd"]
    total, empty = 0, 0
    for first in range(0, 32, 4):
        part = dict(tree, **{k: tree[k][first:first + 4]
                             for k in ("wge", "wup", "wdn")})
        st = dict(mix.static(), held=(first, 4))
        addend = _ffn_apply(dict(moe=part), a, st) - shared
        empty += int((np.abs(np.asarray(addend)).max(-1) < 1e-9).sum())
        total = total + addend
    np.testing.assert_allclose(total + shared, whole, atol=1e-5)
    assert empty >= 8 * 40 // 2 - 40    # half the groups are not chosen
    # ... and it is what the reference gives for the whole layer
    spec = ref.specs(c)[1]
    want, _ = ref._moe(a[0], dict(w["layers"][1]), spec, jnp.float32)
    np.testing.assert_allclose(whole[0], want, atol=1e-5)
    # a share alone is the reference's share
    lw = dict(w["layers"][1], **{r: tree[k][4:8] for r, k in
                                 (("eg", "wge"), ("eu", "wup"),
                                  ("ed", "wdn"))})
    want4, _ = ref._moe(a[0], lw, spec._replace(held=(4, 4)), jnp.float32)
    part = dict(tree, **{k: tree[k][4:8] for k in ("wge", "wup", "wdn")})
    got4 = _ffn_apply(dict(moe=part), a, dict(mix.static(), held=(4, 4)))
    np.testing.assert_allclose(got4[0], want4, atol=1e-5)
