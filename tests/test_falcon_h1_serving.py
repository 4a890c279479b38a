"""Falcon-H1 through `ServingEngine` against the plain reference
(`benchmarks/lib/reference_falcon.py`): prompt chunks through BOTH
caches of every layer — pages for the rotary GQA mixer, a slot of the
state-minor pool for the Mamba-2 mixer, both fed by the block's one norm
— then decode steps through both, on the hybrid body's one step program
at both row counts; a slot handed on starts from zero state and fresh
pages; what cannot be served is refused.  (The step programs' pinned
texts: `test_step_program_pins.py`.)  Toy sizes as `test_falcon_h1.py`'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference_falcon as ref
from paddle_tpu.generation import _cached_step_body, _decode_params
from paddle_tpu.observability import tracing
from paddle_tpu.ops.pallas_ssm import STATE_MINOR
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import _gqa_mixer, _pattern_blocks
from test_falcon_h1 import seeded

PAGE, CHUNK = 8, 16
#: the engine's float32 logits against the reference's: the order of
#: float32 sums (the chunked scan against the token-by-token one, the
#: ragged kernel's online softmax)
ATOL = 3e-4


@pytest.fixture(scope="module")
def tiny():
    return seeded()


def _engine(m, **kw):
    args = dict(max_slots=3, page_size=PAGE, max_context=128,
                prefill_chunk=CHUNK, num_pages=40,
                enable_prefix_cache=False)
    args.update(kw)
    return ServingEngine(m, **args)


def _run(eng, prompts, max_new, stagger=0):
    """Each request's handle, tokens and the logits rows they were
    taken from; `stagger` steps between two arrivals."""
    rows, slots = {}, {}

    def keep(req, row):
        rows.setdefault(req.request_id, []).append(
            np.asarray(row, np.float32))
        slots[req.request_id] = req.slot

    eng.on_logits = keep
    handles = []
    for p, n in zip(prompts, max_new):
        handles.append(eng.add_request(p, max_new_tokens=n))
        for _ in range(stagger):
            eng.step()
    while eng.has_work():
        eng.step()
    eng.collect()
    eng.on_logits = None
    return [(h, np.asarray(h.tokens, np.int32), np.stack(rows[h.request_id]),
             slots[h.request_id]) for h in handles]


def _reference(w, c, prompt, tokens):
    """The float32 logits at the positions the tokens were generated
    from, teacher-forced over prompt + tokens."""
    fed = jnp.asarray(np.concatenate([prompt, tokens[:-1]]), jnp.int32)
    return np.asarray(ref.logits(fed, w, c))[len(prompt) - 1:]


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, n).astype(np.int32) for n in lens]


# ----------------------------------------------------------- the engine
#: prompts of several chunks (the state crosses chunk borders at 16, 32
#: and scan-chunk borders at every 8, the pages theirs at every 8) and of
#: less than one, decode across page borders, three unlike sequences in
#: one launch
CASES = {"chunks_then_decode": ([37], [14]),
         "unlike_lengths": ([19, 5, 33], [9, 12, 7]),
         "one_token_prompt": ([1, 30], [10, 4]),
         "whole_chunks": ([32, 16], [5, 9])}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_logits_and_state_match_the_reference(tiny, case):
    m, w, c = tiny
    lens, new = CASES[case]
    prompts = _prompts(3, lens)
    eng = _engine(m)
    assert eng.ragged and eng._family == "hybrid"
    assert eng._blocks == ("M*", "D") * 2
    for p, (_, tokens, got, slot) in zip(prompts, _run(eng, prompts, new)):
        want = _reference(w, c, p, tokens)
        assert got.shape == want.shape == (len(tokens), 96)
        np.testing.assert_allclose(got, want, atol=ATOL)
        np.testing.assert_array_equal(tokens, want.argmax(-1))
        # ... and the state the slot was left with, in the last layer,
        # is the recurrence's after the last token that was fed
        fed = jnp.asarray(np.concatenate([p, tokens[:-1]]), jnp.int32)
        _, state = ref.hidden_states(fed, w["embed"], w["layers"], c,
                                     jnp.float32, state_of=1)
        np.testing.assert_allclose(eng._pools["ssm"][1][0][slot], state,
                                   atol=ATOL, rtol=1e-4)
    assert eng.program_cache_sizes() == {
        "unified": 1, "feed": 1, "unified_nochunk": 1, "feed_nochunk": 1}
    assert eng.launches == eng.steps - 1    # ONE launch a step, one ahead


def test_every_layer_holds_a_slot_and_pages(tiny):
    m, _, _ = tiny
    eng = _engine(m)
    cfg = m.config
    assert eng._ssm_layers == 2 and len(eng._pools["kv"]) == 2
    assert eng._state_layout == STATE_MINOR
    for state, tail in eng._pools["ssm"]:
        assert state.shape == (4, cfg.mamba_n_heads, cfg.mamba_d_head,
                               cfg.mamba_d_state)
        assert state.dtype == jnp.float32
        assert tail.shape == (4, 3, cfg.conv_dim)
    acct = eng.hbm_accounting()
    # as STORED: 4 B an element of [H, P, N], no lane padded, and the tail
    per = 4 * 4 * 8 * 128 + 3 * cfg.conv_dim * 4
    assert acct["state_pool_bytes"] == 2 * 4 * per
    # the GQA mixer runs 5 query heads a KV head, as published
    assert eng._q_rep == 5


def test_a_launch_with_and_without_a_chunk(tiny):
    """The step records of one request: its prompt's launches carry a
    chunk (and its length), its decode launches none; the state-space
    counts say which slot was started and what was moved."""
    m, _, _ = tiny
    eng = _engine(m)
    (h, tokens, _, _), = _run(eng, _prompts(5, [21]), [6])
    recs = [r for r in tracing.recorder().steps()[-eng.steps:]
            if r.get("ssm_slots_live")]
    chunks = [r["ssm_scan_rows"] for r in recs]
    assert chunks[:2] == [16, 5] and set(chunks[2:]) == {0}
    assert [r["ssm_state_resets"] for r in recs][:3] == [1, 0, 0]
    state = 4 * 4 * 8 * 128
    assert recs[0]["ssm_state_bytes_moved"] == 2 * 1 * state    # 2 layers
    assert recs[1]["ssm_state_bytes_moved"] == 2 * 2 * state
    assert recs[0]["rows_computed"] == 3 + 16
    assert recs[-1]["rows_computed"] == 3
    assert recs[0]["ssm_state_bytes"] == state + 3 * m.config.conv_dim * 4


def test_a_slot_goes_from_a_finished_request_to_a_new_one(tiny):
    """Two slots, four requests: the third and fourth take over the
    slots of the first two — their state, which a flag in the row tables
    zeroes on the device, AND their pages, released and handed out
    again — staggered, so that a slot is reused while the other is
    mid-decode; every logit matches."""
    m, w, c = tiny
    prompts = _prompts(4, [20, 9, 13, 27])
    new = [6, 11, 9, 5]
    eng = _engine(m, max_slots=2, num_pages=12)
    got = _run(eng, prompts, new)
    assert sorted(slot for _, _, _, slot in got) == [0, 0, 1, 1]
    resets = [r["ssm_state_resets"] for r in
              tracing.recorder().steps()[-eng.steps:]]
    assert sum(resets) == 4
    for p, (_, tokens, rows, _) in zip(prompts, got):
        np.testing.assert_allclose(rows, _reference(w, c, p, tokens),
                                   atol=ATOL)


def test_idle_slots_state_is_bit_unchanged(tiny):
    """One request in slot 0 of three: the other slots' state and tails
    (set to a pattern first) come back bit for bit, launch after
    launch."""
    m, _, _ = tiny
    eng = _engine(m)
    eng._pools["ssm"] = [(z + 3.0, t + 1) for z, t in eng._pools["ssm"]]
    before = [(np.asarray(z), np.asarray(t)) for z, t in eng._pools["ssm"]]
    (_, _, _, slot), = _run(eng, _prompts(6, [19]), [5])
    assert slot == 0
    for (z0, t0), (z, t) in zip(before, eng._pools["ssm"]):
        np.testing.assert_array_equal(np.asarray(z)[1:3], z0[1:3])
        np.testing.assert_array_equal(np.asarray(t)[1:3], t0[1:3])
        assert not np.array_equal(np.asarray(z)[0], z0[0])


# ------------------------------------------------------- the refusals
def test_what_a_state_cannot_serve_is_refused(tiny):
    m, _, _ = tiny
    with pytest.raises(ValueError, match="enable_prefix_cache"):
        _engine(m, enable_prefix_cache=True)
    with pytest.raises(ValueError, match="spec_decode"):
        _engine(m, spec_decode=2)
    with pytest.raises(ValueError, match="role"):
        _engine(m, role="prefill")
    eng = _engine(m)
    assert eng.prefix_cache is None and not eng.preemption
    with pytest.raises(ValueError, match="spec_decode stays 0"):
        eng.reconfigure(spec_decode=2)
    p = _decode_params(m)
    assert p["family"] == "hybrid" and p["pattern"] == "[M*]D[M*]D"
    assert len(p["mults"]) == 14 and len(p["attn_static"]) == 2
    with pytest.raises(NotImplementedError, match="ServingEngine"):
        _cached_step_body(p, 32)
    with pytest.raises(NotImplementedError, match="quantisation"):
        _decode_params(m, weight_only_int8=True)


@pytest.mark.parametrize("pattern, want", [
    ("MEMEMEM*EME", tuple("MEMEMEM*EME")),
    ("[M*]D[M*]D", ("M*", "D", "M*", "D")),
    ("KEKDL", tuple("KEKDL")), ("[KL]E", ("KL", "E"))])
def test_a_pattern_names_blocks(pattern, want):
    assert _pattern_blocks(pattern) == want


@pytest.mark.parametrize("pattern", ["[MD]", "[]", "MX", "[M*"])
def test_a_pattern_that_names_no_block_is_refused(pattern):
    with pytest.raises(ValueError):
        _pattern_blocks(pattern)


def test_the_gqa_mixer_without_a_table_is_the_identity_turn(tiny):
    """`rope=None` (a model without a rotary embedding) is the mixer
    under cos 1, sin 0."""
    m, _, _ = tiny
    eng = _engine(m)
    L = eng._w["layers"][0]
    cfg = m.config
    B, C = eng.max_slots, eng.prefill_chunk
    T = B + C
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.normal(0, 1, (1, T, cfg.hidden_size)), jnp.float32)
    nt = jnp.asarray([1, 0, 0, 9], jnp.int32)
    from paddle_tpu.serving.engine import _seq_starts
    ss = _seq_starts(B, 1)
    tables = jnp.zeros((B + 1, eng.pages_per_seq), jnp.int32) \
        .at[0, 0].set(1).at[3, :2].set(jnp.asarray([2, 3]))
    page = jnp.zeros(T, jnp.int32).at[0].set(1).at[3:11].set(2).at[11].set(3)
    off = jnp.zeros(T, jnp.int32).at[3:11].set(jnp.arange(8))
    runs = eng._run_table(ss)(nt, page, off)
    kvl = jnp.asarray([1, 0, 0, 9], jnp.int32)
    geom = dict(heads=cfg.num_attention_heads, kv=cfg.num_key_value_heads,
                d=cfg.head_dim)
    one = jnp.ones((T, cfg.head_dim // 2), jnp.float32)
    pools = lambda: tuple(jnp.zeros_like(p)            # noqa: E731
                          for p in eng._pools["kv"][0])
    y0, _ = _gqa_mixer(L, h, None, pools(), ss, nt, kvl, tables, runs, **geom)
    y1, _ = _gqa_mixer(L, h, (one, 0 * one), pools(), ss, nt, kvl, tables,
                       runs, **geom)
    np.testing.assert_array_equal(y0, y1)
    assert float(jnp.abs(y0[0, 3:12]).max()) > 0


def test_the_step_lowers_with_every_scope(tiny):
    """What `benchmarks/tests` lower it with, and the names the readers
    look for."""
    m, _, _ = tiny
    eng = _engine(m)
    B, C = eng.max_slots, eng.prefill_chunk

    def i32(*d):
        return jax.ShapeDtypeStruct(d, jnp.int32)

    low = eng._jit_unified.lower(
        eng._w, i32(B + C), eng._pools, i32(B + C), i32(B + 1),
        (i32(B + 1), i32(B + 3)), i32(B + 1, eng.pages_per_seq),
        i32(B + C), i32(B + C))
    logits, pools, tokens = low.out_info
    assert logits.shape == (B + 1, 96) and tokens.shape == (B + 1,)
    text = low.as_text(debug_info=True)
    for here in ("attn_norm", "ssm_in_proj", "ssm_conv", "ssm_scan",
                 "ssm_out", "qkv_proj", "cache_write", "attention",
                 "attn_out", "ffn_norm", "ffn", "fused_rope_append",
                 "ragged_paged_attention"):
        assert here in text, here
