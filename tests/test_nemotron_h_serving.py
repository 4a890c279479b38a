"""The Nemotron-H hybrid through `ServingEngine`.

The engine — chunked prefill whose chunks carry the state across chunk
and scan-chunk borders, then decode through the state pool and across a
page border, unlike sequences in one launch, a slot handed from a
finished request to a new one (which starts from ZERO state by a flag in
the row tables) — against the plain float32 reference's full forward
(`benchmarks/lib/reference_nemotron.py`) on seeded weights, in logits;
idle slots' state bit-unchanged; the bytes the engine says it holds; what
it refuses; the step record's counts. (The step programs' pinned
texts: `test_step_program_pins.py`.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference_nemotron as ref
from paddle_tpu.generation import _cached_step_body, _decode_params
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ServingEngine
from test_nemotron_h import seeded

PAGE, CHUNK = 8, 16         # a prefill chunk is two scan chunks of 8


@pytest.fixture(scope="module")
def tiny():
    return seeded()


def _engine(m, **kw):
    args = dict(max_slots=3, page_size=PAGE, max_context=128,
                prefill_chunk=CHUNK, num_pages=40)
    args.update(kw)
    return ServingEngine(m, **args)


def _run(eng, prompts, max_new, stagger=0):
    """Each request's tokens and the logits rows they were taken from;
    `stagger` steps between two arrivals."""
    rows = {}
    eng.on_logits = lambda req, row: rows.setdefault(
        req.request_id, []).append(np.asarray(row, np.float32))
    handles = []
    for p, n in zip(prompts, max_new):
        handles.append(eng.add_request(p, max_new_tokens=n))
        for _ in range(stagger):
            eng.step()
    while eng.has_work():
        eng.step()
    eng.collect()
    eng.on_logits = None
    return [(np.asarray(h.tokens, np.int32), np.stack(rows[h.request_id]))
            for h in handles]


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
#: and scan-chunk borders at every 8) and of less than one, decode
#: across page borders, three unlike sequences in one launch
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
    assert eng.ragged and eng._family == "hybrid"
    for p, (tokens, got) in zip(prompts, _run(eng, prompts, new)):
        want = _reference(w, c, p, tokens)
        assert got.shape == want.shape == (len(tokens), 96)
        np.testing.assert_allclose(got, want, atol=3e-4)
        np.testing.assert_array_equal(tokens, want.argmax(-1))
    assert eng.program_cache_sizes() == {
        "unified": 1, "feed": 1, "unified_nochunk": 1, "feed_nochunk": 1}
    assert eng.launches == eng.steps - 1    # ONE launch a step, one ahead


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
    launch; the live slot's and the spare's do not."""
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
    assert len(eng._pools["ssm"]) == 5
    for s, t in eng._pools["ssm"]:
        assert not bool((s[0] == 3.25).any())


# ------------------------------------------------- bytes and the record
def test_the_bytes_the_engine_says_it_holds(tiny):
    m, _, c = tiny
    eng = _engine(m)
    acct = eng.hbm_accounting()
    held = sum(int(np.prod(p._data.shape)) * 4
               for _, p in m.named_parameters())
    assert acct["weights_bytes"] == held
    state = 8 * 8 * 16 * 4 + 3 * (8 * 8 + 2 * 2 * 16) * 4
    assert acct["state_pool_bytes"] == 5 * 4 * state
    pages = 1 * 2 * 2 * 40 * PAGE * 32 * 4      # ONE attention block
    assert acct["page_pool_bytes"] == pages + 5 * 4 * state
    assert [tuple(a.shape) for a in eng._pools["ssm"][0]] \
        == [(4, 8, 16, 8), (4, 3, 128)]
    assert len(eng._pools["kv"]) == 1


def test_the_step_record_counts_the_state(tiny):
    m, _, _ = tiny
    eng = _engine(m)
    _run(eng, _prompts(7, [21, 3]), [5, 7], stagger=1)
    recs = [r for r in tracing.recorder().steps()[-eng.steps:]
            if r.get("ssm_slots_live")]
    assert recs and all(k in recs[-1] for k in tracing.STEP_COUNTS_SSM)
    assert all(k in recs[-1] for k in tracing.STEP_COUNTS_MOE)
    one = 8 * 8 * 16 * 4
    for r in recs:
        assert r["ssm_state_bytes"] == one + 3 * 128 * 4
        assert r["state_pool_slots_total"] == 3
        live, starts = r["ssm_slots_live"], r["ssm_state_resets"]
        assert r["ssm_state_bytes_moved"] == 5 * one * (2 * live - starts)
        # no fixed-width integer: 5.4e9 at the benchmark's sizes
        assert type(r["ssm_state_bytes_moved"]) is int
        assert r["ssm_scan_rows"] == r["prefill_rows"]
    assert sum(r["ssm_state_resets"] for r in recs) == 2
    assert max(r["state_pool_slots_used"] for r in recs) == 2
    # every launch wrote its pools in place (the last call launches none)
    assert all(r["pools_in_place"] == 1 for r in recs[:-1])


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("kw, why", [
    (dict(enable_prefix_cache=True), "enable_prefix_cache must be off"),
    (dict(spec_decode=2), "spec_decode must be 0"),
    (dict(role="prefill"), "role must be 'colocated'"),
    (dict(role="decode"), "role must be 'colocated'")])
def test_what_would_snapshot_a_state_is_refused_at_construction(tiny, kw,
                                                                why):
    m, _, _ = tiny
    with pytest.raises(ValueError, match="5 state-space blocks") as e:
        _engine(m, **kw)
    assert why in str(e.value)


def test_sharing_and_preemption_are_off_and_a_handoff_raises(tiny):
    m, _, _ = tiny
    eng = _engine(m)
    assert eng.prefix_sharing is False and eng.prefix_cache is None
    assert eng.preemption is False
    same = _prompts(8, [17])[0]
    a = eng.add_request(same, max_new_tokens=4)
    eng.step(), eng.step()
    b = eng.add_request(same, max_new_tokens=4, priority=5)
    while eng.has_work():
        eng.step()
    assert b.shared_tokens == 0 and list(a.tokens) == list(b.tokens)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng.export_request(a)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng.import_request(None)
    with pytest.raises(ValueError, match="spec_decode stays 0"):
        eng.reconfigure(spec_decode=2)


def test_the_cached_generate_path_refuses_the_family(tiny):
    m, _, _ = tiny
    p = _decode_params(m)
    assert p["family"] == "hybrid" and p["pattern"] == "MEMEMEM*EME"
    with pytest.raises(NotImplementedError, match="ServingEngine"):
        _cached_step_body(p, 32)
    with pytest.raises(NotImplementedError, match="quantisation"):
        _decode_params(m, weight_only_int8=True)


def test_an_untileable_shape_is_refused(tiny, monkeypatch):
    from paddle_tpu.serving import engine as eng_mod
    m, _, _ = tiny
    monkeypatch.setattr(eng_mod, "_ragged_step_eligible",
                        lambda *a: False)
    with pytest.raises(ValueError, match="unified ragged step only"):
        _engine(m)


def test_the_hybrid_step_takes_the_nine_inputs(tiny):
    """What `benchmarks/tests` lower it with: `kv_lengths` is a pair."""
    m, _, _ = tiny
    eng = _engine(m)
    B, C = eng.max_slots, eng.prefill_chunk

    def i32(*d):
        return jax.ShapeDtypeStruct(d, jnp.int32)

    low = eng._jit_unified.lower(
        eng._w, i32(B + C), eng._pools, i32(B + C), i32(B + 1),
        (i32(B + 1), i32(B + 3)), i32(B + 1, eng.pages_per_seq),
        i32(B + C), i32(B + C))
    logits, pools, tokens, moe = low.out_info
    assert logits.shape == (B + 1, 96) and tokens.shape == (B + 1,)
    assert moe.shape == (5,)
    text = low.as_text(debug_info=True)
    for here in ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_out",
                 "latent_proj", "routed_ffn", "shared_expert",
                 "fused_rope_append", "ragged_paged_attention"):
        assert here in text, here
