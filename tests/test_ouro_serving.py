"""The Ouro looped decoder through `ServingEngine`.

The engine — chunked prefill, then decode through the cache ACROSS a
page boundary, unlike sequences in one launch, a slot reused after a
finish, a pool so small that a request waits on pages — against the
plain float32 reference's full forward
(`benchmarks/lib/reference_ouro.py`) on seeded weights, in logits; the
planted fault of passes that share one cache slot, which has to fail
that comparison; the bytes the engine says it holds; what it refuses;
the step record's counts. (The step programs' pinned texts:
`test_step_program_pins.py`, which builds its looped toy here.)
"""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import reference_ouro as ref
from benchmarks.systems.ouro_serving import launches_of
from paddle_tpu.generation import _cached_step_body, _decode_params
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ServingEngine
from test_engine_programs import _lower_unified
from test_ouro import seeded

PAGE, CHUNK = 8, 8


@pytest.fixture(scope="module")
def tiny():
    return seeded()


def _engine(m, **kw):
    args = dict(max_slots=3, page_size=PAGE, max_context=128,
                prefill_chunk=CHUNK, num_pages=24)
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
    h = ref.pass_states(fed, w, c)[-1]
    n0 = len(prompt)
    return np.asarray(ref.head_logits(h[n0 - 1:], w["head"],
                                      dtype=jnp.float32))


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, n).astype(np.int32) for n in lens]


# ----------------------------------------------------------- the engine
#: prompts of several chunks and of less than one, decode across page
#: boundaries (8, 16, 24 ...), three unlike sequences in one launch
CASES = {"chunks_then_decode": ([21], [14]),
         "unlike_lengths": ([19, 5, 11], [9, 12, 7]),
         "one_token_prompt": ([1, 30], [10, 4])}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_logits_match_the_reference(tiny, case):
    m, w, c = tiny
    lens, new = CASES[case]
    prompts = _prompts(3, lens)
    eng = _engine(m)
    assert eng.ragged and eng._family == "looped"
    for p, (tokens, got) in zip(prompts, _run(eng, prompts, new)):
        want = _reference(w, c, p, tokens)
        assert got.shape == want.shape == (len(tokens), 96)
        np.testing.assert_allclose(got, want, atol=2e-4)
        np.testing.assert_array_equal(tokens, want.argmax(-1))
    assert eng.program_cache_sizes() == {
        "unified": 1, "feed": 1, "unified_nochunk": 1, "feed_nochunk": 1}
    assert eng.launches == eng.steps - 1    # ONE launch a step, one ahead


def test_a_slot_is_reused_and_a_request_waits_on_pages(tiny):
    """A pool of 9 pages: two requests of 4 pages each leave one, so the
    third (3 pages) waits on PAGES with a slot free, is admitted when
    one finishes, takes over its pages and its slot — and every logit
    still matches."""
    m, w, c = tiny
    prompts = _prompts(4, [20, 18, 13])
    new = [8, 12, 9]
    eng = _engine(m, num_pages=10)
    waited = []
    handles = [eng.add_request(p, max_new_tokens=n)
               for p, n in zip(prompts, new)]
    rows = {}
    eng.on_logits = lambda req, row: rows.setdefault(
        req.request_id, []).append(np.asarray(row, np.float32))
    while eng.has_work():
        eng.step()
        waited.append((len(eng.scheduler.waiting), eng.scheduler.inflight,
                       eng.allocator.available_pages))
    assert (1, 2, 1) in waited          # waiting on pages, a slot free
    assert waited[-1] == (0, 0, 9)      # every page came back
    assert handles[2].slot is None and len(handles[2].tokens) == 9
    for p, h in zip(prompts, handles):
        tokens = np.asarray(h.tokens, np.int32)
        np.testing.assert_allclose(np.stack(rows[h.request_id]),
                                   _reference(w, c, p, tokens), atol=2e-4)


def test_the_text_holds_one_layer_in_one_loop(tiny):
    """The passes are a compiled loop around ONE jitted layer: 7 matmuls
    (and the interpreted kernel's 2 for each KV head of a page visit's
    block, both heads here, on the tile's rows and again on the 8 a
    decode row's visit computes), the gate, the head — and staggered
    arrivals still match."""
    m, w, c = tiny
    prompts = _prompts(5, [13, 4])
    eng = _engine(m)
    assert eng.hbm_accounting()["attn_head_block"] == 2
    assert eng.hbm_accounting()["attn_narrow_rows"] == 8
    assert _lower_unified(eng).as_text().count("stablehlo.dot_general") \
        == 7 + 2 * 2 * 2 + 1 + 1
    for p, (tokens, got) in zip(prompts, _run(eng, prompts, [6, 9], 2)):
        np.testing.assert_allclose(got, _reference(w, c, p, tokens),
                                   atol=2e-4)


def test_passes_sharing_one_slot_fail_the_comparison(tiny):
    """THE PLANTED FAULT: the step built with no offset between the
    passes' pages, so every pass writes over the one before and reads
    the last pass's rows of earlier launches. Its logits are far from
    the reference's — and are what the reference's own one-slot cache
    (`shared_slot_states`, the paper's last-pass reuse) gives."""
    m, w, c = tiny
    prompt, = _prompts(6, [21])
    eng = _engine(m)
    pages, eng.num_pages = eng.num_pages, 0
    eng._build_programs()
    eng.num_pages = pages
    (tokens, got), = _run(eng, [prompt], [14])
    want = _reference(w, c, prompt, tokens)
    # inside the first chunk nothing is shared yet; after it, it shows
    assert np.abs(got - want).max() > 0.3 * want.std()
    assert np.sqrt(((got - want) ** 2).mean()) > 0.05 * want.std()
    fed = jnp.asarray(np.concatenate([prompt, tokens[:-1]]), jnp.int32)
    h = ref.shared_slot_states(fed, w, c, launches_of(21, 13, CHUNK))
    one_slot = np.asarray(ref.head_logits(h[20:], w["head"],
                                          dtype=jnp.float32))
    # (to a hundredth of the fault: the one-slot recurrence amplifies
    # float32 rounding, which the model proper does not)
    assert np.abs(got - one_slot).max() < 0.02 * np.abs(got - want).max()


# ------------------------------------------------- bytes and the record
def test_weights_once_and_four_times_the_pool_bytes(tiny):
    m, _, _ = tiny
    eng = _engine(m)
    acct = eng.hbm_accounting()
    held = sum(int(np.prod(p._data.shape)) * 4
               for _, p in m.named_parameters())
    cos_sin = 2 * 128 * 16 * 4
    assert acct["weights_bytes"] == held + cos_sin
    one_pass = 3 * 2 * 2 * 24 * PAGE * 32 * 4   # layers K+V heads pages ..
    assert acct["page_pool_bytes"] == 3 * one_pass
    assert [tuple(p.shape) for p in eng._pools[0]] \
        == [(2, 3 * 24, PAGE, 32)] * 2
    # a launch READS the layers once a pass
    layers = sum(int(np.prod(p._data.shape)) * 4
                 for n, p in m.named_parameters() if ".layers." in n)
    assert eng._hbm_weight_read_bytes == held + cos_sin + 2 * layers


def test_the_step_record_counts_the_loop(tiny):
    m, _, _ = tiny
    eng = _engine(m)
    _run(eng, _prompts(7, [11, 3]), [5, 7])
    recs = [r for r in tracing.recorder().steps()[-eng.steps:]
            if r.get("ut_steps")]
    assert recs and all(k in recs[-1] for k in tracing.STEP_COUNTS_LOOP)
    for r in recs:
        assert (r["ut_steps"], r["layer_applications"]) == (3, 9)
        assert r["cache_row_bytes"] == 9 * 2 * 2 * 32 * 4
        mass = r["ut_exit_mass"]
        assert len(mass) == 3 and min(mass) > 0
        assert sum(mass) == pytest.approx(1.0, abs=1e-5)
    # every launch wrote its pools in place (the last call launches none)
    assert all(r["pools_in_place"] == 1 for r in recs[:-1])
    # the pool's share counts page ids: a token is charged in all slots
    assert recs[0]["pool_pages_total"] == 23


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("kw, why", [
    (dict(enable_prefix_cache=True), "enable_prefix_cache must be off"),
    (dict(spec_decode=2), "spec_decode must be 0"),
    (dict(role="prefill"), "role must be 'colocated'"),
    (dict(role="decode"), "role must be 'colocated'")])
def test_what_moves_or_shares_a_page_is_refused_at_construction(tiny, kw,
                                                                why):
    m, _, _ = tiny
    with pytest.raises(ValueError, match="runs its layers 3 times") as e:
        _engine(m, **kw)
    assert why in str(e.value)


def test_sharing_is_off_and_a_handoff_raises(tiny):
    m, _, _ = tiny
    eng = _engine(m)        # `prefix_sharing` defaults to True elsewhere
    assert eng.prefix_sharing is False and eng.prefix_cache is None
    same = _prompts(8, [17])[0]
    a = eng.add_request(same, max_new_tokens=4)
    eng.step(), eng.step()
    b = eng.add_request(same, max_new_tokens=4)     # a donor is live
    while eng.has_work():
        eng.step()
    assert b.shared_tokens == 0 and list(a.tokens) == list(b.tokens)
    with pytest.raises(NotImplementedError, match="3 pages a page id"):
        eng.export_request(a)
    with pytest.raises(NotImplementedError, match="3 pages a page id"):
        eng.import_request(None)


def test_adaptive_exit_raises():
    m, _, _ = seeded(early_exit_threshold=0.6)
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        _engine(m)


def test_the_cached_generate_path_refuses_the_family(tiny):
    m, _, _ = tiny
    p = _decode_params(m)
    assert p["family"] == "looped"
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


def _pin_engine():
    """The toy engine whose step programs `test_step_program_pins` pins."""
    from paddle_tpu.models.ouro import OuroForCausalLM, ouro_tiny_config
    paddle.seed(0)
    m = OuroForCausalLM(ouro_tiny_config(max_position_embeddings=64,
                                         rope_positions=64))
    m.eval()
    return ServingEngine(m, max_slots=2, page_size=8, max_context=64,
                         prefill_chunk=8)
