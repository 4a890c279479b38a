"""The LFM2-MoE hybrid through `ServingEngine`, WITH the prefix cache.

The engine — chunked prefill whose chunks carry the convolutions' tails
across chunk and page borders, then decode through the tail pool —
against the plain float32 reference's full forward
(`benchmarks/lib/reference_lfm2.py`) on seeded weights, in logits; and
the adoption of a cached prefix at a page border from the snapshot of the
tails at its last row: cache on against cache off, one, two and five
pages, two adopters side by side, eviction and a re-miss, a page id
reused by another prefix; what the family still refuses; the step
record's counts. (The step programs' pinned texts:
`test_step_program_pins.py`.)
"""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference_lfm2 as ref
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import _pattern_blocks
from test_lfm2 import seeded

PAGE, CHUNK = 8, 16         # a prefill chunk is two pages
PROGRAMS = {"unified": 1, "feed": 1, "unified_nochunk": 1,
            "feed_nochunk": 1}


def _engine(m, **kw):
    args = dict(max_slots=3, page_size=PAGE, max_context=128,
                prefill_chunk=CHUNK, num_pages=60)
    args.update(kw)
    return ServingEngine(m, **args)


@pytest.fixture(scope="module")
def tiny():
    return seeded()


@pytest.fixture(scope="module")
def eng(tiny):
    """THE engine of this module, the prefix cache on (the default): the
    tests that only serve share it, each with prompts of its own seed."""
    return _engine(tiny[0])


@pytest.fixture(scope="module")
def small(tiny):
    """... and one whose pool holds two prompts' pages, for eviction."""
    return _engine(tiny[0], num_pages=14)


def _run(eng, prompts, max_new, stagger=0):
    """Each request's handle, tokens and the logits rows they were taken
    from; `stagger` steps between two arrivals."""
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
    return [(h, np.asarray(h.tokens, np.int32), np.stack(rows[h.request_id]))
            for h in handles]


def _reference(w, c, prompt, tokens):
    """The float32 logits at the positions the tokens were generated
    from, teacher-forced over prompt + tokens."""
    fed = jnp.asarray(np.concatenate([prompt, tokens[:-1]]), jnp.int32)
    return np.asarray(ref.logits(fed, w, c))[len(prompt) - 1:]


def _ids(seed, *lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, n).astype(np.int32) for n in lens]


def _exact(tiny, served, prompts):
    _, w, c = tiny
    for p, (_, tokens, got) in zip(prompts, served):
        want = _reference(w, c, p, tokens)
        assert got.shape == want.shape == (len(tokens), 96)
        np.testing.assert_allclose(got, want, atol=5e-4)


# ----------------------------------------------------------- the engine
#: prompts of several chunks (the tails cross chunk borders at 16, 32
#: and page borders at every 8) and of less than one, decode across page
#: borders, three unlike sequences in one launch
CASES = {"chunks_then_decode": ([37], [14]),
         "unlike_lengths": ([19, 5, 33], [9, 12, 7]),
         "one_token_prompt": ([1, 30], [10, 4]),
         "whole_chunks": ([32, 16], [5, 9])}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_logits_match_the_reference(tiny, eng, case):
    lens, new = CASES[case]
    prompts = _ids(3 + len(case), *lens)
    assert eng._family == "hybrid" and eng._state_kind == "C"
    assert eng.prefix_cache is not None and eng._tail_snapshots
    _exact(tiny, _run(eng, prompts, new), prompts)
    assert eng.program_cache_sizes() == PROGRAMS


def test_a_slot_goes_from_a_finished_request_to_a_new_one(tiny):
    """Two slots, four requests, the cache off: the third and fourth take
    over the slots — and the tails, which a flag in the row tables zeroes
    on the device — of the first two, and every logit matches."""
    m = tiny[0]
    eng = _engine(m, max_slots=2, enable_prefix_cache=False)
    assert eng.prefix_cache is None and not eng._tail_snapshots
    assert [len(e) for e in eng._pools["ssm"]] == [1] * 5
    prompts = _ids(4, 20, 9, 13, 27)
    _exact(tiny, _run(eng, prompts, [6, 11, 9, 5]), prompts)
    assert eng.program_cache_sizes() == PROGRAMS


def test_heads_of_64_lanes_are_stored_in_pairs():
    """A published head of 64 lanes (the cell's) is half a register: the
    pool's KV head is TWO of them side by side — 4 query heads over 2 KV
    heads become 4 over ONE paired row of 128 — and chunks, decode and an
    adoption are as exact as at any other width."""
    tiny = seeded(hidden_size=256)
    eng = _engine(tiny[0])
    assert eng._kv_geom == (1, 128) and eng._q_rep == 4
    assert eng._pools["kv"][0][0].shape == (1, 60, PAGE, 128)
    assert eng._chain.blocks[4].attn["pack"] == 2
    prompts = _ids(71, 37, 19, 5)
    _exact(tiny, _run(eng, prompts, [9, 6, 11]), prompts)
    X, b = _ids(72, 45, 9)
    _run(eng, [X], [2])
    prompt = np.concatenate([X[:32], b])
    served = _run(eng, [prompt], [5])
    assert served[0][0].shared_tokens == 32
    _exact(tiny, served, [prompt])
    assert eng.program_cache_sizes() == PROGRAMS


# ------------------------------------------------------------ adoptions
def test_cache_on_is_cache_off_with_fewer_prefill_rows(tiny, eng):
    """The same two prompts of one 32-token prefix, one after the other,
    with the cache and without: the same logits within float32 noise,
    and the second prompt prefills 32 rows fewer."""
    m = tiny[0]
    X, a, b = _ids(11, 32, 7, 13)
    prompts = [np.concatenate([X, a]), np.concatenate([X, b])]
    out, rows = {}, {}
    for name, e in (("on", eng),
                    ("off", _engine(m, enable_prefix_cache=False))):
        at = e.steps
        out[name] = [_run(e, [p], [6])[0] for p in prompts]
        recs = tracing.recorder().steps()[-(e.steps - at):]
        rows[name] = sum(r["prefill_rows"] for r in recs)
        # (an engine without the cache has no such count)
        assert ("prefix_tokens_adopted" in recs[-1]) == (name == "on")
        adopted = sum(r.get("prefix_tokens_adopted", 0) for r in recs)
        assert adopted == (32 if name == "on" else 0)
    assert [h.shared_tokens for h, _, _ in out["on"]] == [0, 32]
    assert [h.shared_tokens for h, _, _ in out["off"]] == [0, 0]
    assert rows["off"] - rows["on"] == 32
    for (_, t0, l0), (_, t1, l1) in zip(out["on"], out["off"]):
        np.testing.assert_array_equal(t0, t1)
        np.testing.assert_allclose(l0, l1, atol=2e-5)
    _exact(tiny, out["on"], prompts)


@pytest.mark.parametrize("pages", [1, 2, 5])
def test_an_adoption_at_a_page_border_is_exact(tiny, eng, pages):
    """A donor's 45-token prompt is cached; a prompt that shares `pages`
    whole pages of it (and ONE token more, which a page-granular trie
    cannot share) adopts exactly those, reads the tails at their last
    row from the snapshot, and matches the reference's full forward —
    its first two rows read the snapshot directly."""
    X, b = _ids(20 + pages, 45, 9)
    _run(eng, [X], [2])
    cut = pages * PAGE
    prompt = np.concatenate([X[:cut + 1], b])
    prompt[cut + 1] = (X[cut + 1] + 1) % 96         # ... and then differs
    at = eng.steps
    served = _run(eng, [prompt], [5])
    assert served[0][0].shared_tokens == cut
    recs = tracing.recorder().steps()[-(eng.steps - at):]
    assert sum(r["prefix_pages_adopted"] for r in recs) == pages
    assert sum(r["tail_restores"] for r in recs) == 1
    _exact(tiny, served, [prompt])


def test_two_adopters_of_one_prefix_in_one_window(tiny, eng):
    X, a, b, c = _ids(31, 24, 5, 2, 11)
    _run(eng, [np.concatenate([X, a])], [2])
    prompts = [np.concatenate([X, b]), np.concatenate([X, c])]
    served = _run(eng, prompts, [7, 4])
    assert [h.shared_tokens for h, _, _ in served] == [24, 24]
    _exact(tiny, served, prompts)
    assert eng.program_cache_sizes() == PROGRAMS


def _trie_pages(eng, prompt):
    m = eng.prefix_cache.lookup(prompt)
    pages = list(m.pages)
    m.release()
    return pages


def test_eviction_then_a_re_miss_is_still_exact(tiny, small):
    """13 usable pages, five of a 40-token prompt stay in the trie: the
    third and fourth prompts evict the first's pages, leaves first; the
    first prompt's prefix then MISSES and is prefilled whole again,
    exactly."""
    A, B, C, D, tail = _ids(41, 40, 40, 40, 40, 6)
    at = small.steps
    for X in (A, B, C, D):
        _run(small, [X], [3])
    C = D
    assert small.prefix_cache.match_length(np.append(A, 0)) == 0
    assert small.prefix_cache.match_length(np.append(C, 0)) == 40
    prompt = np.concatenate([A[:32], tail])
    served = _run(small, [prompt], [5])
    assert served[0][0].shared_tokens == 0
    recs = tracing.recorder().steps()[-(small.steps - at):]
    assert sum(r["prefix_pages_evicted"] for r in recs) > 0
    _exact(tiny, served, [prompt])


def test_a_reused_page_id_gives_the_new_prefixs_tails(tiny, small):
    """Prefix P's pages are evicted and their ids handed to prefix Q,
    whose chunks overwrite the snapshots before the trie takes the pages:
    an adopter of Q reads Q's tails under P's old ids."""
    P, F, Q, tail = _ids(43, 40, 40, 40, 4)
    _run(small, [P], [3])
    old = set(_trie_pages(small, np.append(P, 0)))
    assert len(old) == 5
    _run(small, [F], [3])
    _run(small, [Q], [3])
    new = _trie_pages(small, np.append(Q, 0))
    assert len(new) == 5 and set(new) & old
    prompt = np.concatenate([Q[:32], tail])
    served = _run(small, [prompt], [6])
    assert served[0][0].shared_tokens == 32
    _exact(tiny, served, [prompt])
    # ... and without the snapshot, or with the snapshot of the page
    # BEFORE, it would NOT have matched: in float32 both faults show
    # (on the chip they are a flipped expert's worth: PERF.md section 7)
    _, w, c = tiny
    toks = served[0][1]
    fed = jnp.asarray(np.concatenate([prompt, toks[:-1]]), jnp.int32)
    for fault in ref.ADOPTION_ABLATIONS:
        off = np.asarray(ref.logits(fed, w, c, ablate=frozenset([fault]),
                                    cut=32, page=PAGE))[len(prompt) - 1:]
        assert np.abs(off - served[0][2]).max() > 100 * 5e-4, fault


# ------------------------------------------------- counts and accounting
def test_the_step_record_counts_the_tails(tiny, eng):
    at = eng.steps
    X = _ids(51, 45)[0]
    _run(eng, [X], [4])
    recs = [r for r in tracing.recorder().steps()[-(eng.steps - at):]
            if r.get("ssm_slots_live")]
    assert recs and all(k in recs[-1] for k in tracing.STEP_COUNTS_TAIL
                        + tracing.STEP_COUNTS_PREFIX)
    assert all(k in recs[-1] for k in tracing.STEP_COUNTS_SSM
               + tracing.STEP_COUNTS_MOE)
    for r in recs:
        # tail only: no state held, none moved
        assert r["ssm_state_bytes"] == 0 == r["ssm_state_bytes_moved"]
        assert r["tail_bytes"] == 2 * 64 * 4
    # 45 tokens in chunks of 16: 2 + 2 + 1 whole pages
    assert sum(r["tail_snapshots_written"] for r in recs) == 5
    assert sum(r["ssm_state_resets"] for r in recs) == 1
    assert all(r["pools_in_place"] == 1 for r in recs[:-1])


def test_the_bytes_the_engine_says_it_holds(tiny, eng):
    acct = eng.hbm_accounting()
    tail = 2 * 64 * 4
    assert acct["state_pool_bytes"] == 5 * 4 * tail
    assert acct["tail_snapshot_bytes"] == 5 * 60 * tail
    pages = 1 * 2 * 2 * 60 * PAGE * 16 * 4      # ONE attention block
    assert acct["page_pool_bytes"] == pages + 5 * (4 + 60) * tail
    assert [tuple(a.shape) for a in eng._pools["ssm"][0]] \
        == [(4, 2, 64), (60, 2, 64)]
    assert len(eng._pools["kv"]) == 1 and len(eng._pools["ssm"]) == 5


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("kw, why", [
    (dict(spec_decode=2), "spec_decode must be 0"),
    (dict(role="prefill"), "role must be 'colocated'"),
    (dict(role="decode"), "role must be 'colocated'")])
def test_what_needs_a_cut_at_a_token_is_refused(tiny, kw, why):
    with pytest.raises(ValueError, match="5 short-convolution blocks") as e:
        _engine(tiny[0], **kw)
    assert why in str(e.value)


def test_a_chunk_that_is_not_whole_pages_is_refused(tiny):
    with pytest.raises(ValueError, match="prefill_chunk 12 must be whole "
                                         "pages of 8"):
        _engine(tiny[0], prefill_chunk=12)
    # ... with the cache on only: without it no snapshot is taken
    off = _engine(tiny[0], prefill_chunk=12, enable_prefix_cache=False)
    assert off.prefix_cache is None


def test_live_donors_and_preemption_are_off_and_a_handoff_raises(tiny, eng):
    """`prefix_sharing` (a live donor shares at a TOKEN) and `preemption`
    are forced off whatever is asked; two equal prompts side by side
    share nothing until the first is cached."""
    asked = _engine(tiny[0], prefix_sharing=True, preemption=True,
                    enable_prefix_cache=False)
    for e in (eng, asked):
        assert e.prefix_sharing is False and e.preemption is False
    same = _ids(61, 17)[0]
    a = asked.add_request(same, max_new_tokens=4)
    asked.step(), asked.step()
    b = asked.add_request(same, max_new_tokens=4, priority=5)
    while asked.has_work():
        asked.step()
    assert b.shared_tokens == 0 and list(a.tokens) == list(b.tokens)
    with pytest.raises(NotImplementedError, match="convolution tails"):
        eng.export_request(a)
    with pytest.raises(ValueError, match="spec_decode stays 0"):
        eng.reconfigure(spec_decode=2)
    with pytest.raises(ValueError, match="whole pages"):
        eng.reconfigure(prefill_chunk=12)


def test_a_mix_with_a_recurrent_state_is_not_a_finite_history():
    assert _pattern_blocks("CD*E") == ("C", "D", "*", "E")
    with pytest.raises(ValueError, match="one letter of MKSC"):
        _pattern_blocks("[C*]D")
