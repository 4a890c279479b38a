"""The serving step owns its KV page pools (ISSUE 29): the jitted
programs are built with the pools donated, so the arrays a launch was
handed are dead once it is dispatched and `ServingEngine._pools` are the
ones it returned.  Pinned here, on the CPU (jax honours donation there):
the ownership itself and the step record's `pools_in_place`; that every
reader of the pools between launches (copy-on-write, handoff export and
import, preemption, a rolled-back draft, `reconfigure()`) goes through
the live handle, by token sequences bit-identical to the same engine
with programs that do NOT own their pools (the behaviour before); and
what the engine does after a launch that raised."""

import functools

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.block_allocator import ChunkSummaryAllocator
from paddle_tpu.serving.scheduler import DECODE
from test_engine_programs import _laguna, _tiny

FAMILIES = ("llama", "gpt", "mla")
ARGS = dict(max_slots=2, page_size=4, prefill_chunk=4)


def _structure(name):
    """(a toy model whose engine keeps pools of another structure than a
    (K, V) pair a layer, the engine arguments it asks for, what says
    that an engine holds that structure)."""
    paddle.seed(0)
    if name == "laguna":        # two kinds of pool: every page / a window's
        return (_laguna(),
                dict(max_slots=2, page_size=8, max_context=64,
                     prefill_chunk=8),
                lambda eng: eng.num_window_pages > 0 and len(
                    {kp.shape[1] for kp, _ in eng._pools}) == 2)
    if name == "eva":           # one pool, two page lists a sequence
        from paddle_tpu.models.evabyte import (EvaByteForCausalLM,
                                               evabyte_tiny_config)
        m = EvaByteForCausalLM(evabyte_tiny_config())
        args = dict(max_slots=2, page_size=8, max_context=256,
                    prefill_chunk=8, num_pages=64)

        def holds(eng):
            return isinstance(eng.allocator, ChunkSummaryAllocator) \
                and len(eng._pools) == len(eng._p["layers"])
    elif name == "looped":      # a page id names a page of every pass
        from paddle_tpu.models.ouro import OuroForCausalLM, ouro_tiny_config
        m = OuroForCausalLM(ouro_tiny_config())
        args = dict(max_slots=2, page_size=8, max_context=128,
                    prefill_chunk=8, num_pages=24)

        def holds(eng):
            return eng._passes > 1 and all(
                kp.shape[1] == eng._passes * eng.num_pages
                for kp, _ in eng._pools)
    else:                       # pages beside slot-indexed states
        from paddle_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                                  nemotron_h_tiny_config)
        m = NemotronHForCausalLM(nemotron_h_tiny_config())
        args = dict(max_slots=2, page_size=8, max_context=128,
                    prefill_chunk=16, num_pages=40)

        def holds(eng):
            return set(eng._pools) == {"kv", "ssm"} \
                and len(eng._pools["ssm"]) > 0
    m.eval()
    return m, args, holds


@pytest.fixture(scope="module")
def models():
    return functools.cache(_tiny)


def _engine(model, **kw):
    return ServingEngine(model, **dict(ARGS, **kw))


def _without_ownership(eng):
    """The same engine with programs that do not own their pools — the
    programs `_build_programs` built before ISSUE 29 — now and after
    every `reconfigure()`."""
    rebuild = eng._build_programs

    def build():
        rebuild()
        eng._programs.update(
            ("unified" + sfx, jax.jit(eng._make_unified_body(chunk)))
            for sfx, chunk in eng._chunk_parts().items())
    eng._build_programs = build
    build()
    return eng


def _records(eng):
    return tracing.recorder().steps()[-eng.steps:]


@pytest.mark.parametrize(
    "family", FAMILIES + ("laguna", "eva", "looped", "hybrid"))
def test_a_step_consumes_the_pools_it_was_handed(models, family):
    m, args, holds = (models(family), {}, None) if family in FAMILIES \
        else _structure(family)
    eng = _engine(m, **args)
    assert holds is None or holds(eng)
    eng.add_request(np.arange(1, 10, dtype=np.int32), max_new_tokens=4)
    launched = []
    while eng.has_work():
        handed = jax.tree.leaves(eng._pools)
        before = eng.launches
        eng.step()
        launched.append(eng.launches - before)
        now = jax.tree.leaves(eng._pools)
        if not launched[-1]:
            # the unified engine's last call only reads back the launch
            # it had queued (ISSUE 34): nothing was handed to anyone
            assert all(a is b for a, b in zip(now, handed))
            continue
        assert all(a.is_deleted() for a in handed)
        assert len(now) == len(handed)
        assert not any(a.is_deleted() for a in now)
        assert {a.shape for a in now} == {a.shape for a in handed}
    recs = _records(eng)
    assert sum(launched) == eng.launches and launched[0] >= 1
    assert [r["pools_in_place"] for r in recs] == \
        [int(n > 0) for n in launched]
    # the pools are readable through the live handle, and hold the run
    assert float(abs(np.asarray(now[0], np.float32)).sum()) > 0
    # an idle step launches nothing, so nothing was updated in place
    eng.step()
    assert _records(eng)[-1]["pools_in_place"] == 0


def _scenario(eng, vocab):
    """A seeded run through every reader and writer of the pools between
    launches; returns ({request: tokens}, what happened on the way)."""
    rng = np.random.RandomState(29)
    out = {}

    def steps(n=1):
        for _ in range(n):
            eng.step()
            out.update(eng.collect())

    # a periodic prompt, so the n-gram drafter proposes and the model,
    # which knows nothing of the period, rejects: drafts are rolled back
    base = np.tile(np.asarray([7, 11, 3], np.int32), 4)[:10]
    a = eng.add_request(base, max_new_tokens=12, request_id="a")
    while a.state != DECODE or len(a.tokens) < 2:   # 3 pages and on
        steps()
    # forks "a"'s first 6 tokens: its second page is shared in part, so
    # "b"'s first own row copies it on write (`_apply_copies`)
    eng.add_request(
        np.concatenate([base[:6], rng.randint(0, vocab, 4)]).astype(
            np.int32), max_new_tokens=10, request_id="b")
    steps(2)
    # between two steps "a" leaves through a handoff and comes back
    eng.import_request(eng.export_request(a))
    steps()
    eng.reconfigure(prefill_chunk=8, spec_decode=1)
    steps()
    # both slots are taken: the high-priority arrival preempts one
    eng.add_request(rng.randint(0, vocab, 5).astype(np.int32),
                    max_new_tokens=4, request_id="c", priority=2)
    while eng.has_work():
        steps()
    recs = _records(eng)
    happened = {
        "cow_pages": sum(r["cow_pages"] for r in recs),
        "preempted": sum(r["preempted"] for r in recs),
        "handoffs": dict(eng._handoff_counts), "rebuilds": eng.rebuilds,
        "drafts_rolled_back": eng.spec_drafted - eng.spec_accepted,
        "in_place": {r["pools_in_place"] for r in recs
                     if r["decode_rows"] + r["prefill_rows"]}}
    return {k: np.asarray(v) for k, v in out.items()}, happened


@pytest.mark.parametrize("family", FAMILIES)
def test_tokens_are_those_of_programs_that_do_not_own_the_pools(
        models, family):
    m = models(family)
    V = m.config.vocab_size
    got, did = _scenario(_engine(m, spec_decode=2), V)
    want, before = _scenario(
        _without_ownership(_engine(m, spec_decode=2)), V)
    assert set(got) == {"a", "b", "c"} == set(want)
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])
    # the run did what it says, on both sides alike
    assert did["cow_pages"] >= 1 and did["preempted"] >= 1
    assert did["handoffs"] == {"export": 1, "import": 1}
    assert did["rebuilds"] == 1
    assert did["drafts_rolled_back"] >= 1
    assert did["in_place"] == {1} and before["in_place"] == {0}
    did.pop("in_place"), before.pop("in_place")
    assert did == before


def test_a_launch_that_took_the_pools_and_raised_ends_the_engine(models):
    """Chosen behaviour (CHANGES.md, PR 29): the pools are not rebuilt —
    their contents, every live request's cache, are gone with them — so
    the engine says that it cannot run again, at the next step and at
    every other reader of the pools."""
    eng = _engine(models("llama"))
    req = eng.add_request(np.arange(1, 8, dtype=np.int32), max_new_tokens=6)
    while req.state != DECODE:
        eng.step()
    program = eng._programs["unified_nochunk"]  # a launch of decode rows

    def took_them_then_raised(w, tok, pools, *tables):
        for a in jax.tree.leaves(pools):
            a.delete()
        raise RuntimeError("device fault")

    eng._programs["unified_nochunk"] = took_them_then_raised
    with pytest.raises(RuntimeError, match="device fault"):
        eng.step()
    eng._programs["unified_nochunk"] = program
    for use in (eng.step, lambda: eng.export_request(req)):
        with pytest.raises(RuntimeError, match="pools were lost"):
            use()


def test_a_launch_that_raised_before_it_took_the_pools_keeps_them(models):
    eng = _engine(models("llama"))
    eng.add_request(np.arange(1, 8, dtype=np.int32), max_new_tokens=3)
    program = eng._programs["unified"]

    def refused(*args):
        raise ValueError("refused before dispatch")

    eng._programs["unified"] = refused
    handed = jax.tree.leaves(eng._pools)
    with pytest.raises(ValueError, match="refused before dispatch"):
        eng.step()
    eng._programs["unified"] = program
    kept = jax.tree.leaves(eng._live_pools())
    assert len(kept) == len(handed)
    assert all(a is b for a, b in zip(kept, handed))
    assert not any(a.is_deleted() for a in handed)


def test_a_copy_on_write_compiles_nothing_when_it_comes(models):
    """Two random prompts that share their FIRST token fork a page, and
    the second one's first row copies it on write. The copy is the one
    fixed-shape program `_build_programs` built and ran once: when it
    comes, mid-serving, nothing compiles (a compile inside a measured
    window makes a benchmark run incorrect: PERF.md, PR 33)."""
    m = models("llama")
    eng = _engine(m)
    rng = np.random.RandomState(5)
    V = m.config.vocab_size
    first = rng.randint(0, V, 12).astype(np.int32)
    eng.add_request(first, max_new_tokens=8, request_id="donor")
    for _ in range(5):      # the step is compiled here, at both row counts
        eng.step()
    assert all(n == 1 for n in eng.program_cache_sizes().values())
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    rider = np.concatenate([first[:1], rng.randint(0, V, 6)]).astype(np.int32)
    assert rider[1] != first[1]
    eng.add_request(rider, max_new_tokens=4, request_id="rider")
    while eng.has_work():
        eng.step()
    eng.collect()
    assert sum(r["cow_pages"] for r in _records(eng)) >= 1
    assert compiles == []
