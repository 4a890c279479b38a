"""The unified step keeps ONE launch queued ahead (ISSUE 34): call k of
`ServingEngine.step()` builds and dispatches launch k before it reads
the result of launch k-1, the decode rows of launch k take their input
token from launch k-1's greedy tokens ON THE DEVICE, and what `step()`
returns and a `Request` shows is retired work.

Pinned here, on the CPU, at toy sizes of the three families the
benchmark serves (a dense GQA decoder, Laguna's two page kinds, A.X-K1's
latent rows):

- exactness under every event the queue changes, over one schedule:
  staggered arrivals, a prompt of several chunks ending while others
  decode, a finish by `max_new_tokens` whose slot the next arrival
  takes, a finish by EOS (seen one launch late: one row computed and
  dropped, its pages back in the pool at once, with the prefix cache
  and a fork in play), two live prompts that share their first token
  (a copy-on-write with a launch in flight), a preemption, a handoff
  out and back between two steps, `reconfigure` on a live engine, and
  speculative decoding (depth 0). The oracles: solo greedy
  `generate_cached`; the SAME engine read back after every step (the
  queue always empty: every token then comes from the host), bit for
  bit in tokens and logits; and each family's plain reference under the
  limit its own test file holds it to;
- no program is compiled after the warm-up and every program keeps one
  cache entry, after every step;
- the order itself, from the step records."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import test_axk1_serving as axk1_t
import test_engine_reference as llama_t
import test_laguna_serving as laguna_t
from paddle_tpu.generation import generate_cached
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.scheduler import DECODE, FINISHED
from test_engine_programs import _tiny

PHASES = ["serving.engine." + p for p in
          ("admit", "build", "launch", "sync", "sample", "account")]


def _laguna():
    from paddle_tpu.models.laguna import (LagunaForCausalLM,
                                          laguna_tiny_config)
    paddle.seed(0)
    m = LagunaForCausalLM(laguna_tiny_config(experts_held=(4, 4)))
    m.eval()
    return m


def _axk1():
    from paddle_tpu.models.axk1 import AXK1ForCausalLM, axk1_tiny_config
    paddle.seed(0)
    m = AXK1ForCausalLM(axk1_tiny_config(experts_held=(4, 4)))
    m.eval()
    for lyr in m.model.layers:      # as tests/test_axk1_serving.py
        w = lyr.self_attn.q_b_proj.weight
        w._data = w._data * 4.0
        if hasattr(lyr.mlp, "gate_weight"):
            g = lyr.mlp.gate_weight
            g._data = g._data * 20.0
    return m


#: family -> (model, engine arguments, vocabulary, has a window)
CASES = {
    "llama": (lambda: _tiny("llama"), dict(
        max_slots=3, page_size=4, prefill_chunk=4, max_context=64), False),
    "mistral_bf16": (llama_t._model, dict(
        max_slots=3, page_size=8, prefill_chunk=16, max_context=128), False),
    "laguna": (_laguna, dict(
        max_slots=3, page_size=8, prefill_chunk=16, max_context=256,
        num_pages=70), True),
    "axk1": (_axk1, dict(
        max_slots=3, page_size=8, prefill_chunk=16, max_context=128,
        num_pages=40), False),
}


@pytest.fixture(scope="module")
def models():
    built = {}

    def get(family):
        if family not in built:
            built[family] = CASES[family][0]()
        return built[family]
    return get


def _vocab(m):
    return min(int(m.config.vocab_size), 256)


class Run:
    """What a schedule left behind."""

    def __init__(self, eng, prompts, tokens, rows, records, free0):
        self.eng, self.prompts, self.tokens = eng, prompts, tokens
        self.rows, self.records, self.free0 = rows, records, free0

    def total(self, key):
        return sum(r[key] for r in self.records)


def _schedule(m, family, eos_of=None, read_back_every_step=False,
              **engine):
    """The one schedule, on a fresh engine. `read_back_every_step`
    makes the twin whose queue is always empty."""
    args, windowed = dict(CASES[family][1]), CASES[family][2]
    args.update(engine)
    C, V = args["prefill_chunk"], _vocab(m)
    rng = np.random.default_rng(34)

    def prompt(n):
        return rng.integers(1, V, n, dtype=np.int32)

    long = prompt(2 * C + C // 2 + 1)           # three chunks
    plan = {        # call -> [(id, prompt, max_new, more)]
        0: [("long", long, 7, {}), ("short", prompt(C // 2), 3, {})],
        1: [("eos", prompt(C + 2), 8, {"eos_token_id": eos_of})],
        2: [("late", prompt(C // 2 + 1), 4, {})],
        # shares its FIRST token with the live "long": a fork whose
        # first own row copies the shared page on write
        5: [("rider", np.concatenate([long[:1], prompt(C // 2 + 2)]), 4,
             {})],
        9: [("tail", prompt(C + 1), 3, {})],
    }
    vip = prompt(3)
    eng = ServingEngine(m, **args)
    assert eng.ragged
    # the warm-up: every program the loop can reach has run once
    eng.add_request(prompt(C + 1), max_new_tokens=2, request_id="warm")
    eng.run_to_completion()
    if eng.prefix_cache is not None:
        eng.prefix_cache.evict(10 ** 6)
    free0 = eng.allocator.free_pages
    rows = {}
    eng.on_logits = lambda req, row: rows.setdefault(
        req.request_id, []).append(np.asarray(row, np.float32))
    reqs, prompts, seq0, call = {}, {"vip": vip}, eng.steps, 0
    moved = reconfigured = False
    while plan or eng.has_work():
        for rid, p, n, more in plan.pop(call, ()):
            prompts[rid] = p
            reqs[rid] = eng.add_request(p, max_new_tokens=n,
                                        request_id=rid, **more)
        if "vip" not in reqs and call >= 4 and eng.scheduler.active(
                DECODE) and eng.scheduler.inflight == eng.max_slots:
            # every slot is taken: it preempts a decode of priority 0
            reqs["vip"] = eng.add_request(vip, max_new_tokens=3,
                                          request_id="vip", priority=2)
        eng.step()
        if read_back_every_step:
            eng.retire()
        call += 1
        assert all(v <= 1 for v in eng.program_cache_sizes().values())
        a = reqs.get("long")
        if not windowed and not moved and a is not None \
                and a.state == DECODE and len(a.tokens) >= 2:
            # between two steps "long" leaves through a handoff and
            # comes back: the payload holds every row that was queued
            reqs["long"] = eng.import_request(eng.export_request(a))
            moved = True
        if call == 12 and not reconfigured:
            reconfigured = eng.reconfigure(prefill_chunk=C // 2)
        assert call < 400
    eng.collect()
    tokens = {rid: np.asarray(r.tokens) for rid, r in reqs.items()}
    assert all(r.state == FINISHED for r in reqs.values())
    assert moved or windowed
    assert reconfigured
    records = tracing.recorder().steps()[-(eng.steps - seq0):]
    return Run(eng, prompts, tokens,
               {k: np.stack(v) for k, v in rows.items()}, records, free0)


def _eos_token(m, family):
    """A token "eos" makes when nothing stops it, new where it comes
    (so the finish by EOS comes exactly there): (token, its index, the
    run without an EOS)."""
    run = _schedule(m, family, read_back_every_step=True)
    toks = run.tokens["eos"]
    assert len(toks) == 8
    at = next(j for j in range(1, 7) if toks[j] not in toks[:j])
    return int(toks[at]), at, run


@pytest.fixture(scope="module")
def runs(models):
    """family -> (the run with the queue, its twin read back after every
    step, the run without an EOS), each made once."""
    made = {}

    def get(family):
        if family not in made:
            m = models(family)
            eos, at, free_running = _eos_token(m, family)
            free_running.eos_at = at
            made[family] = (
                _schedule(m, family, eos_of=eos),
                _schedule(m, family, eos_of=eos,
                          read_back_every_step=True), free_running)
        return made[family]
    return get


FAMILIES = list(CASES)


@pytest.mark.parametrize("family", FAMILIES)
def test_tokens_and_logits_are_those_of_the_empty_queue(runs, family):
    ahead, twin, free_running = runs(family)
    assert set(ahead.tokens) == set(twin.tokens) == {
        "long", "short", "eos", "late", "rider", "vip", "tail"}
    # a routed layer's sums follow the rows that ride together, and the
    # twin's launches are made up otherwise: float32 noise there, the
    # same bits in the dense families
    atol = {"laguna": laguna_t.ATOL, "axk1": axk1_t.ATOL}.get(family, 0)
    for rid in twin.tokens:
        np.testing.assert_array_equal(ahead.tokens[rid], twin.tokens[rid])
        np.testing.assert_allclose(ahead.rows[rid], twin.rows[rid],
                                   atol=atol, rtol=0)
        assert np.array_equal(ahead.rows[rid].argmax(-1),
                              ahead.tokens[rid])
    # the finish by EOS came where the token first shows, by max_new
    # elsewhere
    n = free_running.eos_at + 1
    assert len(ahead.tokens["eos"]) == n < 8
    np.testing.assert_array_equal(ahead.tokens["eos"],
                                  free_running.tokens["eos"][:n])
    assert [len(ahead.tokens[k]) for k in
            ("long", "short", "late", "rider", "vip", "tail")] == \
        [7, 3, 4, 4, 3, 3]
    # the queue was in use, and the twin's never
    assert ahead.total("launch_ahead") >= len(ahead.records) - 6
    assert twin.total("launch_ahead") == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_the_events_the_queue_changes_all_happened(runs, family):
    ahead, twin, _ = runs(family)
    windowed = CASES[family][2]
    recs = ahead.records
    # ONE row was computed for a request that had ended (the EOS seen a
    # launch late), in the ahead run only
    assert ahead.total("rows_dropped") == 1
    assert twin.total("rows_dropped") == 0
    assert ahead.total("preempted") >= 1
    if not windowed:
        # a copy-on-write with a launch in flight
        assert any(r["cow_pages"] and r["launch_ahead"] for r in recs)
        assert ahead.eng._handoff_counts == {"export": 1, "import": 1}
    assert ahead.eng.rebuilds == 1
    # every page went back: the dropped row's too
    for run in (ahead, twin):
        eng = run.eng
        if eng.prefix_cache is not None:
            eng.prefix_cache.evict(10 ** 6)
        assert eng.allocator.free_pages == run.free0
        st = eng.allocator.stats()
        assert st["pages_used"] == 0
        assert st.get("window_pages_used", 0) == 0
        assert eng._inflight is None and not eng.has_work()


@pytest.mark.parametrize("family", FAMILIES)
def test_nothing_compiles_after_the_warm_up(models, family):
    """Every device program the loop can reach was built and run once
    by the warm-up (`_build_programs` + the first launch): the schedule
    without its handoff and `reconfigure` (whose eager page copies and
    rebuilt programs do compile, and which no cell runs) compiles
    nothing, step by step, and every program keeps one cache entry."""
    m = models(family)
    args = CASES[family][1]
    C, V = args["prefill_chunk"], _vocab(m)
    rng = np.random.default_rng(5)
    eng = ServingEngine(m, **args)
    eng.add_request(rng.integers(1, V, C + 1, dtype=np.int32),
                    max_new_tokens=2)
    eng.run_to_completion()
    sizes = eng.program_cache_sizes()
    assert sizes == {
        "unified": 1, "feed": 1, "unified_nochunk": 1, "feed_nochunk": 1}
    first = rng.integers(1, V, 2 * C + 3, dtype=np.int32)
    eng.add_request(first, max_new_tokens=6)
    seq0 = eng.steps
    for call in range(200):
        if call == 3:       # a fork and its copy-on-write
            eng.add_request(np.concatenate(
                [first[:1], rng.integers(1, V, C // 2, dtype=np.int32)]),
                max_new_tokens=3)
        if call == 5:
            eng.add_request(rng.integers(1, V, 3, dtype=np.int32),
                            max_new_tokens=2, eos_token_id=None)
        if not eng.has_work():
            break
        eng.step()
        assert eng.program_cache_sizes() == sizes
    recs = tracing.recorder().steps()[-(eng.steps - seq0):]
    assert [r["compiles"] for r in recs] == [0] * len(recs)
    if not CASES[family][2]:
        assert sum(r["cow_pages"] for r in recs) >= 1


def test_tokens_are_solo_greedy(models, runs):
    """The float32 toy llama: every stream of the schedule is the solo
    `generate_cached` stream (cut at the EOS)."""
    m = models("llama")
    ahead, _, _ = runs("llama")
    for rid, p in ahead.prompts.items():
        got = ahead.tokens[rid]
        want, _ = generate_cached(m, paddle.to_tensor(p[None]),
                                  max_new_tokens=8 if rid == "eos"
                                  else len(got),
                                  decode_strategy="greedy_search")
        np.testing.assert_array_equal(got, want.numpy()[0][:len(got)])


@pytest.mark.parametrize("family", ["mistral_bf16", "laguna", "axk1"])
def test_logits_stay_within_the_reference_limit(models, runs, family):
    """The rows the queued engine sampled from, against the family's
    plain reference, under the limit the family's own test file holds
    the engine to."""
    m = models(family)
    ahead, _, _ = runs(family)
    for rid in ("long", "short", "eos"):
        p = ahead.prompts[rid]
        toks, rows = ahead.tokens[rid], ahead.rows[rid]
        if family == "mistral_bf16":
            ref = llama_t.ref
            w = llama_t._weights(m, ahead.eng)
            with ref.highest():
                f32 = llama_t._reference_rows(m, w, p, toks, jnp.float32)
            b16 = llama_t._reference_rows(m, w, p, toks, jnp.bfloat16)
            got, noise = llama_t._rms(rows - f32), llama_t._rms(b16 - f32)
            assert 0 < got <= llama_t.LIMIT * noise, (rid, got, noise)
        elif family == "laguna":
            np.testing.assert_allclose(
                rows, laguna_t._reference_rows(m, p, toks),
                atol=laguna_t.ATOL, rtol=0)
        else:
            np.testing.assert_allclose(
                rows, axk1_t._reference_rows(m, p, toks),
                atol=axk1_t.ATOL, rtol=0)


def test_a_drafting_engine_runs_with_the_queue_empty(models, runs):
    """`spec_decode=2`: the n-gram drafter reads the last token on the
    host, so the engine retires its launch before it returns — the same
    code at depth 0 — and the streams are the plain engine's."""
    _, _, plain = runs("llama")         # the schedule without an EOS
    run = _schedule(models("llama"), "llama", spec_decode=2)
    assert run.eng.spec_k == 2 and run.eng.spec_drafted > 0
    assert [r["launch_ahead"] for r in run.records] == \
        [0] * len(run.records)
    assert run.total("rows_dropped") == 0
    assert set(run.tokens) == set(plain.tokens)
    for rid, toks in plain.tokens.items():
        np.testing.assert_array_equal(run.tokens[rid], toks)
    assert run.eng._inflight is None


# ------------------------------------------------------------ the order

def test_a_steady_run_launches_before_it_reads_back(models):
    m = models("llama")
    V = _vocab(m)
    rng = np.random.default_rng(0)
    eng = ServingEngine(m, max_slots=2, page_size=4, prefill_chunk=4)
    seq0 = eng.steps
    for n in (6, 9):
        eng.add_request(rng.integers(1, V, n, dtype=np.int32),
                        max_new_tokens=12)
    outs = []
    while eng.has_work():
        outs.append(eng.step())
    recs = tracing.recorder().steps()[-(eng.steps - seq0):]
    assert len(recs) == len(outs) >= 14
    assert recs[0]["launch_ahead"] == 0     # nothing was in flight
    assert recs[-1]["launch_ahead"] == 0    # nothing was left to launch
    for r, out in zip(recs, outs):
        assert [n for n, _, _ in r["phases"]] == PHASES
        spans = {n: (a, b) for n, a, b in r["phases"]}
        # launch k is dispatched before launch k-1 is read back
        assert spans[PHASES[2]][1] <= spans[PHASES[3]][0]
        # the record and the return describe the launch RETIRED
        assert r["prefill_rows"] == out["prefill_tokens"]
        assert r["decode_rows"] == out["decoded"]
    assert [r["launch_ahead"] for r in recs[1:-1]] == \
        [1] * (len(recs) - 2)
    assert sum(r["rows_dropped"] for r in recs) == 0


def test_step_returns_what_it_retired(models):
    """A 3-chunk prompt alone: the first call queues a launch and
    returns no prefill; `has_work()` holds until the last launch has
    retired; over the calls the prompt is counted once."""
    m = models("llama")
    eng = ServingEngine(m, max_slots=2, page_size=4, prefill_chunk=4)
    prompt = np.arange(1, 12, dtype=np.int32)       # 4 + 4 + 3
    req = eng.add_request(prompt, max_new_tokens=2)
    out = eng.step()
    assert out["admitted"] == 1 and out["prefill_tokens"] == 0
    assert eng._inflight is not None and eng.has_work()
    assert req.prefill_pos == 0                     # nothing retired
    assert eng.allocator.seq_length(req.request_id) == 4    # dispatched
    seen = [out]
    while eng.has_work():
        seen.append(eng.step())
        assert req.prefill_pos == sum(o["prefill_tokens"] for o in seen)
    # queue chunk 1 | queue 2, retire 1 | queue 3, retire 2 | queue the
    # decode row, retire 3 (the first token) | retire the row (the last)
    assert [o["prefill_tokens"] for o in seen] == [0, 4, 4, 3, 0]
    assert [o["decoded"] for o in seen] == [0, 0, 0, 0, 1]
    assert [o["finished"] for o in seen] == [0, 0, 0, 0, 1]
    assert eng._inflight is None and len(req.tokens) == 2
    # between two steps `retire()` brings the host up to date, and the
    # next return carries what it retired
    req = eng.add_request(prompt[:3], max_new_tokens=3)
    eng.step()
    assert req.tokens == [] and eng._inflight is not None
    eng.retire()
    assert len(req.tokens) == 1 and eng._inflight is None
    out = eng.step()
    assert out["prefill_tokens"] == 3 and out["decoded"] == 0
