"""A launch computes the rows it carries (ISSUE 53): the step body is
compiled at two row counts — `max_slots x (1 + spec_k) + prefill_chunk`
flat rows, and `max_slots x (1 + spec_k)` with no chunk part — and
`_unified_step` launches the second whenever no prompt is being
dispatched in the launch it builds.

One case a family, at toy sizes on the CPU (a dense GQA decoder, Laguna's
two page kinds, EvaByte's chunk summaries, Ouro's loop, Nemotron's
state-space blocks, Ling's KDA blocks, GPT, latent attention, and a
drafting engine), over ONE schedule whose launches alternate between the
two:

- the tokens are those of the SAME engine made to run every launch at
  the full row count, which is the tree before this change (the twin:
  `_build_unified` always handed `prefill_chunk`, the full programs
  under both names);
- a launch built without a prompt computed `max_slots x (1 + spec_k)`
  rows and one with a chunk the full count, by the step record's
  `rows_computed`; `scrape()` adds them up;
- after the mix every program has ONE cache entry, and a step compiled
  only where a program had its first launch;
- `reconfigure()` rebuilds both programs and both feeds, and the rule
  holds at the new chunk."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ServingEngine
from test_bailing_hybrid_serving import seeded as ling_seeded
from test_engine_programs import _laguna, _tiny
from test_falcon_h1 import seeded as falcon_seeded
from test_nemotron_h import seeded as nemotron_seeded
from test_ouro import seeded as ouro_seeded

NAMES = ("unified", "feed", "unified_nochunk", "feed_nochunk")


def _eva():
    from paddle_tpu.models.evabyte import (EvaByteForCausalLM,
                                           evabyte_tiny_config)
    paddle.seed(0)
    m = EvaByteForCausalLM(evabyte_tiny_config())
    m.eval()
    return m


_DENSE = dict(max_slots=3, page_size=4, prefill_chunk=4, max_context=64)
_HYBRID = dict(max_slots=3, page_size=8, prefill_chunk=16, max_context=128,
               num_pages=40)
#: family -> (model, engine arguments)
CASES = {
    "llama": (lambda: _tiny("llama"), _DENSE),
    "llama_window": (_laguna, dict(
        max_slots=3, page_size=8, prefill_chunk=16, max_context=256,
        num_pages=70)),
    "eva": (_eva, dict(max_slots=3, page_size=8, prefill_chunk=8,
                       max_context=256, num_pages=64)),
    "looped": (lambda: ouro_seeded()[0], dict(
        max_slots=3, page_size=8, prefill_chunk=8, max_context=128,
        num_pages=24)),
    "hybrid_ssm": (lambda: nemotron_seeded()[0], _HYBRID),
    "hybrid_kda": (lambda: ling_seeded()[0], _HYBRID),
    "hybrid_two_mixers": (lambda: falcon_seeded()[0], _HYBRID),
    "gpt": (lambda: _tiny("gpt"), _DENSE),
    "mla": (lambda: _tiny("mla"), _DENSE),
    "llama_spec": (lambda: _tiny("llama"), dict(_DENSE, spec_decode=2)),
}


def _full_rows_only(eng):
    """Make `eng` the tree before this change: every launch is built
    and run at the full row count, a prompt's chunk or not."""
    build, rebuild = eng._build_unified, eng._build_programs

    def same_programs():
        for name in ("unified", "feed"):
            eng._programs[name + "_nochunk"] = eng._programs[name]

    def build_programs():
        rebuild()
        same_programs()

    eng._build_unified = lambda preq, rows, fl, chunk: build(
        preq, rows, fl, eng.prefill_chunk)
    eng._build_programs = build_programs
    same_programs()


def _schedule(m, args, twin=False):
    """The one schedule on a fresh engine -> (the engine, {request id:
    tokens}, [(the step's record, the cache sizes before it, after it,
    the chunk it ran under)])."""
    V = min(int(m.config.vocab_size), 60)
    C = args["prefill_chunk"]
    rng = np.random.default_rng(53)

    def prompt(n):
        return rng.integers(1, V, n, dtype=np.int32)

    # arrivals far enough apart that decode-only launches lie between
    # two prompts' chunks, and close enough that chunks ride beside
    # other requests' decode rows
    plan = {0: [("a", prompt(2 * C + 1), 7)],
            6: [("b", prompt(C // 2 + 1), 6)],
            10: [("c", prompt(C + 2), 4)],
            # ... and after `reconfigure` has rebuilt the programs
            17: [("d", prompt(C + 1), 5), ("e", prompt(2), 3)]}
    eng = ServingEngine(m, **args)
    if twin:
        _full_rows_only(eng)
    reqs, steps, call = {}, [], 0
    while plan or eng.has_work():
        for rid, p, n in plan.pop(call, ()):
            reqs[rid] = eng.add_request(p, max_new_tokens=n,
                                        request_id=rid)
        if call == 16:
            assert eng.reconfigure(prefill_chunk=C // 2)
        before = eng.program_cache_sizes()
        eng.step()
        steps.append((tracing.recorder().steps()[-1], before,
                      eng.program_cache_sizes(), eng.prefill_chunk))
        call += 1
        assert call < 200
    eng.collect()
    return eng, {k: list(r.tokens) for k, r in reqs.items()}, steps


@pytest.mark.parametrize("family", sorted(CASES))
def test_a_launch_computes_the_rows_it_carries(family):
    build, args = CASES[family]
    m = build()
    eng, tokens, steps = _schedule(m, args)
    _, want, twin_steps = _schedule(m, args, twin=True)

    # the tokens are the ones every launch at the full row count gives
    assert {k: len(v) for k, v in tokens.items()} == \
        {"a": 7, "b": 6, "c": 4, "d": 5, "e": 3}
    assert tokens == want

    base = eng.max_slots * (1 + eng.spec_k)
    kinds = []      # of each launch retired: did it carry a chunk
    for rec, before, after, chunk in steps:
        rows = rec["rows_computed"]
        if rec["prefill_rows"]:
            assert rows == base + chunk, rec
        elif rec["decode_rows"]:
            assert rows == base, rec
        else:
            assert rows == 0, rec
        if rows:
            kinds.append(rows > base)
        # a step compiles only where a program has its first launch
        if before == after:
            assert rec["compiles"] == 0, rec
        assert set(after) == set(NAMES) and max(after.values()) <= 1
    # the schedule alternated, before `reconfigure` and after it
    turns = sum(a != b for a, b in zip(kinds, kinds[1:]))
    assert turns >= 5 and 0 < sum(kinds) < len(kinds), kinds
    # the twin computed the full count in every launch
    assert all(rec["rows_computed"] in (0, base + chunk)
               for rec, _, _, chunk in twin_steps)

    # every program ran, once compiled, at both chunk lengths
    assert eng.rebuilds == 1
    assert eng.program_cache_sizes() == dict.fromkeys(NAMES, 1)
    halfway = next(before for _, before, _, chunk in steps
                   if chunk != args["prefill_chunk"])
    assert halfway == dict(dict.fromkeys(NAMES, 0), feed=1, feed_nochunk=1)
    assert next(before for _, before, _, chunk in reversed(steps)
                if chunk == args["prefill_chunk"]) == dict.fromkeys(NAMES, 1)

    # the engine's totals are the records'
    snap = eng.scrape()

    def total(name):
        return sum(s["value"] for s in snap[name]["series"])

    assert total("serving.replica.rows_computed") == eng.rows_computed \
        == sum(rec["rows_computed"] for rec, *_ in steps)
    assert total("serving.replica.rows_owned") == eng.rows_owned == sum(
        rec["decode_rows"] + rec["prefill_rows"] for rec, *_ in steps)
    assert eng.rows_owned < eng.rows_computed < sum(
        rec["rows_computed"] for rec, *_ in twin_steps)


def test_an_engine_that_never_decodes_never_runs_the_short_program():
    """``role="prefill"``: its requests leave by a handoff after their
    first token, so no launch is without a chunk."""
    m = _tiny("llama")
    eng = ServingEngine(m, role="prefill", **_DENSE)
    rng = np.random.default_rng(1)
    for n in (9, 3):
        eng.add_request(rng.integers(1, 60, n, dtype=np.int32),
                        max_new_tokens=4)
    seq0 = eng.steps
    while eng.has_work():
        eng.step()
    assert len(eng.handoff_ready) == 2
    recs = tracing.recorder().steps()[-(eng.steps - seq0):]
    full = eng.max_slots + eng.prefill_chunk
    assert {r["rows_computed"] for r in recs} - {0} == {full}
    assert eng.program_cache_sizes() == dict(
        dict.fromkeys(NAMES, 1), unified_nochunk=0)
