"""SDAR-MoE through `ServingEngine` against the plain reference
(`benchmarks/lib/reference_sdar.py`): generation by diffusion over
blocks — a slot owns a block of four rows that see each other, five
launches commit four tokens — as `benchmarks/systems/sdar_serving.py`'s
`check()` holds it: every denoise pass's logits against the reference
teacher-forced with the engine's ids, the transfer rule exact on the
engine's own logits, committed tokens the last pass's block. Toy sizes
as `test_sdar.py`'s; ONE engine a module (three slots), compiled once;
the S = 2 case has a second."""

import numpy as np
import pytest

from benchmarks.lib import reference_sdar as ref
from benchmarks.systems.sdar_serving import BlockLog, rule_holds
from paddle_tpu import observability as obs
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ServingEngine
from test_sdar import seeded

PAGE, CHUNK, PAGES = 8, 8, 40
#: the engine's float32 logits against the reference's: the order of
#: float32 sums (the ragged kernel's online softmax, the grouped GEMM)
ATOL = 3e-5


@pytest.fixture(scope="module")
def tiny():
    return seeded()


def _engine(m, **kw):
    args = dict(max_slots=3, page_size=PAGE, max_context=64,
                prefill_chunk=CHUNK, num_pages=PAGES,
                enable_prefix_cache=False)
    args.update(kw)
    return ServingEngine(m, **args)


@pytest.fixture(scope="module")
def eng(tiny):
    return _engine(tiny[0])


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 250, n).astype(np.int32) for n in lens]


def _run(eng, prompts, max_new, eos=None, late=(), between=None):
    """Each request's handle and the log of its blocks' passes. `late`:
    indices added only after three steps (a request joining a window
    that is under way); `between`: called between two steps."""
    log = BlockLog()
    eng.on_block = log
    handles = {}

    def add(i):
        handles[i] = eng.add_request(prompts[i], max_new_tokens=max_new[i],
                                     eos_token_id=eos)

    for i in range(len(prompts)):
        if i not in late:
            add(i)
    n = 0
    while eng.has_work() or len(handles) < len(prompts):
        eng.step()
        n += 1
        if n == 3:
            for i in late:
                add(i)
        if between is not None:
            between(n)
    eng.collect()
    eng.on_block = None
    return [handles[i] for i in range(len(prompts))], log


def _holds(tiny, h, log, prompt, max_new, eos=None):
    """`check()`'s three comparisons for one request."""
    _, w, c = tiny
    B, S, mask = c["block_length"], c["denoising_steps"], c["mask_token_id"]
    want, passes = ref.generate(prompt, max_new, w, c, eos=eos)
    assert h.tokens == want
    blocks = log.blocks(h.request_id)
    mine = [r for blk in blocks for r in blk[:-1]]
    assert len(mine) == len(passes)
    for rec, (before, z, after) in zip(mine, passes):
        np.testing.assert_array_equal(rec["before"], before)
        np.testing.assert_array_equal(rec["after"], after)
        np.testing.assert_allclose(rec["logits"], z, atol=ATOL, rtol=ATOL)
    g = len(prompt) % B
    for k, blk in enumerate(blocks):
        assert len(blk) == ref.block_passes(B, S, g if k == 0 else 0)
        assert all(rule_holds(r, mask, B // S) for r in blk)
    # the prompt's remainder counts as prefilled once its block commits
    assert h.prefill_pos == len(prompt)


# ----------------------------------------------------------- the engine
def test_remainders_budgets_and_a_request_joining_midway(tiny, eng):
    # prompt remainders 0-3 (13 crosses a page, 2 is shorter than a
    # block: all given tokens, no prefill), budgets that are no whole
    # blocks; the fourth joins after three steps, into a freed slot
    lens, news = [13, 8, 2, 23], [9, 6, 5, 7]
    prompts = _prompts(0, lens)
    rec = tracing.recorder()
    rec.clear()
    handles, log = _run(eng, prompts, news, late=(3,))
    for h, p, n in zip(handles, prompts, news):
        _holds(tiny, h, log, p, n)
        assert h.result.tolist() == h.tokens
    # pages back at finish, one compile a program
    assert eng.allocator.free_pages == PAGES - 1
    assert set(eng.program_cache_sizes().values()) == {1}
    # the step records' counts of what the launches did
    steps = rec.steps()
    den = sum(s["diffusion_passes_denoise"] for s in steps)
    com = sum(s["diffusion_passes_commit"] for s in steps)
    blocks = sum(len(log.blocks(h.request_id)) for h in handles)
    assert com == blocks
    assert den == sum(len(b) - 1 for h in handles
                      for b in log.blocks(h.request_id))
    assert sum(s["diffusion_tokens_committed"] for s in steps) == sum(news)
    assert sum(s["diffusion_rows_masked"] for s in steps) == sum(
        int((r["before"] == 255).sum()) for h in handles
        for b in log.blocks(h.request_id) for r in b[:-1])
    assert max(s["diffusion_blocks_open"] for s in steps) == 3
    assert all(s["diffusion_kv_tokens"] >= 4 * s["diffusion_blocks_open"]
               for s in steps)
    # every block's commit is stamped on its request's timeline
    stamps = [e for e in rec.trace(handles[0].request_id).timeline()
              if e.name == "block_commit"]
    assert [e.meta["tokens"] for e in stamps] == [3, 4, 2]
    assert [e.meta["passes"] for e in stamps] == [4, 5, 5]


def test_an_eos_inside_a_block_ends_the_request_at_it(tiny, eng):
    prompt = _prompts(3, [10])[0]
    want, _ = ref.generate(prompt, 12, tiny[1], tiny[2])
    eos = want[4]           # inside the second block (two given tokens)
    cut = want[:want.index(eos) + 1]
    (h,), log = _run(eng, [prompt], [12], eos=eos)
    assert h.tokens == cut and h.result[:len(cut)].tolist() == cut
    _holds(tiny, h, log, prompt, 12, eos=eos)
    assert eng.allocator.free_pages == PAGES - 1


def test_a_retire_between_two_passes_moves_nothing(tiny, eng):
    # the host reads the block back mid-way (a deadline sweep, a drain):
    # the next pass is fed from the host's copy of the block
    prompts = _prompts(5, [9, 4])
    handles, log = _run(eng, prompts, [6, 5],
                        between=lambda n: eng.retire() if n % 2 else None)
    for h, p, n in zip(handles, prompts, [6, 5]):
        _holds(tiny, h, log, p, n)
    assert set(eng.program_cache_sizes().values()) == {1}


def test_two_rows_a_pass():
    # S = 2 with B = 4: a pass unmasks two rows, three launches a block
    tiny = seeded(denoising_steps=2)
    eng = _engine(tiny[0], max_slots=2)
    prompts = _prompts(7, [7, 12])
    handles, log = _run(eng, prompts, [7, 4])
    for h, p, n in zip(handles, prompts, [7, 4]):
        _holds(tiny, h, log, p, n)
    assert [len(b) for b in log.blocks(handles[1].request_id)] == [3]
    assert [len(b) for b in log.blocks(handles[0].request_id)] == [2, 3, 3]


def test_the_registry_counts_the_passes(tiny, eng):
    fam = obs.registry().counter("serving.engine.diffusion_passes",
                                 labels=("kind",))
    before = {k: fam.labels(kind=k).value for k in ("denoise", "commit")}
    (h,), log = _run(eng, _prompts(9, [8]), [4])
    assert fam.labels(kind="denoise").value - before["denoise"] == 4
    assert fam.labels(kind="commit").value - before["commit"] == 1


# ----------------------------------------------------------- refusals
@pytest.mark.parametrize("kw,match", [
    (dict(spec_decode=2), "spec_decode must be 0"),
    (dict(enable_prefix_cache=True), "enable_prefix_cache must be off"),
    (dict(role="prefill"), "role must be 'colocated'"),
    (dict(page_size=6), "page_size 6 must be whole blocks of 4"),
    (dict(prefill_chunk=10), "prefill_chunk 10 must be whole blocks of 4"),
])
def test_what_cannot_be_served_is_refused_by_name(tiny, kw, match):
    with pytest.raises(ValueError, match=match) as e:
        _engine(tiny[0], **kw)
    assert "block" in str(e.value)


def test_no_handoff_no_preemption_no_drafts_on_a_live_engine(tiny, eng):
    assert eng.prefix_cache is None and not eng.prefix_sharing \
        and not eng.preemption
    req = eng.add_request(np.arange(8, dtype=np.int32), max_new_tokens=4)
    eng.step()
    with pytest.raises(NotImplementedError, match="diffusion over blocks"):
        eng.export_request(req)
    with pytest.raises(ValueError, match="whole blocks of 4"):
        eng.reconfigure(spec_decode=1)
    with pytest.raises(ValueError, match="whole blocks of 4"):
        eng.reconfigure(prefill_chunk=6)
    eng.run_to_completion()
    # a budget in whole blocks has to fit the context
    with pytest.raises(ValueError, match="whole blocks of 4"):
        eng.add_request(np.arange(30, dtype=np.int32), max_new_tokens=35)
