"""SDAR-MoE through `ServingEngine` against the plain reference
(`benchmarks/lib/reference_sdar.py`): generation by diffusion over
blocks — a slot owns a block of four rows that see each other, five
PASSES commit four tokens, the commit pass riding in the launch of the
next block's first pass wherever there is a next block and room — as
`benchmarks/systems/sdar_serving.py`'s `check()` holds it: every denoise
pass's logits against the reference teacher-forced with the engine's
ids, the transfer rule exact on the engine's own logits, committed
tokens the last pass's block; and against the schedule without riding
commits (five LAUNCHES a block), pass for pass. Toy sizes as
`test_sdar.py`'s; ONE engine a module (three slots, so ONE riding commit
a launch), compiled once, and its twin without the region; the S = 2
case has a third."""

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


@pytest.fixture(scope="module")
def alone(tiny):
    """The engine whose every commit takes a launch of its own: the
    region for riding commits, which the engine derives from its slots
    and the model's steps, set to nothing (there is no switch)."""
    e = _engine(tiny[0])
    e._riders = 0
    e._build_programs()
    return e


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


def _passes(steps):
    """The step records' passes by kind, summed."""
    return {k: sum(s["diffusion_passes_" + k] for s in steps)
            for k in ("denoise", "commit", "fused")}


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
    n = _passes(steps)
    blocks = sum(len(log.blocks(h.request_id)) for h in handles)
    # a block's commit rode in a launch or took one; a request's last
    # has no block to ride with
    assert n["commit"] + n["fused"] == blocks
    assert n["commit"] >= len(handles) and n["fused"] >= 3
    assert n["denoise"] == sum(len(b) - 1 for h in handles
                               for b in log.blocks(h.request_id))
    # riding rows are decode rows: every pass of every block, computed
    assert sum(s["decode_rows"] for s in steps) == 4 * sum(
        len(b) for h in handles for b in log.blocks(h.request_id))
    assert sum(s["diffusion_tokens_committed"] for s in steps) == sum(news)
    assert sum(s["diffusion_rows_masked"] for s in steps) == sum(
        int((r["before"] == 255).sum()) for h in handles
        for b in log.blocks(h.request_id) for r in b[:-1])
    assert max(s["diffusion_blocks_open"] for s in steps) == 3
    assert all(s["diffusion_kv_tokens"] >= 4 * s["diffusion_blocks_open"]
               for s in steps)
    # a request's cache tokens count once a launch, riding or not
    assert max(s["diffusion_kv_tokens"] for s in steps) <= sum(
        len(p) + 4 + n for p, n in zip(prompts, news))
    # every block's commit is stamped on its request's timeline
    stamps = [e for e in rec.trace(handles[0].request_id).timeline()
              if e.name == "block_commit"]
    assert [e.meta["tokens"] for e in stamps] == [3, 4, 2]
    assert [e.meta["passes"] for e in stamps] == [4, 5, 5]


def test_an_eos_inside_a_block_ends_the_request_at_it(tiny, eng):
    prompt = _prompts(3, [10])[0]
    want, _ = ref.generate(prompt, 12, tiny[1], tiny[2])
    # the first new token value past the first block (two given tokens,
    # two generated): an EOS inside the second block, of four
    at = next(i for i in range(2, 6) if want[i] not in want[:i])
    eos, cut = want[at], want[:at + 1]
    rec = tracing.recorder()
    rec.clear()
    (h,), log = _run(eng, [prompt], [12], eos=eos)
    assert h.tokens == cut and h.result[:len(cut)].tolist() == cut
    _holds(tiny, h, log, prompt, 12, eos=eos)
    assert eng.allocator.free_pages == PAGES - 1
    # both commits rode, so the EOS was seen with the third block opened
    # and a launch queued behind it: its rows in both are dropped, no
    # pass of it is reported, the pages are back
    assert _passes(rec.steps()) == {"denoise": 2 + 4 + 2, "commit": 0,
                                    "fused": 2}
    assert sum(s["rows_dropped"] for s in rec.steps()) == 8
    assert [len(b) for b in log.blocks(h.request_id)] == [3, 5]


# what the riding commits may not move: the schedule without them
CASES = {
    # remainders 0-3, budgets that are no whole blocks, a late joiner
    "remainders": dict(lens=[13, 8, 2, 23], news=[9, 6, 5, 7], late=(3,)),
    # three slots in step (no prompt rows: nothing is prefilled), so
    # three stand at the end of a block in ONE launch and the region
    # holds one: the others commit alone, which moves them a launch on
    "region_full": dict(lens=[3, 3, 3], news=[9, 9, 9]),
    # a budget that ends on the block: nothing to ride with
    "one_block": dict(lens=[8, 5], news=[4, 3]),
    # an EOS in the first block, seen with the second one opened
    "eos": dict(lens=[10], news=[12], eos_at=1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_riding_commit_moves_no_token_and_no_pass(tiny, eng, alone, case):
    c = CASES[case]
    prompts = _prompts(11, c["lens"])
    eos = None
    if "eos_at" in c:
        eos = ref.generate(prompts[0], c["news"][0], tiny[1],
                           tiny[2])[0][c["eos_at"]]
    rec = tracing.recorder()
    runs = {}
    for name, e in (("riding", eng), ("alone", alone)):
        rec.clear()
        handles, log = _run(e, prompts, c["news"], eos=eos,
                            late=c.get("late", ()))
        runs[name] = handles, log, _passes(rec.steps()), len(rec.steps())
        assert e.allocator.free_pages == PAGES - 1
        assert set(e.program_cache_sizes().values()) <= {0, 1}
    (hr, lr, nr, steps_r), (ha, la, na, steps_a) = runs["riding"], \
        runs["alone"]
    for a, b in zip(hr, ha):
        assert a.tokens == b.tokens
        # `on_block` call for call: (p, total, before, after), a riding
        # commit as the last pass of its block, before the next's first
        mine, theirs = lr.passes[a.request_id], la.passes[b.request_id]
        assert [(r["p"], r["total"]) for r in mine] == \
            [(r["p"], r["total"]) for r in theirs]
        for r, t in zip(mine, theirs):
            np.testing.assert_array_equal(r["before"], t["before"])
            np.testing.assert_array_equal(r["after"], t["after"])
            if r["p"] < r["total"] - 1:
                # a block's pass 0 read the block before's FINAL K/V,
                # as after a commit that took a launch
                np.testing.assert_allclose(r["logits"], t["logits"],
                                           atol=ATOL, rtol=ATOL)
    blocks = sum(len(lr.blocks(h.request_id)) for h in hr)
    # (past an EOS the two schedules drop different launches)
    assert na == {"denoise": nr["denoise"] - (eos is not None),
                  "commit": blocks, "fused": 0}
    assert nr["commit"] + nr["fused"] == blocks
    if case == "region_full":
        # every request's last block, and three the region was full for:
        # two of three in step, then one of the two still in step
        assert (nr["commit"], nr["fused"]) == (3 + 3, blocks - 6)
    else:
        assert nr["commit"] == (0 if eos is not None else len(hr))
    # a riding commit is a launch less for its request (the run ends
    # with the slowest: the one the region was full for, one that ended)
    assert steps_r < steps_a if case == "remainders" \
        else steps_r == steps_a


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
    kinds = ("denoise", "commit", "fused")
    before = {k: fam.labels(kind=k).value for k in kinds}
    (h,), log = _run(eng, _prompts(9, [8]), [8])
    assert [fam.labels(kind=k).value - before[k] for k in kinds] == [8, 1, 1]


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
