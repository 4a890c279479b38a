"""Phi-4-mini-flash through `ServingEngine` against the plain reference
(`benchmarks/lib/reference_phi4flash.py`): prompt chunks through window
pages, ONE full pool and a slot of the Mamba-1 state pool, then decode
steps through all three, on the hybrid body's one step program at both
row counts; fourteen — here four — of its blocks own NO memory and read
another block's pages or scan output inside the launch; a context past
the window with released pages; a slot handed on starts from zero state
and fresh pages of both kinds; what cannot be served is refused.  (The
step programs' pinned texts: `test_step_program_pins.py`.)  Toy sizes as `test_phi4flash.py`'s; ONE
engine a module (two slots), compiled once."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference_phi4flash as ref
from paddle_tpu.generation import _cached_step_body, _decode_params
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import _pattern_blocks
from test_phi4flash import seeded

PAGE, CHUNK, WINDOW = 8, 16, 12
#: the engine's float32 logits against the reference's: the order of
#: float32 sums (the scan kernel against the token-by-token recurrence,
#: the ragged kernel's online softmax over padded pairs)
ATOL = 3e-4


@pytest.fixture(scope="module")
def tiny():
    return seeded()


def _engine(m, **kw):
    args = dict(max_slots=2, page_size=PAGE, max_context=128,
                prefill_chunk=CHUNK, num_pages=24,
                enable_prefix_cache=False)
    args.update(kw)
    return ServingEngine(m, **args)


@pytest.fixture(scope="module")
def eng(tiny):
    return _engine(tiny[0])


def _run(eng, prompts, max_new):
    """Each request's handle, tokens, the logits rows they were taken
    from and its slot."""
    rows, slots = {}, {}

    def keep(req, row):
        rows.setdefault(req.request_id, []).append(
            np.asarray(row, np.float32))
        slots[req.request_id] = req.slot

    eng.on_logits = keep
    handles = [eng.add_request(p, max_new_tokens=n)
               for p, n in zip(prompts, max_new)]
    while eng.has_work():
        eng.step()
    eng.collect()
    eng.on_logits = None
    return [(h, np.asarray(h.tokens, np.int32), np.stack(rows[h.request_id]),
             slots[h.request_id]) for h in handles]


def _reference(w, c, prompt, tokens):
    fed = jnp.asarray(np.concatenate([prompt, tokens[:-1]]), jnp.int32)
    return np.asarray(ref.logits(fed, w, c))[len(prompt) - 1:]


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, n).astype(np.int32) for n in lens]


# ----------------------------------------------------------- the engine
#: prompts of several chunks (the state crosses chunk borders at 16, 32,
#: the pages theirs at every 8, every context leaves the window of 12)
#: and of less than one; four requests through two slots: the third and
#: fourth take over a finished request's slot, state and pages
CASES = {"chunks_then_decode": ([37], [14]),
         "slots_handed_on": ([19, 5, 33, 9], [9, 12, 7, 5]),
         "one_token_prompt": ([1, 30], [10, 4])}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_logits_and_state_match_the_reference(tiny, eng, case):
    _, w, c = tiny
    lens, new = CASES[case]
    prompts = _prompts(3, lens)
    steps0 = eng.steps
    got = _run(eng, prompts, new)
    for p, (_, tokens, rows, slot) in zip(prompts, got):
        want = _reference(w, c, p, tokens)
        assert rows.shape == want.shape == (len(tokens), 96)
        np.testing.assert_allclose(rows, want, atol=ATOL)
        np.testing.assert_array_equal(tokens, want.argmax(-1))
    # the LAST request's slot still holds its state: the memory layer's
    # is the recurrence's after the last token that was fed
    p, (_, tokens, _, slot) = prompts[-1], got[-1]
    fed = jnp.asarray(np.concatenate([p, tokens[:-1]]), jnp.int32)
    _, state = ref.hidden_states(fed, w["embed"], w["layers"], c,
                                 jnp.float32, state_of=4)
    np.testing.assert_allclose(eng._pools["ssm"][2][0][slot, 0], state.T,
                               atol=ATOL, rtol=1e-4)
    assert eng.program_cache_sizes() == {
        "unified": 1, "feed": 1, "unified_nochunk": 1, "feed_nochunk": 1}
    recs = tracing.recorder().steps()[-(eng.steps - steps0):]
    if case == "slots_handed_on":
        assert sorted(s for _, _, _, s in got) == [0, 0, 1, 1]
        assert sum(r.get("ssm_state_resets", 0) for r in recs) == 4
    if case == "chunks_then_decode":
        # a launch with a chunk and without: the prompt's launches carry
        # its rows, the decode launches the decode rows alone
        live = [r for r in recs if r.get("ssm_slots_live")]
        assert [r["ssm_scan_rows"] for r in live][:3] == [16, 16, 5]
        assert {r["ssm_scan_rows"] for r in live[3:]} == {0}
        assert live[0]["rows_computed"] == 2 + 16
        assert live[-1]["rows_computed"] == 2
        # the window's pages go back as the window passes; the full
        # pool's stay; eight launches a step would read it at published
        # depth, two here (its own and the one cross block's)
        assert sum(r["window_pages_freed"] for r in recs) >= 3
        assert live[-1]["pages_live.window"] <= 3 < live[-1]["pages_live.full"]
        assert live[-1]["shared_pool_readers"] == 2
        state = 4 * 16 * 128
        assert live[0]["ssm_state_bytes_moved"] == 3 * 1 * state
        assert live[1]["ssm_state_bytes_moved"] == 3 * 2 * state


def test_fourteen_of_thirty_two_blocks_would_own_no_memory(tiny, eng):
    """Here: eight layers, three Mamba-1 slots, two window pools, ONE
    full pool; the gated unit and the cross block hold no entry."""
    m = tiny[0]
    cfg = m.config
    assert eng.ragged and eng._family == "hybrid"
    assert eng._blocks == ("S", "D", "*", "D") * 2 + (
        "S", "D", "*", "D", "G8", "D", "X10", "D")
    assert eng._ssm_layers == 3 and len(eng._pools["ssm"]) == 3
    assert len(eng._pools["kv"]) == 3 and eng._layer_kind == [1, 1, 0]
    assert eng._pool_readers == [1, 1, 2]
    for state, tail in eng._pools["ssm"]:
        assert state.shape == (3, 1, 16, 128) and state.dtype == jnp.float32
        assert tail.shape == (3, 3, cfg.d_inner)
    # the pair layout: a KV head of the pool is two heads side by side
    for k, (kp, vp) in zip(eng._layer_kind, eng._pools["kv"]):
        pages = eng.num_window_pages if k else eng.num_pages
        assert kp.shape == vp.shape == (2, pages, PAGE, 16)
    assert eng._q_rep == 4 and eng._window == WINDOW
    acct = eng.hbm_accounting()
    # as STORED: 4 B an element of [16, 128], nothing padded, and the tail
    assert acct["state_pool_bytes"] == 3 * 3 * (4 * 16 * 128 + 3 * 128 * 4)
    assert acct["weights_bytes"] >= 4 * sum(
        int(np.prod(p._data.shape)) for _, p in m.named_parameters())
    p = _decode_params(m)
    assert p["family"] == "hybrid" and p["head"] is None
    assert p["pattern"] == "SD*DSD*DSD*DG8DX10D"
    assert p["diff"] == {2: 1, 6: 3, 10: 5, 14: 7}
    assert [s["window"] for s in p["attn_static"]] == [12, 12, None]
    assert "wk" not in p["layers"][14] and "wk" in p["layers"][10]


def test_idle_slots_state_is_bit_unchanged(tiny, eng):
    """One request in slot 0 of two: the other slot's state and tails
    (set to a pattern first) come back bit for bit."""
    eng._pools["ssm"] = [(z + 3.0, t + 1) for z, t in eng._pools["ssm"]]
    before = [(np.asarray(z), np.asarray(t)) for z, t in eng._pools["ssm"]]
    (_, _, _, slot), = _run(eng, _prompts(6, [19]), [5])
    assert slot == 0
    for (z0, t0), (z, t) in zip(before, eng._pools["ssm"]):
        np.testing.assert_array_equal(np.asarray(z)[1], z0[1])
        np.testing.assert_array_equal(np.asarray(t)[1], t0[1])
        assert not np.array_equal(np.asarray(z)[0], z0[0])


def test_what_a_state_and_a_window_cannot_serve_is_refused(tiny):
    m = tiny[0]
    with pytest.raises(ValueError, match="enable_prefix_cache"):
        _engine(m, enable_prefix_cache=True)
    with pytest.raises(ValueError, match="spec_decode"):
        _engine(m, spec_decode=2)
    with pytest.raises(ValueError, match="role"):
        _engine(m, role="prefill")
    with pytest.raises(NotImplementedError, match="ServingEngine"):
        _cached_step_body(_decode_params(m), 32)
    with pytest.raises(NotImplementedError, match="quantisation"):
        _decode_params(m, weight_only_int8=True)


@pytest.mark.parametrize("pattern, want", [
    ("SD*DG0DX2D", ("S", "D", "*", "D", "G0", "D", "X2", "D")),
    ("SD*DSD*DG32DX34D"[:8] + "G4DX6D",
     ("S", "D", "*", "D", "S", "D", "*", "D", "G4", "D", "X6", "D"))])
def test_a_pattern_names_blocks_that_borrow(pattern, want):
    assert _pattern_blocks(pattern) == want


@pytest.mark.parametrize("pattern", ["G0D", "SDX0D", "SD*DG2D", "SDG4D",
                                     "[SG0]", "SDGD"])
def test_a_borrower_of_nothing_is_refused(pattern):
    with pytest.raises(ValueError):
        _pattern_blocks(pattern)


def test_the_step_lowers_with_every_scope(tiny, eng):
    """What `benchmarks/tests` lower it with, and the names the readers
    look for: 2 appends and 4 attention launches at this depth (8 and 16
    at the published one), no pool for a block that borrows."""
    B, C = eng.max_slots, eng.prefill_chunk

    def i32(*d):
        return jax.ShapeDtypeStruct(d, jnp.int32)

    table = i32(B + 1, eng.pages_per_seq)
    low = eng._jit_unified.lower(
        eng._w, i32(B + C), eng._pools, i32(B + C), i32(B + 1),
        (i32(B + 1), i32(B + 3)), (table, table), (i32(B + C), i32(B + C)),
        i32(B + C))
    logits, pools, tokens = low.out_info
    assert logits.shape == (B + 1, 96) and tokens.shape == (B + 1,)
    assert len(pools["kv"]) == 3 and len(pools["ssm"]) == 3
    text = low.as_text(debug_info=True)
    for here in ("attn_norm", "ssm1_in_proj", "ssm1_conv", "ssm1_scan",
                 "ssm1_out", "gmu", "qkv_proj", "cache_write", "attention",
                 "shared_attention", "diff_combine", "attn_out", "ffn_norm",
                 "ffn", "fused_rope_append", "ragged_paged_attention"):
        assert here in text, here
