"""Xing 4.0 through `ServingEngine` against the plain reference
(`benchmarks/lib/reference_xing.py`): prefill in chunks, then decode
through the pages, the four-stream residual mixed by the two
`ops.pallas_mhc` kernels (interpreted here) around every sublayer of the
ONE mla step body — whose residual is a seam (`engine._Residual`), the
plain add its default.  (The step programs' pinned texts:
`test_step_program_pins.py`.)  Toy sizes as `test_xing.py`'s: the tier-1
run has seconds to spare."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference_xing as ref
from paddle_tpu.generation import _cached_step_body, _decode_params
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as eng_mod
from test_xing import seeded

PAGE, CHUNK = 8, 16
#: the engine's float32 logits against the reference's: the order of
#: float32 sums (absorbed against unabsorbed attention, the grouped GEMM
#: against a loop over experts) and the kernels' chunked sums
ATOL = 5e-5


@pytest.fixture(scope="module")
def tiny():
    return seeded(experts_held=(4, 4))


def _engine(m, **kw):
    return ServingEngine(m, max_slots=2, page_size=PAGE, max_context=64,
                         prefill_chunk=CHUNK, num_pages=20,
                         enable_prefix_cache=False, **kw)


@pytest.fixture(scope="module")
def served(tiny):
    """Two unlike requests through one engine: 37 tokens in three chunks
    and 5 in one, decode across page borders (37 + 6 crosses 40);
    (prompts, requests, logits rows, engine, the run's step records)."""
    m, _, _ = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, n, dtype=np.int32) for n in (37, 5)]
    eng = _engine(m)
    rows = {}
    eng.on_logits = lambda req, row: rows.setdefault(
        req.request_id, []).append(row.copy())
    reqs = [eng.add_request(p, max_new_tokens=k)
            for p, k in zip(prompts, (6, 9))]
    eng.run_to_completion()
    return prompts, reqs, rows, eng, \
        list(tracing.recorder().steps()[-eng.steps:])


def test_prefill_in_chunks_then_paged_decode_matches_in_logits(tiny, served):
    _, w, c = tiny
    prompts, reqs, rows, eng, _ = served
    assert eng.ragged and eng._family == "mla" and eng._hc == 4
    for r, p in zip(reqs, prompts):
        toks = np.asarray(r.tokens)
        fed = jnp.asarray(np.concatenate([p, toks[:-1]]), jnp.int32)
        want = np.asarray(ref.logits(fed, w, c))[len(p) - 1:]
        got = np.stack(rows[r.request_id])
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
        np.testing.assert_array_equal(toks, want.argmax(-1))
    assert eng.program_cache_sizes() == {
        "unified": 1, "feed": 1, "unified_nochunk": 1, "feed_nochunk": 1}
    assert eng.launches == eng.steps - 1    # ONE launch a step, one ahead
    assert eng.allocator.stats()["pages_used"] == 0


def test_the_step_record_counts_the_mixing(served):
    _, _, _, eng, recs = served
    recs = [r for r in recs if r.get("mhc_rows")]
    assert recs and all(k in recs[-1] for k in tracing.STEP_COUNTS_MHC)
    assert all(k in recs[-1] for k in tracing.STEP_COUNTS_MOE)
    assert all(k in recs[-1] for k in tracing.STEP_COUNTS_LATENT)
    for r in recs:
        # every row of the launch's flat buffer is mixed (the two
        # slots' rows, and a chunk's behind them where it carries one),
        # around two sublayers a layer; a row of the stream is 4 x 64
        # float32 here
        assert r["mhc_rows"] == r["rows_computed"] \
            == 2 + CHUNK * bool(r["prefill_rows"])
        assert r["mhc_sublayers"] == 4
        # taken on the device: rows sum to 1, columns as far as twenty
        # iterations bring them
        assert 0 < r["mhc_colsum_err_max"] < 0.2
    assert eng.hbm_accounting()["residual_stream_bytes"] \
        == (2 + CHUNK) * 1024


def _lower(eng):
    B, C = eng.max_slots, eng.prefill_chunk

    def i32(*d):
        return jax.ShapeDtypeStruct(d, jnp.int32)

    return eng._jit_unified.lower(
        eng._w, i32(B + C), eng._pools, i32(B + C), i32(B + 1), i32(B + 1),
        i32(B + 1, eng.pages_per_seq), i32(B + C), i32(B + C))


def test_the_step_runs_the_mixing_under_its_own_scopes(served):
    eng = served[3]
    low = _lower(eng)
    logits, pools, tokens, counts = low.out_info
    assert logits.shape == (3, 256) and counts.shape == (6,)
    text = low.as_text(debug_info=True)
    for here in ("mhc_pre", "mhc_post", "mhc_merge", "mla_q", "mla_kv",
                 "mla_attention", "mla_out", "routed_ffn", "shared_expert"):
        assert here in text, here
    # two kernels a sublayer, on the stream [T, 4 x 64]
    assert text.count("mhc_pre/pallas_call") \
        == text.count("mhc_post/pallas_call") > 0
    from paddle_tpu.observability.attribution import SCOPE_ALIASES, SCOPES
    assert all(SCOPE_ALIASES[k] in SCOPES
               for k in ("mhc_pre", "mhc_post", "mhc_merge"))


# ------------------------------------------------------------- refusals
def test_what_the_family_cannot_do_is_refused_by_name(tiny):
    m, _, _ = tiny
    with pytest.raises(ValueError, match="spec_decode must be 0"):
        _engine(m, spec_decode=2)
    with pytest.raises(NotImplementedError, match="hyper-connected"):
        _decode_params(m, weight_only_int8=True)
    with pytest.raises(NotImplementedError, match="Xing family"):
        _cached_step_body(_decode_params(m), 32)


def test_a_launch_the_mixing_kernels_cannot_tile_is_refused(tiny,
                                                            monkeypatch):
    m, _, _ = tiny
    monkeypatch.setattr(eng_mod, "_mhc_step_eligible", lambda *a: False)
    with pytest.raises(ValueError,
                       match="hyper-connection kernels do not tile"):
        _engine(m)
    monkeypatch.undo()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert eng_mod._mhc_step_eligible(128 + 256, 4, 3584)
    assert not eng_mod._mhc_step_eligible(100 + 256, 4, 3584)
    assert not eng_mod._mhc_step_eligible(2 + CHUNK, 4, 64)


def test_the_plain_residual_adds_nothing():
    res = eng_mod._PLAIN
    x, y = jnp.ones((1, 3, 4)), jnp.full((1, 3, 4), 2.0)
    assert res.enter(x) is x and res.exit(x) is x
    assert res.feed(x) == (x, None)
    assert bool((res.leave(x, y) == x + y).all())
