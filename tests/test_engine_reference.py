"""`ServingEngine`'s LOGITS against the plain reference of the dense GQA
decoder (`benchmarks/lib/reference_llama.py`, imported, not copied): the
tier-1 oracle of the llama family that shares no code with the step
under test (no `_mm_w`, no `_ffn_apply`, no kernel, no cache).

A toy Mistral shape (8 query heads over 2 KV heads, untied head, 2
layers) with bfloat16 weights, as the serving cells hold them; `q_proj`
is drawn at 4 x Xavier so that attention carries signal (a near-uniform
softmax hides a wrong mask).  Held as the cells' `correct` is: the
engine's distance from the float32 reference (RMS over the rows it
emitted and the vocabulary) is at most LIMIT x the distance of the SAME
reference run in bfloat16 — two correct bfloat16 evaluations disagree by
the noise of each.  Measured here: 0.64-0.92 (int8: 1.07); the
reference with another rotary base (the negative control) reads 31."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import reference_llama as ref
from benchmarks.systems.llama_serving import model_layers
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import ServingEngine

LIMIT = 2.0
#: weight-only int8 stores each matrix as int8 x a per-column float32
#: scale; the engine multiplies them back in bfloat16 (scale and product
#: both rounded), the reference in float32 from the same integers: one
#: more rounding a weight than the bfloat16 yardstick has
LIMIT_INT8 = 2.5
ATTENTION_GAIN = 4.0
ENGINE = dict(max_slots=2, page_size=8, max_context=128, prefill_chunk=16,
              enable_prefix_cache=False)


def _model(**cfg):
    paddle.seed(0)
    args = dict(num_attention_heads=8, num_key_value_heads=2,
                tie_word_embeddings=False)
    args.update(cfg)
    m = LlamaForCausalLM(llama_tiny_config(**args))
    m.eval()
    for lyr in m.llama.layers:
        w = lyr.self_attn.q_proj.weight
        w._data = w._data * ATTENTION_GAIN
    for _, prm in m.named_parameters():
        prm._data = prm._data.astype(jnp.bfloat16)
    return m


def _prompts(m, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, m.config.vocab_size, n, dtype=np.int32)
            for n in lens]


def _serve(m, prompts, max_new, stagger=0, **engine):
    """Run the prompts (request i joins `stagger` steps after i - 1);
    returns per request (tokens, the logits row behind each)."""
    eng = ServingEngine(m, **dict(ENGINE, **engine))
    assert eng.ragged
    rows = {}
    eng.on_logits = lambda req, row: rows.setdefault(
        req.request_id, []).append(row.astype(np.float32))
    reqs, waiting = [], list(prompts)
    while waiting or eng.has_work():
        if waiting and eng.steps >= stagger * len(reqs):
            reqs.append(eng.add_request(waiting.pop(0),
                                        max_new_tokens=max_new))
        eng.step()
    assert eng.program_cache_sizes() == {
        "unified": 1, "feed": 1, "unified_nochunk": 1, "feed_nochunk": 1}
    assert eng.allocator.stats()["pages_used"] == 0
    return [(np.asarray(r.tokens), np.stack(rows[r.request_id]))
            for r in reqs], eng


def _weights(m, eng):
    """The reference's weights: the model's own arrays, or, under
    weight-only int8, the engine's integers times their scales."""
    layers = model_layers(m)
    p = eng._p
    head = m.lm_head.weight._data if m.lm_head is not None else None

    def dq(d, key):
        return d[key + "_q"].astype(jnp.float32) * d[key + "_s"]

    if "wq_q" in p["layers"][0]:
        layers = [dict(L, **{k: dq(pl, k) for k in
                             ("wq", "wk", "wv", "wo", "wg", "wu", "wd")})
                  for L, pl in zip(layers, p["layers"])]
        if "head_q" in eng._w:
            head = dq(eng._w, "head")
    embed = m.llama.embed_tokens.weight._data
    return {"embed": embed, "layers": layers,
            "norm": m.llama.norm.weight._data,
            "head": embed.T if head is None else head}


def _reference_rows(m, w, prompt, tokens, dtype, **fault):
    c = m.config
    cfg = dict(head_dim=c.head_dim, rope_theta=c.rope_theta,
               num_attention_heads=c.num_attention_heads,
               num_key_value_heads=c.num_key_value_heads,
               rms_norm_eps=c.rms_norm_eps)
    cfg.update(fault)
    ids = jnp.asarray(np.concatenate([prompt, tokens]).astype(np.int32))
    x = ref.hidden_states(ids[None], w["embed"], w["layers"], cfg, dtype)
    n0, n1 = len(prompt), len(tokens)
    return np.asarray(ref.head_logits(
        x[:, n0 - 1:n0 - 1 + n1], w["norm"], w["head"],
        eps=c.rms_norm_eps, dtype=dtype))[0]


def _rms(a):
    return float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))


def _distances(m, eng, prompts, served):
    """Per request: (engine's distance from the float32 reference, the
    bfloat16 reference's)."""
    w = _weights(m, eng)
    out = []
    for p, (tokens, rows) in zip(prompts, served):
        with ref.highest():
            f32 = _reference_rows(m, w, p, tokens, jnp.float32)
        b16 = _reference_rows(m, w, p, tokens, jnp.bfloat16)
        assert rows.shape == f32.shape
        out.append((_rms(rows - f32), _rms(b16 - f32)))
    return out


def _hold(m, prompts, max_new, limit=LIMIT, stagger=0, **engine):
    served, eng = _serve(m, prompts, max_new, stagger, **engine)
    for got, noise in _distances(m, eng, prompts, served):
        assert noise > 0
        print(f"engine {got:.5f} bfloat16 reference {noise:.5f} "
              f"ratio {got / noise:.3f}")
        assert got <= limit * noise, (got, noise, got / noise)
    return served, eng


@pytest.fixture(scope="module")
def mistral():
    return _model()


class TestEngineAgainstTheLlamaReference:
    def test_prompt_crossing_pages_and_chunks(self, mistral):
        # 45 = 2 chunks of 16 and 13 rows of a third, 5 pages and a part
        _hold(mistral, _prompts(mistral, (45,)), 6)

    def test_decode_rows_beside_a_chunk(self, mistral):
        # the second request's chunks share their launches with the
        # first one's decode rows
        prompts = _prompts(mistral, (9, 40), seed=1)
        served, eng = _hold(mistral, prompts, 8, stagger=2)
        assert eng.launches < sum(
            -(-len(p) // 16) + 8 for p in prompts)

    def test_one_query_head_a_kv_head(self):
        m = _model(num_attention_heads=4, num_key_value_heads=4)
        _hold(m, _prompts(m, (21, 30), seed=2), 5)

    def test_tied_head(self):
        m = _model(tie_word_embeddings=True)
        assert m.lm_head is None
        _hold(m, _prompts(m, (19,), seed=3), 5)

    def test_weight_only_int8(self, mistral):
        served, eng = _hold(mistral, _prompts(mistral, (27,), seed=4), 5,
                            limit=LIMIT_INT8, weight_only_quant="int8")
        assert "wq_q" in eng._w["layers"][0] and "head_q" in eng._w

    def test_context_that_fills_its_last_page(self, mistral):
        # 27 + 5 tokens: the last decode row is slot 7 of page 4, and
        # nothing is written past it
        served, eng = _hold(mistral, _prompts(mistral, (27,), seed=5), 5)
        assert (27 + 5) % eng.page_size == 0

    def test_a_wrong_reference_lies_outside_the_limit(self, mistral):
        """The yardstick measures something: against the reference with
        another rotary base the engine is far outside the limit."""
        prompts = _prompts(mistral, (45,))
        served, eng = _serve(mistral, prompts, 6)
        w = _weights(mistral, eng)
        tokens, rows = served[0]
        with ref.highest():
            f32 = _reference_rows(mistral, w, prompts[0], tokens,
                                  jnp.float32)
            wrong = _reference_rows(mistral, w, prompts[0], tokens,
                                    jnp.float32, rope_theta=1e5)
        b16 = _reference_rows(mistral, w, prompts[0], tokens, jnp.bfloat16)
        print(f"wrong reference: ratio "
              f"{_rms(rows - wrong) / _rms(b16 - f32):.1f}")
        assert _rms(rows - wrong) > 5 * LIMIT * _rms(b16 - f32)
