"""Weight-only int8 decode for the MoE and MLA families (r5): the llama
family had the 1.85x int8 decode win recorded; the MoE family (where the
expert stacks are the bulk of HBM weight traffic) and DeepSeek-MLA had no
int8 path at all. Per-expert out-channel scales for 3-D stacks, fp router
gate (routing is decision-sensitive, not rounding-tolerant), dequantize
in VMEM fused into the consuming einsum. Ref capability: PaddleNLP
weight-only-int8 deploy across the LLM families (SURVEY §2.2
quantization row)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.generation import (_decode_params, _cached_step_body,
                                   _llama_weights, _init_caches,
                                   generate_cached)


def _logits_pair(model, S0=6, B=2, seed=0):
    """(fp logits, int8 logits) from one prefill step of the cached body."""
    rng = np.random.RandomState(seed)
    ids = jnp.asarray(
        rng.randint(1, model.config.vocab_size, (B, S0)), jnp.int32)
    outs = {}
    for tag, wo in (("fp", False), ("int8", True)):
        p = _decode_params(model, weight_only_int8=wo)
        body = _cached_step_body(p, S0 + 2)
        w = _llama_weights(p)
        caches = _init_caches(p, B, S0 + 2)
        logits, _ = body(w, ids, caches, 0)
        outs[tag] = np.asarray(logits, np.float32)
    return outs["fp"], outs["int8"]


def _check_tracks(fp, q8):
    # same contract as the llama int8 test: small per-channel error,
    # logits track fp, argmax mostly agrees on a random tiny model
    rel = np.abs(q8 - fp).max() / (np.abs(fp).max() + 1e-9)
    assert rel < 0.08, rel
    assert (q8.argmax(-1) == fp.argmax(-1)).mean() >= 0.9


class TestMoEInt8:
    @pytest.fixture(scope="class")
    def model(self):
        from paddle_tpu.models.moe_llm import (MoEForCausalLM,
                                               qwen2_moe_tiny_config)
        paddle.seed(17)
        cfg = qwen2_moe_tiny_config(moe_dropless=True,
                                    first_k_dense_replace=1,
                                    max_position_embeddings=32)
        m = MoEForCausalLM(cfg)
        m.eval()
        return m

    def test_int8_logits_track_fp(self, model):
        fp, q8 = _logits_pair(model)
        _check_tracks(fp, q8)

    def test_expert_stacks_quantized_per_expert(self, model):
        p = _decode_params(model, weight_only_int8=True)
        moe_layers = [L["moe"] for L in p["layers"] if "moe" in L]
        assert moe_layers, "tiny config must have routed layers"
        mo = moe_layers[0]
        assert mo["wup_q"].dtype == jnp.int8
        E = model.config.num_experts
        assert mo["wup_q"].shape[0] == E
        assert mo["wup_s"].shape == (E, mo["wup_q"].shape[-1])
        # router gate stays fp — routing decisions are not
        # rounding-tolerant
        assert "gate_q" not in mo and mo["gate"].dtype != jnp.int8
        # shared expert quantized
        assert "shared" in mo and mo["shared"]["su_q"].dtype == jnp.int8

    def test_generate_cached_int8_runs(self, model):
        rng = np.random.RandomState(2)
        ids = paddle.to_tensor(
            rng.randint(1, model.config.vocab_size, (1, 4)).astype("int32"))
        toks, _ = generate_cached(model, ids, max_new_tokens=4,
                                  decode_strategy="greedy_search",
                                  weight_only_int8=True)
        assert toks.numpy().shape == (1, 4)


class TestMLAInt8:
    @pytest.fixture(scope="class")
    def model(self):
        from paddle_tpu.models.deepseek import (DeepSeekV2ForCausalLM,
                                                deepseek_v2_tiny_config)
        paddle.seed(19)
        cfg = deepseek_v2_tiny_config(moe_dropless=True,
                                      max_position_embeddings=32)
        m = DeepSeekV2ForCausalLM(cfg)
        m.eval()
        return m

    def test_int8_logits_track_fp(self, model):
        fp, q8 = _logits_pair(model, seed=1)
        _check_tracks(fp, q8)

    def test_projections_quantized(self, model):
        p = _decode_params(model, weight_only_int8=True)
        L = p["layers"][0]
        for key in ("wkva", "wkvb", "wo", "wqa", "wqb"):
            assert key + "_q" in L and L[key + "_q"].dtype == jnp.int8, key
        assert "head_q" in p

    def test_generate_cached_int8_runs(self, model):
        rng = np.random.RandomState(3)
        ids = paddle.to_tensor(
            rng.randint(1, model.config.vocab_size, (1, 4)).astype("int32"))
        toks, _ = generate_cached(model, ids, max_new_tokens=4,
                                  decode_strategy="greedy_search",
                                  weight_only_int8=True)
        assert toks.numpy().shape == (1, 4)


class TestGPTInt8Refusal:
    def test_clear_error(self):
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny_config
        paddle.seed(23)
        m = GPTForCausalLM(gpt_tiny_config(max_position_embeddings=16))
        m.eval()
        ids = paddle.to_tensor(np.ones((1, 3), np.int32))
        with pytest.raises(NotImplementedError, match="GPT family is fp"):
            generate_cached(m, ids, max_new_tokens=2,
                            decode_strategy="greedy_search",
                            weight_only_int8=True)


class TestLlamaInt4:
    """Packed-int4 decode (llama family): the even/odd contraction split
    keeps the unpack an elementwise chain fused into the dot operand
    loads — nothing bf16-sized hits HBM (quarter the int8 weight
    traffic). Ref: weight_only_linear int4 deploy (SURVEY §2.1 fused
    kernels row)."""

    @pytest.fixture(scope="class")
    def model(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        paddle.seed(29)
        m = LlamaForCausalLM(llama_tiny_config(max_position_embeddings=32))
        m.eval()
        return m

    def test_int4_split_matches_whole_dequant(self):
        # h @ W == h[:,0::2] @ lo + h[:,1::2] @ hi, exactly, against the
        # op-level unpack (ops/quant.weight_dequantize)
        from paddle_tpu.ops.quant import weight_quantize, weight_dequantize
        from paddle_tpu.generation import _int4_halves
        rng = np.random.RandomState(0)
        w = jnp.asarray(rng.randn(16, 8), jnp.float32)
        h = jnp.asarray(rng.randn(3, 16), jnp.float32)
        q4, s = weight_quantize(w, algo="weight_only_int4")
        lo, hi = _int4_halves(q4, s.astype(jnp.float32))
        got = h[:, 0::2] @ lo + h[:, 1::2] @ hi
        exp = h @ weight_dequantize(q4, s, algo="weight_only_int4")
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=1e-4)

    def test_int4_kernel_unaligned_n_matches_dequant(self):
        # the packed kernel tiles N in 128-lane blocks; a non-128-multiple
        # N (the vocab-16032 lm-head shape, scaled down) used to fall back
        # to the bf16 _int4_halves path — now it zero-pads to the next 128
        # inside the launch and slices back, and must stay EXACT against
        # the whole-dequant reference
        from paddle_tpu.ops.quant import (weight_quantize,
                                          weight_dequantize,
                                          weight_only_linear)
        rng = np.random.RandomState(1)
        for N in (160, 8, 136):
            w = jnp.asarray(rng.randn(32, N), jnp.float32)
            h = jnp.asarray(rng.randn(3, 32), jnp.float32)
            q4, s = weight_quantize(w, algo="weight_only_int4")
            got = weight_only_linear(h, q4, s, algo="weight_only_int4")
            exp = h @ weight_dequantize(q4, s, algo="weight_only_int4")
            assert got.shape == (3, N)
            np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                       atol=1e-4, err_msg=f"N={N}")

    def test_int4_body_matches_dequantized_reference(self, model):
        # the MECHANISM must be exact: running the int4 body equals
        # running the fp body on the SAME quantized weights dequantized
        # whole (differences are summation-order only). Quantization
        # noise vs the original fp model is int4's accuracy trade-off,
        # not a property of this code path — a random-init tiny model
        # shows ~0.3 rel there, trained weights far less.
        from paddle_tpu.generation import (_llama_decode_params,
                                           _cached_step_body, _heads_w,
                                           _llama_weights, _init_caches)
        from paddle_tpu.ops.quant import weight_dequantize
        rng = np.random.RandomState(4)
        ids = jnp.asarray(
            rng.randint(1, model.config.vocab_size, (2, 6)), jnp.int32)
        p4 = _llama_decode_params(model, weight_only_quant="int4")
        body = _cached_step_body(p4, 8)
        got, _ = body(_llama_weights(p4), ids, _init_caches(p4, 2, 8), 0)

        def deq(d):
            out = {}
            for k, v in d.items():
                if k.endswith("_q4"):
                    base = k[:-3]
                    out[base] = weight_dequantize(
                        v, d[base + "_s"],
                        algo="weight_only_int4").astype(jnp.float32)
                elif k.endswith("_s") or (v is None and k + "_q4" in d):
                    # scales are consumed above; a None placeholder
                    # (head) must not clobber its dequantized entry
                    continue
                else:
                    out[k] = v
            # a float q / k / v leaf is stored [heads, head_dim, in],
            # as the decode-params builder leaves it
            _heads_w(out, model.config.head_dim, "wq", "wk", "wv")
            return out

        pf = {k: (deq(v) if isinstance(v, dict)
                  else [deq(L) for L in v] if k == "layers" else v)
              for k, v in deq(p4).items()}
        bodyf = _cached_step_body(pf, 8)
        exp, _ = bodyf(_llama_weights(pf), ids, _init_caches(pf, 2, 8), 0)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(exp, np.float32),
                                   rtol=1e-4, atol=1e-3)

    def test_generate_cached_int4_runs(self, model):
        rng = np.random.RandomState(5)
        ids = paddle.to_tensor(
            rng.randint(1, model.config.vocab_size, (1, 4)).astype("int32"))
        toks, _ = generate_cached(model, ids, max_new_tokens=4,
                                  decode_strategy="greedy_search",
                                  weight_only_quant="int4")
        assert toks.numpy().shape == (1, 4)

    def test_moe_int4_runs_and_packs_expert_stacks(self):
        # ISSUE 14: the 3-D expert stacks pack per expert ([E, K/2, N]
        # two nibbles per byte, scales [E, N]) and read back through
        # _dq's plane-interleave — int4-MoE decode now RUNS instead of
        # refusing, and the layer dict carries _q4 stacks end-to-end
        from paddle_tpu.models.moe_llm import (MoEForCausalLM,
                                               qwen2_moe_tiny_config)
        from paddle_tpu.generation import _decode_params
        paddle.seed(31)
        m = MoEForCausalLM(qwen2_moe_tiny_config(
            moe_dropless=True, max_position_embeddings=16))
        m.eval()
        p = _decode_params(m, weight_only_quant="int4")
        moe_layers = [q for q in p["layers"] if "moe" in q]
        assert moe_layers
        for q in moe_layers:
            assert "wup_q4" in q["moe"] and "wdn_q4" in q["moe"]
            assert q["moe"]["wup_q4"].ndim == 3
            E, K2, N = q["moe"]["wup_q4"].shape
            assert q["moe"]["wup_s"].shape == (E, N)
            assert "gate_q4" not in q["moe"]   # router stays fp
        ids = paddle.to_tensor(np.ones((1, 3), np.int32))
        toks, _ = generate_cached(m, ids, max_new_tokens=2,
                                  decode_strategy="greedy_search",
                                  weight_only_quant="int4")
        assert toks.numpy().shape == (1, 2)

    def test_moe_expert_stack_dequant_matches_op_level(self):
        # _dq's 3-D plane-interleave (stack lo/hi nibbles then reshape)
        # must be EXACT against per-expert weight_dequantize — the
        # .at[0::2]/.at[1::2] interleave order is the contract
        from paddle_tpu.generation import _dq
        from paddle_tpu.ops.quant import weight_quantize, weight_dequantize
        rng = np.random.RandomState(33)
        w = jnp.asarray(rng.randn(3, 16, 8), jnp.float32)
        q4, s = jax.vmap(
            lambda t: weight_quantize(t, algo="weight_only_int4"))(w)
        d = {"wup_q4": q4, "wup_s": s.astype(jnp.float32)}
        got = _dq(d, "wup", jnp.float32)
        exp = jax.vmap(lambda q, sc: weight_dequantize(
            q, sc, algo="weight_only_int4"))(q4, s.astype(jnp.float32))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


class TestInt4Dequantize:
    """int4_dequantize — the whole-tensor unpack kernel behind the MLA
    absorbed projections (wkvb is reshaped/sliced, so the
    split-contraction matmul doesn't apply). Must be EXACT against
    weight_dequantize, including non-128-multiple N (mirrors the PR-5
    lm-head padding fix)."""

    def test_unaligned_n_exact(self):
        from paddle_tpu.ops.quant import (int4_dequantize, weight_quantize,
                                          weight_dequantize)
        rng = np.random.RandomState(2)
        for N in (160, 8, 136, 128):
            w = jnp.asarray(rng.randn(32, N), jnp.float32)
            q4, s = weight_quantize(w, algo="weight_only_int4")
            got = int4_dequantize(q4, s)
            exp = weight_dequantize(q4, s, algo="weight_only_int4")
            assert got.shape == (32, N)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(exp),
                                          err_msg=f"N={N}")


class TestMlaInt4:
    """Packed-int4 MLA decode (VERDICT item 6 tail + ISSUE 14): attention
    projections + head run int4 (absorbed wkvb read whole via
    int4_dequantize); since ISSUE 14 the FFN/expert stacks pack int4
    too (3-D per-expert packing, read back through _dq)."""

    @pytest.fixture(scope="class")
    def model(self):
        from paddle_tpu.models.deepseek import (DeepSeekV2ForCausalLM,
                                                deepseek_v2_tiny_config)
        paddle.seed(11)
        m = DeepSeekV2ForCausalLM(deepseek_v2_tiny_config(
            moe_dropless=True, num_hidden_layers=2,
            max_position_embeddings=32))
        m.eval()
        return m

    def test_generate_cached_int4_runs(self, model):
        rng = np.random.RandomState(7)
        ids = paddle.to_tensor(
            rng.randint(1, model.config.vocab_size, (1, 4)).astype("int32"))
        toks, _ = generate_cached(model, ids, max_new_tokens=4,
                                  decode_strategy="greedy_search",
                                  weight_only_quant="int4")
        assert toks.numpy().shape == (1, 4)

    def test_int4_covers_attention_and_expert_stacks(self, model):
        # layout check (ISSUE 14): attention projections AND the 3-D
        # expert stacks carry _q4 keys; the router gate stays fp
        from paddle_tpu.generation import _decode_params
        p = _decode_params(model, weight_only_quant="int4")
        L = p["layers"][0]
        assert any(k.endswith("_q4") for k in L
                   if not k.startswith("head"))
        moe_layers = [q for q in p["layers"] if "moe" in q]
        assert moe_layers and all(
            "wup_q4" in q["moe"] and "wdn_q4" in q["moe"]
            and "gate_q4" not in q["moe"] for q in moe_layers)


class TestBeamSearchQuant:
    def test_beam_search_cached_int8_runs(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        from paddle_tpu.generation import beam_search_cached
        paddle.seed(37)
        m = LlamaForCausalLM(llama_tiny_config(max_position_embeddings=32))
        m.eval()
        rng = np.random.RandomState(6)
        ids = paddle.to_tensor(
            rng.randint(1, m.config.vocab_size, (1, 4)).astype("int32"))
        toks, sc = beam_search_cached(m, ids, max_new_tokens=4,
                                      num_beams=2,
                                      weight_only_int8=True)
        assert toks.numpy().shape[-1] == 4
