"""The LFM2-MoE hybrid's own forward (`paddle_tpu.models.lfm2`: gated
short convolutions beside rotary GQA with q / k norms, a dense then
routed FFNs with a bias that picks, a tied head) against the plain
float32 reference (`benchmarks/lib/reference_lfm2.py`) on seeded weights;
the convolution against a loop written here; the router's bias; the
planted faults, which have to show."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import reference_lfm2 as ref
from benchmarks.systems.lfm2_serving import reader_config, reference_weights
from paddle_tpu.generation import _cached_step_body, _decode_params, generate
from paddle_tpu.incubate import moe
from paddle_tpu.models.lfm2 import (Lfm2MoeConfig, Lfm2MoeForCausalLM,
                                    lfm2_tiny_config, short_conv)

CFG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "layer_types", "num_attention_heads",
            "num_key_value_heads", "moe_intermediate_size", "norm_eps",
            "norm_topk_prob", "num_dense_layers", "num_experts",
            "num_experts_per_tok", "routed_scaling_factor",
            "use_expert_bias", "conv_L_cache")


def seeded(**kw):
    """A seeded toy LFM2 whose every mechanism carries signal (gains
    N(1, 0.3), a sharp softmax through ``q_layernorm``, a router and an
    expert bias of the scores' own spread), its reference weights and
    the reference's configuration."""
    paddle.seed(0)
    cfg = lfm2_tiny_config(**kw)
    m = Lfm2MoeForCausalLM(cfg)
    m.eval()
    rng = np.random.default_rng(0)

    def draw(p, mean, std):
        p._data = jnp.asarray(rng.normal(mean, std, p._data.shape),
                              jnp.float32)

    for n, p in m.named_parameters():
        if n.endswith("q_layernorm.weight"):
            draw(p, 3, 0.3)
        elif n.endswith("norm.weight"):
            draw(p, 1, 0.3)
        elif n.endswith("e_score_correction_bias"):
            draw(p, 0, 0.15)
        elif n.endswith("gate_weight"):
            draw(p, 0, 0.3)
    c = {k: getattr(cfg, k) for k in CFG_KEYS}
    c["rope_parameters"] = {"rope_theta": cfg.rope_theta}
    return m, reference_weights(m), reader_config(c)


@pytest.fixture(scope="module")
def tiny():
    return seeded()


IDS = np.random.default_rng(1).integers(0, 96, 37).astype(np.int32)


def _model(m, ids):
    return np.asarray(m(paddle.to_tensor(ids[None]))._data)[0]


def test_the_pattern_follows_the_published_layer_types():
    c = Lfm2MoeConfig(num_hidden_layers=40, layers_held=range(10))
    assert c.pattern == "CDCD" + 2 * "*ECECECE"
    assert c.layer_types[:6] == ("conv", "conv", "full_attention", "conv",
                                 "conv", "conv")
    assert (c.head_dim, c.conv_kernel, c.conv_dim) == (64, 3, 2048)
    assert lfm2_tiny_config().pattern == "CDCD*ECECECE"
    with pytest.raises(ValueError, match="layers_held"):
        Lfm2MoeConfig(num_hidden_layers=4, layers_held=(3, 2))
    with pytest.raises(NotImplementedError, match="untied"):
        Lfm2MoeConfig(tie_embedding=False)


def test_model_logits_match_the_reference(tiny):
    m, w, c = tiny
    want = np.asarray(ref.logits(jnp.asarray(IDS), w, c))
    got = _model(m, IDS)
    assert got.shape == want.shape == (37, 96)
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert want.std() > 0.5
    # in blocks (queries, experts, vocabulary) it is the same function
    blocked = np.asarray(ref.logits(jnp.asarray(IDS), w, c, q_block=16,
                                    expert_block=2, vocab_block=32))
    np.testing.assert_allclose(blocked, want, atol=2e-5)


def test_the_convolution_against_a_loop():
    """Depthwise, causal, zeros left of the sequence, no bias, NO
    activation: the model's and the reference's, against numpy."""
    rng = np.random.default_rng(2)
    S, W, K = 11, 5, 3
    u = rng.normal(size=(S, W)).astype(np.float32)
    w = rng.normal(size=(W, K)).astype(np.float32)
    want = np.zeros((S, W), np.float32)
    for t in range(S):
        for j in range(K):
            src = t - (K - 1) + j
            if src >= 0:
                want[t] += w[:, j] * u[src]
    ext = jnp.concatenate([jnp.zeros((K - 1, W)), jnp.asarray(u)])
    np.testing.assert_allclose(short_conv(ext, jnp.asarray(w)), want,
                               atol=1e-6)
    spec = ref.Spec(1, 1, 1e-5, 1, True, 1.0, 0, 0, frozenset(), 0, 0)
    np.testing.assert_allclose(
        ref.short_conv(jnp.asarray(u), jnp.asarray(w), spec), want,
        atol=1e-6)
    assert (want < 0).any()         # no activation clipped it
    # an adoption at 6 without its snapshot moves rows 6 and 7 only
    cut = spec._replace(ablate=frozenset(["tail_zero"]), cut=6)
    off = np.asarray(ref.short_conv(jnp.asarray(u), jnp.asarray(w), cut))
    moved = np.abs(off - want).max(-1) > 1e-6
    assert moved.tolist() == [t in (6, 7) for t in range(S)]


def test_the_bias_picks_and_does_not_weigh(tiny):
    """`_route` with a bias: the choice is of s + b, the weights are the
    chosen experts' own s, renormalised — the reference's routing, and
    the bias changes the choice at some rows."""
    _, w, c = tiny
    layer = w["layers"][2]          # the first routed layer
    rng = np.random.default_rng(3)
    h2 = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    s = jax.nn.sigmoid(h2 @ layer["router"])
    gv, topi, _, _ = moe._route(s, 2, True, None, 1.0, bias=layer["bias"])
    spec = ref.Spec(4, 2, 1e-5, 2, True, 1.0, 0, 0, frozenset(), 0, 0)
    wts, ids = ref.routing(h2, layer["router"], layer["bias"], spec)
    np.testing.assert_array_equal(topi, ids)
    np.testing.assert_allclose(gv, wts, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(gv),
        np.take_along_axis(np.asarray(s), np.asarray(topi), -1)
        / np.take_along_axis(np.asarray(s), np.asarray(topi), -1)
        .sum(-1, keepdims=True), atol=1e-5)
    plain = jax.lax.top_k(s, 2)[1]
    flipped = np.any(np.sort(plain, -1) != np.sort(topi, -1), -1).mean()
    assert 0.05 < flipped < 0.9


@pytest.mark.parametrize("what", ref.ABLATIONS)
def test_a_planted_fault_shows(tiny, what):
    """In float32 every planted fault is far outside the tolerance."""
    _, w, c = tiny
    want = np.asarray(ref.logits(jnp.asarray(IDS), w, c))
    off = np.asarray(ref.logits(jnp.asarray(IDS), w, c,
                                ablate=frozenset([what])))
    # a hundred times the tolerance the model is held to above (the
    # bias's weighing is the smallest: renormalised, it moves a routed
    # layer's weights by a few percent, 0.03 here)
    assert np.abs(off - want).max() > 100 * 2e-4


def test_generate_runs_it_and_the_cached_paths_refuse_it(tiny):
    m, w, c = tiny
    out, _ = generate(m, paddle.to_tensor(IDS[None, :9]), max_new_tokens=3,
                      decode_strategy="greedy_search")
    toks = np.asarray(out._data if hasattr(out, "_data") else out)[0]
    fed = list(IDS[:9])
    for t in toks:
        assert int(t) == int(np.asarray(
            ref.logits(jnp.asarray(fed, jnp.int32), w, c))[-1].argmax())
        fed.append(int(t))
    p = _decode_params(m)
    assert p["family"] == "hybrid" and p["head"] is None
    assert p["pattern"] == "CDCD*ECECECE"
    assert all(st["held"] is None and st["score"] == "sigmoid"
               for st in p["moe_static"])
    with pytest.raises(NotImplementedError, match="LFM2"):
        _cached_step_body(p, 64)
