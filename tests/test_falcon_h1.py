"""Falcon-H1's own forward (`paddle_tpu.models.falcon_h1`: both mixers
on one norm, the chunked scan from zero state, fourteen multipliers)
against the plain float32 reference
(`benchmarks/lib/reference_falcon.py`: the recurrence token by token) on
seeded weights; the parameter count by hand at the published sizes;
every multiplier, the column order of ``m`` and the rotary pairing each
caught by a case; and the cut: the first layers of an uncut toy, and a
slice of its vocabulary, are what the cut model computes."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import costs_falcon as costs, reference_falcon as ref
from benchmarks.systems.falcon_serving import model_layers
from paddle_tpu.models.falcon_h1 import (MULTIPLIERS, FalconH1ForCausalLM,
                                         falcon_h1_config,
                                         falcon_h1_tiny_config, mup_vector)

CFG_KEYS = ("num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "mamba_n_heads",
            "mamba_d_head", "mamba_d_ssm", "mamba_d_state",
            "mamba_n_groups", "mamba_d_conv", "mamba_chunk_size",
            "rms_norm_eps", "rope_theta", "vocab_size", "hidden_size",
            "intermediate_size")


def reference_config(cfg) -> dict:
    """`reference_falcon`'s configuration (the published names) of a
    `FalconH1Config`."""
    c = {k: getattr(cfg, k) for k in CFG_KEYS}
    mu = cfg.multipliers
    c.update({k + "_multiplier": mu[k] for k in MULTIPLIERS[:7]})
    c["ssm_multipliers"] = [mu[k] for k in MULTIPLIERS[7:12]]
    c["mlp_multipliers"] = [mu[k] for k in MULTIPLIERS[12:]]
    return c


def seeded(**kw):
    """A seeded toy Falcon-H1 whose every mechanism carries signal
    (gains N(1, 0.3), a convolution bias, `D` N(1, 0.5), sharp scores,
    every multiplier its own value), its reference weights and the
    reference's configuration."""
    paddle.seed(0)
    cfg = falcon_h1_tiny_config(**kw)
    m = FalconH1ForCausalLM(cfg)
    m.eval()
    rng = np.random.default_rng(0)

    def draw(p, mean, std):
        p._data = jnp.asarray(rng.normal(mean, std, p._data.shape),
                              jnp.float32)

    for n, p in m.named_parameters():
        if n.endswith("norm.weight") or n.endswith("layernorm.weight"):
            draw(p, 1, 0.3)
        elif n.endswith("conv_bias"):
            draw(p, 0, 0.2)
        elif n.endswith(".D"):
            draw(p, 1, 0.5)
        elif n.endswith("q_proj.weight"):
            draw(p, 0, 0.6)
        elif n.endswith("in_proj.weight"):
            draw(p, 0, 0.4)     # dt and the gate swing by a few units
        elif n.endswith("embed_tokens.weight"):
            draw(p, 0, 1.0)
    w = {"embed": m.model.embed_tokens.weight._data,
         "norm": m.model.final_layernorm.weight._data,
         "head": m.lm_head.weight._data, "layers": model_layers(m)}
    return m, w, reference_config(cfg)


@pytest.fixture(scope="module")
def tiny():
    return seeded()


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(1).integers(0, 96, (2, 21)).astype(np.int32)


@pytest.fixture(scope="module")
def model_logits(tiny, ids):
    return np.asarray(tiny[0](paddle.to_tensor(ids))._data, np.float32)


def test_model_matches_the_reference(tiny, ids, model_logits):
    """21 tokens: the model's scan crosses two scan-chunk borders (8,
    16); the reference walks token by token."""
    _, w, c = tiny
    for b in range(2):
        want = np.asarray(ref.logits(jnp.asarray(ids[b]), w, c))
        assert want.shape == (21, 96) and want.std() > 0.05
        np.testing.assert_allclose(model_logits[b], want, atol=2e-4)


@pytest.mark.parametrize("what", [a for a in ref.ABLATIONS
                                  if a != "state_bf16"])
def test_a_fault_shows(tiny, ids, model_logits, what):
    """Each multiplier dropped (read as 1), ``m`` in another column
    order, interleaved rotary pairs, no rotation: the model's logits are
    NOT that reference's, by twenty times the 2e-4 the true reference
    is matched to (the least is B's multiplier, whose scale the gated
    group norm takes out again but for the `D x'` term: 0.008)."""
    _, w, c = tiny
    off = np.asarray(ref.logits(jnp.asarray(ids[0]), w, c,
                                ablate=frozenset([what])))
    far = np.abs(model_logits[0] - off).max()
    assert far > 4e-3, (what, far)


def test_a_bfloat16_state_shows_in_the_state_not_in_the_logits(
        tiny, ids, model_logits):
    """The recurrent state rounded to bfloat16 after every token moves
    the logits of 21 tokens by no more than the float32 sums' own order
    does — which is why the benchmark's check compares the STATE: the
    last layer's, after the last token, is 2^-9 off."""
    _, w, c = tiny
    fed = jnp.asarray(ids[0])
    off = np.asarray(ref.logits(fed, w, c, ablate=frozenset(["state_bf16"])))
    assert np.abs(model_logits[0] - off).max() < 3e-3
    states = [np.asarray(ref.hidden_states(
        fed, w["embed"], w["layers"], c, jnp.float32, ablate=frozenset(a),
        state_of=1)[1]) for a in ((), ("state_bf16",))]
    assert states[0].shape == (4, 8, 128)
    rel = np.linalg.norm(states[1] - states[0]) / np.linalg.norm(states[0])
    assert 5e-4 < rel < 2e-2, rel


def test_the_fourteen_multipliers_are_distinct_and_named(tiny):
    m, _, c = tiny
    mu = m.config.multipliers
    assert tuple(mu) == MULTIPLIERS == ref.MULTIPLIERS and len(mu) == 14
    assert len(set(mu.values())) == 14 and 1.0 not in mu.values()
    assert ref.multipliers(c) == mu
    # ``m``: ssm_multipliers[0..4] over [z | x' | B | C | dt]
    v = np.asarray(mup_vector(m.config, jnp.float32))
    assert v.shape == (32 + 32 + 2 * 2 * 128 + 4,)
    cuts = np.cumsum([0, 32, 32, 256, 256, 4])
    for (a, b), k in zip(zip(cuts, cuts[1:]), MULTIPLIERS[7:12]):
        assert (v[a:b] == np.float32(mu[k])).all(), k


def test_the_published_defaults_are_the_catalog_row():
    cfg = falcon_h1_config(model_type="falcon_h1", num_logits_to_keep=1,
                           mlp_expansion_factor=8)
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size) == \
        (5120, 72, 261120)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.conv_dim) == (32, 128, 256, 2, 5120)
    assert cfg.pattern == "[M*]D" * 72 and cfg.rope_theta == 1e11
    mu = cfg.multipliers
    assert mu["key"] == 0.011048543456039804 and mu["attention_in"] == 1.0
    assert mu["ssm_B"] == 0.1767766952966369 and mu["mlp_down"] == \
        0.011160714285714284
    for bad in (dict(mamba_norm_before_gate=True), dict(mamba_use_mlp=False),
                dict(attn_layer_indices=[0]), dict(attention_bias=True),
                dict(tie_word_embeddings=True)):
        with pytest.raises(NotImplementedError):
            falcon_h1_config(**bad)
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        falcon_h1_config(mamba_d_ssm=4000)


def test_parameters_by_hand_at_published_sizes():
    """430,120,032 a layer; 33,642,516,224 whole — the model's own
    "34B"; 4,205,319,008 held by the benchmark's cut (9 layers, an
    eighth of the vocabulary)."""
    whole = dict(hidden_size=5120, num_attention_heads=20,
                 num_key_value_heads=4, head_dim=128,
                 intermediate_size=21504, mamba_d_ssm=4096,
                 mamba_n_heads=32, mamba_d_head=128, mamba_d_state=256,
                 mamba_n_groups=2, mamba_d_conv=4, num_hidden_layers=72,
                 vocab_size=261120)
    # W_in 5120 x 9248; the convolution 5120 x 4 and its bias; dt_bias,
    # A_log, D; the gated norm's gain; W_out 4096 x 5120
    assert costs.mamba_params(whole) == 47_349_760 + 25_600 + 96 + 4_096 \
        + 20_971_520 == 68_351_072
    assert costs.attention_params(whole) == 31_457_280
    assert costs.ffn_params(whole) == 330_301_440
    assert costs.layer_params(whole) == 430_120_032
    assert costs.n_params(whole) == 72 * 430_120_032 \
        + 2 * 261_120 * 5_120 + 5_120 == 33_642_516_224
    held = dict(whole, num_hidden_layers=9, vocab_size=32_640)
    assert costs.n_params(held) == 4_205_319_008
    assert costs.state_only_bytes(held) == 4_194_304
    assert costs.state_bytes(held) == 4_194_304 + 30_720
    assert costs.kv_row_bytes(held) == 2_048
    # the model's own count, at toy widths
    m, _, c = seeded()
    got = sum(int(np.prod(p._data.shape)) for _, p in m.named_parameters())
    assert got == costs.n_params(c)


def test_the_cut_is_the_first_layers_and_a_slice_of_the_vocabulary(ids):
    """An uncut toy of 4 layers over 96 rows against its cut (2 layers,
    the first 48 rows of the embedding and of the head): the cut's
    hidden states are the uncut's after layer 1, and its logits the
    uncut head's first 48 columns over them."""
    whole, _, _ = seeded(num_hidden_layers=4)
    cut, _, _ = seeded(num_hidden_layers=2, vocab_size=48)
    src = dict(whole.named_parameters())
    for n, p in cut.named_parameters():
        a = src[n]._data
        p._data = a[:48] if "embed_tokens" in n else \
            a[:, :48] if "lm_head" in n else a
    few = paddle.to_tensor(ids % 48)
    x = whole.model.embed_tokens(few) * whole.config.multipliers["embedding"]
    for lyr in list(whole.model.layers)[:2]:
        x = lyr(x)
    want = whole.lm_head(whole.model.final_layernorm(x))._data[..., :48] \
        * whole.config.multipliers["lm_head"]
    np.testing.assert_allclose(cut(few)._data, want, atol=1e-5)
