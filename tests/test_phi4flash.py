"""Phi-4-mini-flash's own forward (`paddle_tpu.models.phi4flash`: all
five mixer kinds, the selective-scan kernel from zero state, differential
heads) against the plain float32 reference
(`benchmarks/lib/reference_phi4flash.py`: the recurrence token by token,
the two softmaxes of a differential head apart on unpadded heads) on
seeded weights; the parameter count by hand at the published sizes; and
each wrong reading of the layout caught by a case."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import costs_phi4flash as costs
from benchmarks.lib import reference_phi4flash as ref
from paddle_tpu.models.phi4flash import (Phi4FlashConfig,
                                         Phi4FlashForCausalLM, layer_kinds,
                                         pair_queries, phi4flash_config,
                                         phi4flash_tiny_config)

#: the catalog row's `config`, key for key
PUBLISHED = dict(
    embd_pdrop=0, hidden_act="silu", hidden_size=2560,
    intermediate_size=10240, layer_norm_eps=1e-05,
    max_position_embeddings=262144, mb_per_layer=2, model_type="phi4flash",
    num_attention_heads=40, num_hidden_layers=32, num_key_value_heads=20,
    resid_pdrop=0, sliding_window=512, tie_word_embeddings=True,
    mlp_bias=False, lm_head_bias=False, vocab_size=200064)


def reference_config(cfg: Phi4FlashConfig) -> dict:
    """`reference_phi4flash`'s configuration of a `Phi4FlashConfig`."""
    return dict(hidden_size=cfg.hidden_size,
                num_hidden_layers=cfg.num_hidden_layers,
                num_attention_heads=cfg.num_attention_heads,
                num_key_value_heads=cfg.num_key_value_heads,
                sliding_window=cfg.sliding_window,
                layer_norm_eps=cfg.layer_norm_eps,
                intermediate_size=cfg.intermediate_size,
                vocab_size=cfg.vocab_size,
                mamba_d_state=cfg.ssm_state_size,
                mamba_d_conv=cfg.conv_kernel, mamba_dt_rank=cfg.dt_rank)


def model_weights(m) -> dict:
    """The reference's weight names over the model's own arrays."""
    return jax.tree_util.tree_map(lambda t: t._data, m.weights(),
                                  is_leaf=lambda t: hasattr(t, "_data"))


def seeded(**kw):
    """A seeded toy whose every mechanism carries signal (biases, gains
    and lambda vectors drawn, sharp scores, a memory that reaches the
    gated units), its reference weights and the reference's
    configuration."""
    paddle.seed(0)
    cfg = phi4flash_tiny_config(**kw)
    m = Phi4FlashForCausalLM(cfg)
    m.eval()
    rng = np.random.default_rng(0)

    def draw(p, mean, std):
        p._data = jnp.asarray(rng.normal(mean, std, p._data.shape),
                              jnp.float32)

    for n, p in m.named_parameters():
        if "layernorm" in n or n.endswith("subln"):
            draw(p, float(n.endswith("weight") or n.endswith("subln")), 0.3)
        elif n.endswith("proj.bias") or n.endswith("conv_bias"):
            draw(p, 0, 0.2)
        elif "lambda" in n:
            draw(p, 0, 0.4)
        elif n.endswith(".D"):
            draw(p, 1, 0.5)
        elif n.endswith("q_proj.weight"):
            draw(p, 0, 0.5)
        elif n.endswith("embed_tokens.weight"):
            draw(p, 0, 1.0)
    return m, model_weights(m), reference_config(cfg)


@pytest.fixture(scope="module")
def tiny():
    return seeded()


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(1).integers(0, 96, (2, 41)).astype(np.int32)


@pytest.fixture(scope="module")
def own(tiny, ids):
    """The model's own logits [2, 41, 96]."""
    return np.asarray(tiny[0](paddle.to_tensor(ids))._data)


def test_the_model_is_the_reference_on_logits(tiny, ids, own):
    _, w, c = tiny
    assert layer_kinds(8) == ref.layer_kinds(8) == "SWSWSFGX"
    for row, got in zip(ids, own):
        want = np.asarray(ref.logits(jnp.asarray(row), w, c))
        np.testing.assert_allclose(got, want, atol=2e-4)
        # blocked, the reference is the same reference
        blocked = np.asarray(ref.logits(jnp.asarray(row), w, c, q_block=16,
                                        ffn_block=32))
        np.testing.assert_allclose(blocked, want, atol=2e-4)


#: how far each wrong reading moves the logits of the toy (largest
#: absolute difference over 41 positions; the true reading: 2e-4)
@pytest.mark.parametrize("fault", [f for f in ref.ABLATIONS
                                   if f != "state_bf16"])
def test_a_wrong_reading_is_caught_on_logits(tiny, ids, own, fault):
    _, w, c = tiny
    off = np.asarray(ref.logits(jnp.asarray(ids[0]), w, c,
                                ablate=frozenset([fault])))
    assert np.abs(off - own[0]).max() > 5e-3, fault


def test_a_bfloat16_state_is_caught_on_the_state(tiny, ids):
    """Logits hardly see a rounded state at 41 tokens; the state does."""
    _, w, c = tiny
    row = jnp.asarray(ids[0])
    at = c["num_hidden_layers"] // 2
    _, good = ref.hidden_states(row, w["embed"], w["layers"], c,
                                jnp.float32, state_of=at)
    _, bad = ref.hidden_states(row, w["embed"], w["layers"], c, jnp.float32,
                               state_of=at, ablate=frozenset(["state_bf16"]))
    rel = float(jnp.linalg.norm(bad - good) / jnp.linalg.norm(good))
    assert 1e-4 < rel < 1e-1


def test_the_padded_pair_is_the_head_it_uses():
    """`pair_queries`: a 2D-wide score against (K_2j | K_2j+1) is the
    D-wide score against the one head the query head uses."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(0, 1, (3, 8, 4)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (5, 4, 4)), jnp.float32)
    pairs = k.reshape(5, 2, 8)
    got = jnp.einsum("thw,shw->ths", pair_queries(q),
                     pairs[:, np.arange(8) // 4])
    want = jnp.einsum("thd,shd->ths", q,
                      k[:, 2 * (np.arange(8) // 4) + np.arange(8) % 2])
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_the_parameter_count_by_hand_at_the_published_sizes():
    """119,895,040 / 98,322,304 / 104,867,840 / 91,766,144 a layer kind;
    3,852,562,944 whole — the model's own name, 3.8B — by `costs` and by
    the model's parameters (built lazily: no array is made)."""
    c = dict(PUBLISHED)
    assert costs.ffn_params(c) == 78_643_200
    assert costs.mamba_params(c) == 41_241_600
    assert costs.attention_params(c) == 19_668_864
    assert costs.attention_params(c, cross=True) == 13_112_704
    assert costs.gmu_params(c) == 26_214_400
    assert [costs.layer_params(c, k) for k in "SWGX"] == [
        119_895_040, 98_322_304, 104_867_840, 91_766_144]
    kinds = costs.layer_kinds(c)
    assert kinds == layer_kinds(32) == "SW" * 8 + "SF" + "GX" * 7
    assert [kinds.count(k) for k in "SWFGX"] == [9, 8, 1, 7, 7]
    assert costs.n_params(c) == 9 * 119_895_040 + 9 * 98_322_304 \
        + 7 * 104_867_840 + 7 * 91_766_144 + 512_163_840 + 5_120 \
        == 3_852_562_944
    with paddle.LazyGuard():
        m = Phi4FlashForCausalLM(phi4flash_config(**PUBLISHED))
    assert sum(int(np.prod(p._data.shape))
               for _, p in m.named_parameters()) == 3_852_562_944
    assert costs.state_only_bytes(c) == 327_680
    assert costs.state_bytes(c) == 327_680 + 30_720
    assert costs.kv_row_bytes(c) == 5_120
    assert costs.page_bytes(c, 256) == 1_310_720
    cfg = m.config
    assert (cfg.d_inner, cfg.dt_rank, cfg.head_dim) == (5120, 160, 64)
    assert cfg.pattern == "SD*D" * 8 + "SD*D" + "G32DX34D" * 7


def test_costs_by_hand():
    c = dict(PUBLISHED)
    # one slot's update: 7 FLOPs an element of [5120, 16]; the state in
    # and out, dt / x / y rows and B / C, A once
    assert costs.ssm1_update_cost(c, 1) == (
        7.0 * 81_920, 2 * 327_680 + (3 * 5120 + 32) * 4 + 327_680)
    assert costs.ssm1_update_cost(c, 0) == (0.0, 0.0)
    # W_x [5120, 192], W_dt [160, 5120] and its bias, bfloat16
    assert costs.ssm1_operand_weight_bytes(c) == 2 * (
        983_040 + 819_200 + 5_120)
    flops, byts = costs.ssm1_scan_cost(c, 256, starts=True)
    assert flops == 7.0 * 81_920 * 256
    assert byts == 3 * 327_680 + 256 * (3 * 5120 + 32) * 4 + 327_680
    # a decode row at context 1,000: 40 heads, QK^T over 64, PV over 128
    flops, byts = costs.attention_cost(c, [(1, 1000)])
    assert flops == (128 + 256) * 40 * 1000.0
    assert byts == 5120 * 1000 + 40 * 192 * 2
    _, wb = costs.attention_cost(c, [(1, 1000)], window=512)
    assert wb == 5120 * 512 + 40 * 192 * 2
    # the shared pool once a reader: 8; the window layers once each
    got = costs.serve_step_bytes(c, 10, 1, 1, 0, 100, 50)
    assert got == 10 + 2 * 2560 + 9 * 358_400 * 2 + 5120 * (800 + 400)


def test_what_the_config_refuses():
    with pytest.raises(ValueError, match="whole"):
        phi4flash_tiny_config(num_hidden_layers=6)
    with pytest.raises(NotImplementedError):
        phi4flash_tiny_config(tie_word_embeddings=False)
    with pytest.raises(ValueError, match="pair"):
        phi4flash_tiny_config(num_key_value_heads=2)
