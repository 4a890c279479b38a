"""The Nemotron-H hybrid's own forward (`paddle_tpu.models.nemotron_h`:
the chunked scan from zero state, `_ffn_apply`'s latent routed FFN)
against the plain float32 reference
(`benchmarks/lib/reference_nemotron.py`: the recurrence token by token)
on seeded weights: each block kind alone and the published stage's
pattern; the four planted faults, which have to show; the share test —
four chips' routed addends through the linear `W_up` plus the shared
expert ONCE add up to the uncut layer; and `_route` / the experts'
activations unchanged where no bias and no relu2 is asked for."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import reference_nemotron as ref
from benchmarks.systems.nemotron_serving import model_layers
from paddle_tpu.generation import _ffn_apply
from paddle_tpu.incubate import moe
from paddle_tpu.models.nemotron_h import (NemotronHForCausalLM, arrays,
                                          nemotron_h_tiny_config)

CFG_KEYS = ("hybrid_override_pattern", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "mamba_num_heads", "mamba_head_dim", "n_groups",
            "ssm_state_size", "conv_kernel", "chunk_size",
            "n_routed_experts", "num_experts_per_tok",
            "moe_intermediate_size", "moe_latent_size",
            "moe_shared_expert_intermediate_size", "routed_scaling_factor",
            "norm_topk_prob", "layer_norm_epsilon", "vocab_size",
            "hidden_size", "experts_held")


def seeded(**kw):
    """A seeded toy Nemotron-H whose every mechanism carries signal
    (gains N(1, 0.3), a correction bias of the scores' own spread, a
    convolution bias, `D` N(1, 0.5), a sharp softmax), its reference
    weights and the reference's configuration."""
    paddle.seed(0)
    cfg = nemotron_h_tiny_config(**kw)
    m = NemotronHForCausalLM(cfg)
    m.eval()
    rng = np.random.default_rng(0)

    def draw(p, mean, std):
        p._data = jnp.asarray(rng.normal(mean, std, p._data.shape),
                              jnp.float32)

    for n, p in m.named_parameters():
        if n.endswith("norm.weight") or n.endswith("norm_f.weight"):
            draw(p, 1, 0.3)
        elif n.endswith("e_score_correction_bias"):
            draw(p, 0, 0.2)
        elif n.endswith("conv_bias"):
            draw(p, 0, 0.2)
        elif n.endswith(".D"):
            draw(p, 1, 0.5)
        elif n.endswith("gate_weight"):
            draw(p, 0, 0.3)
        elif "q_proj" in n:
            p._data = p._data * 4
    w = {"embed": m.model.embed_tokens.weight._data,
         "norm": m.model.norm_f.weight._data, "head": m.lm_head.weight._data,
         "layers": model_layers(m)}
    return m, w, {k: getattr(cfg, k) for k in CFG_KEYS}


@pytest.fixture(scope="module")
def tiny():
    return seeded()


IDS = np.random.default_rng(1).integers(0, 96, 37).astype(np.int32)


def _model(m, ids):
    return np.asarray(m(paddle.to_tensor(ids[None]))._data)[0]


@pytest.mark.parametrize("pattern", ["M", "*", "E", "MM", "MEMEMEM*EME"])
def test_logits_match_the_reference(pattern):
    m, w, c = seeded(hybrid_override_pattern=pattern)
    got = _model(m, IDS)
    want = np.asarray(ref.logits(jnp.asarray(IDS), w, c))
    assert got.shape == (37, 96) and want.std() > 0.3
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("fault", ref.ABLATIONS)
def test_a_planted_fault_shows(tiny, fault):
    """Each of the four omissions the cell's check has to catch moves
    the reference's own logits by far more than float32 rounding."""
    _, w, c = tiny
    ids = jnp.asarray(IDS)
    want = np.asarray(ref.logits(ids, w, c))
    off = np.asarray(ref.logits(ids, w, c, ablate=frozenset([fault])))
    err = np.sqrt(((off - want) ** 2).mean())
    # (a bfloat16 state is the smallest: rounding, 300 x float32's)
    assert err > (3e-5 if fault == "state_bf16" else 2e-2) * want.std(), err


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One chip's routed addend is linear in what its experts give, so
    the four shares' addends — each through `W_up` — plus the shared
    expert counted ONCE are the uncut layer."""
    m, w, c = seeded(hybrid_override_pattern="E")
    mix = m.model.layers[0].mixer
    a = jnp.asarray(np.random.default_rng(2).normal(0, 1, (1, 40, 32)),
                    jnp.float32)
    tree = arrays(mix.weights())
    whole = _ffn_apply(dict(moe=tree), a, mix.static())
    shared = jnp.square(jax.nn.relu(a @ tree["shared"]["su"])) \
        @ tree["shared"]["sd"]
    total = 0
    for first in range(0, 16, 4):
        part = dict(tree, wup=tree["wup"][first:first + 4],
                    wdn=tree["wdn"][first:first + 4])
        st = dict(mix.static(), held=(first, 4))
        total = total + _ffn_apply(dict(moe=part), a, st) - shared
    np.testing.assert_allclose(total + shared, whole, atol=1e-5)
    # ... and it is what the reference gives for the whole layer
    spec, = ref.specs(c)
    want, _ = ref._moe(a[0], dict(w["layers"][0]), spec, jnp.float32)
    np.testing.assert_allclose(whole[0], want, atol=1e-5)
    # a share alone is the reference's share
    spec4 = spec._replace(held=(4, 4))
    lw = dict(w["layers"][0], eu=tree["wup"][4:8], ed=tree["wdn"][4:8])
    want4, _ = ref._moe(a[0], lw, spec4, jnp.float32)
    part = dict(tree, wup=tree["wup"][4:8], wdn=tree["wdn"][4:8])
    got4 = _ffn_apply(dict(moe=part), a, dict(mix.static(), held=(4, 4)))
    np.testing.assert_allclose(got4[0], want4, atol=1e-5)


# ------------------------------------------------ what was there before
def _gates(seed=3, T=12, E=16):
    return jax.nn.softmax(jnp.asarray(
        np.random.default_rng(seed).normal(0, 1, (T, E)), jnp.float32), -1)


@pytest.mark.parametrize("held, scale, group", [
    (None, 1.0, None), ((4, 8), 2.5, None), ((0, 8), 1.0, (4, 2))])
def test_route_without_a_bias_lowers_to_the_same_text(held, scale, group):
    """`bias=None` adds no operation: the lowered text with the new
    argument left out and given as None is the same, and a zero bias
    picks and weighs as no bias."""
    g = _gates()
    old = jax.jit(lambda g: moe._route(g, 4, True, held, scale, group)[:2])
    new = jax.jit(lambda g: moe._route(g, 4, True, held, scale, group,
                                       None)[:2])
    assert old.lower(g).as_text() == new.lower(g).as_text()
    zero = moe._route(g, 4, True, held, scale, group, jnp.zeros(16))
    for a, b in zip(old(g), zero[:2]):
        np.testing.assert_array_equal(a, b)


def test_a_bias_picks_and_does_not_weigh():
    g = _gates()
    b = jnp.zeros(16).at[5].set(10.0)
    gv, topi, _, _ = moe._route(g, 4, False, None, 1.0, None, b)
    assert bool((topi == 5).any(-1).all())      # everyone picks expert 5
    np.testing.assert_allclose(gv, jnp.take_along_axis(g, topi, -1))


@pytest.mark.parametrize("ffn", [moe.dense_expert_ffn,
                                 moe.dropless_expert_ffn])
@pytest.mark.parametrize("act", ["swiglu", "gelu", "relu2"])
def test_the_experts_activations(ffn, act):
    """swiglu and gelu as they were (against the formula written out),
    relu2 the non-gated expert of two matrices."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(0, 1, (12, 8)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(0, 0.5, (16, 8, 6)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(0, 0.5, (16, 6, 8)), jnp.float32)
    g = _gates()
    y, topi = ffn(x, g, None if act == "relu2" else wg, wu, wd, top_k=4,
                  renormalize=True, activation=act)
    gv = jnp.take_along_axis(g, topi, -1)
    gv = gv / gv.sum(-1, keepdims=True)
    up = jnp.einsum("th,ehi->tei", x, wu)
    h = {"swiglu": lambda: jax.nn.silu(
            jnp.einsum("th,ehi->tei", x, wg)) * up,
         "gelu": lambda: jax.nn.gelu(up),
         "relu2": lambda: jnp.square(jax.nn.relu(up))}[act]()
    every = jnp.einsum("tei,eih->teh", h, wd)
    want = jnp.einsum("tk,tkh->th", gv, jnp.take_along_axis(
        every, topi[..., None], 1))
    np.testing.assert_allclose(y, want, atol=1e-5)
