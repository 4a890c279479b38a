"""The Ouro looped decoder's own forward (`paddle_tpu.models.ouro`)
against the plain float32 reference (`benchmarks/lib/reference_ouro.py`)
on seeded weights: the logits, every pass's state, the exit
distribution; one pass is the one-pass sandwich decoder; a threshold
under 1 lets tokens leave early."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import reference_ouro as ref
from benchmarks.systems.ouro_serving import model_layers
from paddle_tpu.models.ouro import (OuroForCausalLM, exit_distribution,
                                    ouro_tiny_config)

CFG_KEYS = ("num_attention_heads", "head_dim", "rms_norm_eps", "rope_theta",
            "total_ut_steps", "early_exit_threshold", "vocab_size")


def seeded(**kw):
    """A seeded toy Ouro whose every mechanism carries signal (gains
    N(1, 0.3), a sharp softmax, a gate of deviation ~1), its reference
    weights and the reference's configuration."""
    paddle.seed(0)
    cfg = ouro_tiny_config(**kw)
    m = OuroForCausalLM(cfg)
    m.eval()
    rng = np.random.default_rng(0)
    for n, p in m.named_parameters():
        if "norm" in n:
            p._data = jnp.asarray(rng.normal(1, 0.3, p._data.shape),
                                  jnp.float32)
        if "q_proj" in n:
            p._data = p._data * 4
        if "early_exit_gate" in n:
            p._data = jnp.asarray(rng.normal(0, 0.15 if p._data.ndim == 2
                                             else 1.0, p._data.shape),
                                  jnp.float32)
    gate = m.model.early_exit_gate
    w = {"embed": m.model.embed_tokens.weight._data,
         "norm": m.model.norm.weight._data, "head": m.lm_head.weight._data,
         "gate_w": gate.weight._data[:, 0], "gate_b": gate.bias._data[0],
         "layers": model_layers(m)}
    return m, w, {k: getattr(cfg, k) for k in CFG_KEYS}


@pytest.fixture(scope="module")
def tiny():
    return seeded()


IDS = np.random.default_rng(1).integers(0, 96, 37).astype(np.int32)


def _model(m, ids):
    logits, hs, p = m(paddle.to_tensor(ids[None]), return_passes=True)
    return (np.asarray(logits._data)[0], [np.asarray(h._data)[0] for h in hs],
            np.asarray(p._data)[0])


def test_logits_match_the_reference(tiny):
    m, w, c = tiny
    got, _, _ = _model(m, IDS)
    want, _, _ = ref.logits(jnp.asarray(IDS), w, c)
    assert got.shape == (37, 96) and np.asarray(want).std() > 0.3
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("u", [0, 1, 2])
def test_every_pass_state_matches_the_reference(tiny, u):
    m, w, c = tiny
    _, hs, _ = _model(m, IDS)
    want = ref.pass_states(jnp.asarray(IDS), w, c)
    assert len(hs) == len(want) == 3
    np.testing.assert_allclose(hs[u], want[u], atol=1e-4)
    if u:   # a pass does something: its state is not the one before's
        assert np.abs(hs[u] - hs[u - 1]).max() > 0.1


def test_exit_distribution_sums_to_one_and_matches(tiny):
    m, w, c = tiny
    _, _, p = _model(m, IDS)
    want = ref.exit_distribution(ref.pass_states(jnp.asarray(IDS), w, c),
                                 w["gate_w"], w["gate_b"])
    assert p.shape == (37, 3)
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(p, want, atol=1e-4)
    assert p.min() > 1e-3 and p.std() > 0.05       # no pass is idle
    lam = jnp.asarray([[0.25, 0.5, 0.9]])
    np.testing.assert_allclose(exit_distribution(lam),
                               [[0.25, 0.375, 0.375]], atol=1e-7)


def test_blocked_queries_and_columns_change_nothing(tiny):
    _, w, c = tiny
    ids = jnp.asarray(np.resize(IDS, 48))
    plain = ref.pass_states(ids, w, c)[-1]
    blocked = ref.pass_states(ids, w, c, q_block=16, ffn_block=32)[-1]
    np.testing.assert_allclose(plain, blocked, atol=1e-4)


def test_one_pass_is_the_one_pass_sandwich_decoder():
    """`total_ut_steps` 1: the same weights give the reference's
    one-pass fault, which is a plain sandwich decoder."""
    m, w, c = seeded(total_ut_steps=1)
    got, hs, p = _model(m, IDS)
    assert len(hs) == 1
    np.testing.assert_allclose(p, 1.0)
    three = dict(c, total_ut_steps=3)
    one = ref.pass_states(jnp.asarray(IDS), w, three,
                          ablate=frozenset(["passes_1"]))
    np.testing.assert_allclose(hs[0], one[0], atol=1e-4)
    want, _, _ = ref.logits(jnp.asarray(IDS), w, c)
    np.testing.assert_allclose(got, want, atol=1e-4)
    # and it is not what three passes give
    far = ref.pass_states(jnp.asarray(IDS), w, three)[-1]
    assert np.abs(np.asarray(far) - hs[0]).max() > 0.1


@pytest.mark.parametrize("fault", ref.ABLATIONS)
def test_every_planted_fault_moves_the_logits(tiny, fault):
    _, w, c = tiny
    ids = jnp.asarray(IDS)
    want = ref.pass_states(ids, w, c)[-1]
    off = ref.pass_states(ids, w, c, ablate=frozenset([fault]))[-1]
    assert np.abs(np.asarray(off - want)).max() > 0.05, fault


def test_a_threshold_under_one_lets_tokens_leave_early():
    m, w, c = seeded(early_exit_threshold=0.6)
    got, hs, p = _model(m, IDS)
    want, _, _ = ref.logits(jnp.asarray(IDS), w, c)
    np.testing.assert_allclose(got, want, atol=1e-4)
    leave = (np.cumsum(p, -1) >= 0.6).argmax(-1)
    assert len(set(leave)) > 1          # not every token at one pass
    head = np.asarray(w["head"])
    for t in (0, 17, 36):
        np.testing.assert_allclose(got[t], hs[leave[t]][t] @ head, atol=1e-4)
