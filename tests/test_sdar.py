"""SDAR-MoE (`paddle_tpu/models/sdar.py`): the model's forward under the
block-causal mask against the plain reference
(`benchmarks/lib/reference_sdar.py`, imported, not copied) at a toy
size, the transfer rule as a pure function, and what the batch APIs
refuse. The engine's part is `test_sdar_serving.py`."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import reference_sdar as ref
from benchmarks.systems.sdar_serving import (reference_config,
                                             reference_weights)
from paddle_tpu.generation import (_decode_params, generate,
                                   generate_cached, generate_compiled)
from paddle_tpu.models.sdar import (SDARMoeConfig, SDARMoeForCausalLM,
                                    block_causal_mask, block_passes,
                                    sdar_tiny_config)

#: float32 sums in another order (the model's einsums against the
#: reference's per-expert loop)
ATOL = 2e-5


def seeded(seed=0, **kw):
    """(the toy model, the reference's weights over its arrays, the
    reference's config)."""
    paddle.seed(seed)
    cfg = sdar_tiny_config(**kw)
    m = SDARMoeForCausalLM(cfg)
    m.eval()
    # gains that are not 1, so that a norm left out or misplaced shows
    rng = np.random.default_rng(seed)
    for lyr in m.model.layers:
        for norm in (lyr.self_attn.q_norm, lyr.self_attn.k_norm):
            norm.weight._data = jnp.asarray(
                rng.uniform(0.5, 2.0, norm.weight._data.shape), jnp.float32)
    return m, reference_weights(m), reference_config(cfg)


@pytest.mark.parametrize("block,steps", [(1, 1), (2, 1), (4, 4)])
def test_the_forward_is_the_reference_under_the_block_mask(block, steps):
    m, w, c = seeded(block_length=block, denoising_steps=steps)
    ids = np.random.default_rng(1).integers(0, 250, 19).astype(np.int32)
    got = np.asarray(m(paddle.to_tensor(ids[None]))._data)[0]
    want = np.asarray(ref.logits(jnp.asarray(ids), w, c))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=ATOL)
    # the mask matters: the causal rule gives other logits, unless B is 1
    causal = np.asarray(ref.logits(jnp.asarray(ids), w, c,
                                   ablate=frozenset(["causal"])))
    if block == 1:
        # a block of one IS the causal mask, bit for bit
        np.testing.assert_array_equal(want, causal)
        np.testing.assert_array_equal(
            np.asarray(block_causal_mask(19, 1)), np.tril(np.ones((19, 19),
                                                                   bool)))
    else:
        assert np.abs(want - causal).max() > 1e-3


@pytest.mark.parametrize("what", ["qk_norm", "renorm"])
def test_a_planted_fault_moves_the_reference(what):
    _, w, c = seeded()
    ids = jnp.asarray(np.arange(3, 20, dtype=np.int32))
    off = np.asarray(ref.logits(ids, w, c, ablate=frozenset([what])))
    assert np.abs(off - np.asarray(ref.logits(ids, w, c))).max() > 1e-3


def test_the_transfer_rule():
    z = np.full((4, 8), -5.0, np.float32)
    z[0, 3], z[1, 1], z[2, 6], z[3, 2] = 2.0, 4.0, 4.0, 1.0
    x0, chosen, c = ref.transfer(z, np.array([1, 1, 1, 0], bool), 1)
    assert x0.tolist() == [3, 1, 6, 2]
    # rows 1 and 2 tie at the largest confidence: the lower position
    assert chosen.tolist() == [False, True, False, False] and c[1] == c[2]
    # two a pass: the tie's both rows, never the unmasked row 3
    assert ref.transfer(z, np.array([1, 1, 1, 0], bool), 2)[1].tolist() \
        == [False, True, True, False]
    # fewer masked than a pass unmasks: all that are left, no other row
    assert ref.transfer(z, np.array([0, 0, 1, 0], bool), 2)[1].tolist() \
        == [False, False, True, False]
    assert [block_passes(4, 4, g) for g in range(4)] == [5, 4, 3, 2]
    assert [block_passes(4, 2, g) for g in range(4)] == [3, 3, 2, 2]
    assert block_passes(1, 1) == 2


def test_the_decode_parameters_are_the_moe_familys_with_the_norms():
    m, _, _ = seeded()
    p = _decode_params(m)
    assert p["family"] == "moe" and "rope_fn" in p
    assert {"q_norm", "k_norm"} <= set(p["layers"][0])
    assert all(st["held"] is None and st["scale"] == 1.0 and st["renorm"]
               for st in p["moe_static"])
    with pytest.raises(ValueError, match="denoising_steps"):
        SDARMoeConfig(block_length=4, denoising_steps=3)


@pytest.mark.parametrize("api", [generate, generate_cached,
                                 generate_compiled])
def test_the_batch_apis_refuse_it_by_name(api):
    m, _, _ = seeded()
    with pytest.raises(NotImplementedError, match="diffusion over blocks"):
        api(m, np.arange(6, dtype=np.int32)[None], max_new_tokens=4)
