"""Xing 4.0 (`paddle_tpu.models.xing`: the DeepSeek layer under a
four-stream residual mixed by hyper-connections) against the plain
reference (`benchmarks/lib/reference_xing.py`, imported, not copied), at
a toy size with every mechanism on: `hc_mult` 4 and 20 Sinkhorn
iterations kept, hidden 64, a dense and a routed layer (the tier-1 run
has seconds to spare: the planted faults, the blocked reference and the
2 + 2-layer engine run are in `benchmarks/tests/test_xing.py`), 8
sigmoid-scored experts with a correction bias, top-4, a shared expert,
routed scale 2.  Float32 under `default_matmul_precision("highest")`
(conftest).  And the configuration's arithmetic by hand at the
published sizes."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import costs_xing as costs, reference_xing as ref
from benchmarks.systems.xing_serving import (model_kwargs, model_layers,
                                              reader_config)
from paddle_tpu.generation import _ffn_apply, _mlp_params
from paddle_tpu.models.xing import XingForCausalLM, xing_tiny_config

ATOL = 3e-5
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: what `reference_xing` reads, in the published names
TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, q_lora_rank=48,
    kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=32, rms_norm_eps=1e-6, rope_theta=10000.0,
    rope_scaling={"type": "yarn", "factor": 16, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 64},
    first_k_dense_replace=1, n_routed_experts=8, num_experts_per_tok=4,
    n_group=1, topk_group=1, norm_topk_prob=True,
    routed_scaling_factor=2.0, hc_mult=4, hc_sinkhorn_iters=20,
    hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)


def seeded(**kw):
    """A seeded toy Xing whose every mechanism carries signal: a sharp
    softmax, a router and a correction bias of the scores' own spread,
    and a residual matrix far enough from the identity (``b`` 1 on its
    diagonal, not the benchmark's 4) that ONE Sinkhorn iteration is not
    twenty."""
    paddle.seed(0)
    kw = dict(dict(num_hidden_layers=2, first_k_dense_replace=1), **kw)
    m = XingForCausalLM(xing_tiny_config(**kw))
    m.eval()
    rng = np.random.default_rng(0)
    for n, p in m.named_parameters():
        if n.endswith("q_b_proj.weight"):
            p._data = p._data * 4.0
        elif n.endswith("gate_weight"):
            p._data = p._data * 20.0
        elif n.endswith("e_score_correction_bias"):
            p._data = jnp.asarray(rng.normal(0, 0.2, p._data.shape),
                                  jnp.float32)
        elif n.endswith(".b"):
            p._data = p._data / 4.0
    w = {"embed": m.model.embed_tokens.weight._data,
         "norm": m.model.norm.weight._data, "head": m.lm_head.weight._data,
         "layers": model_layers(m)}
    c = dict(TINY, **{k: v for k, v in kw.items() if k in TINY})
    if "experts_held" in kw:
        c["experts_held"] = kw["experts_held"]
    return m, w, c


@pytest.fixture(scope="module")
def tiny():
    return seeded()


IDS = np.random.default_rng(1).integers(0, 256, 24, dtype=np.int32)


def test_model_forward_matches_the_reference(tiny):
    m, w, c = tiny
    got = np.asarray(m(paddle.to_tensor(IDS[None]))._data)[0]
    want = np.asarray(ref.logits(jnp.asarray(IDS), w, c))
    assert got.shape == want.shape == (24, 256)
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_the_exit_is_the_sum_of_the_streams(tiny):
    """Exit by the mean instead would give the same logits behind the
    last RMSNorm: held here, on the stream itself."""
    m, w, c = tiny
    inner = m.model
    x = inner.enter(inner.embed_tokens(paddle.to_tensor(IDS[None])))
    assert x.shape == [1, 24, 4 * 64]
    for layer in inner.layers:
        x = layer(x, inner.rope_cos._data, inner.rope_sin._data)
    want, _ = ref.hidden_states(jnp.asarray(IDS), w["embed"], w["layers"],
                                c, stream=True)
    got = np.asarray(x._data)[0].reshape(24, 4, 64)
    np.testing.assert_allclose(got, want, atol=ATOL)
    np.testing.assert_allclose(np.asarray(inner.exit(x)._data)[0],
                               got.sum(1), atol=1e-5)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One chip's routed addend is linear in what its experts give: the
    four chips' addends, with the shared expert — like the mixing and
    the attention, data-parallel — counted ONCE, are the uncut layer,
    and that is the reference's."""
    m, w, c = seeded()
    lyr = m.model.layers[1]
    a = jnp.asarray(np.random.default_rng(2).normal(0, 1, (1, 24, 64)),
                    jnp.float32)
    tree, st = _mlp_params(lyr)
    assert "bias" in tree["moe"] and st["score"] == "sigmoid"
    whole = _ffn_apply(tree, a, st)
    sh = tree["moe"]["shared"]
    shared = (jax.nn.silu(a @ sh["sg"]) * (a @ sh["su"])) @ sh["sd"]
    total = 0
    for first in range(0, 8, 2):
        part = dict(tree["moe"], **{k: tree["moe"][k][first:first + 2]
                                    for k in ("wge", "wup", "wdn")})
        total = total + _ffn_apply(dict(moe=part), a, dict(
            st, held=(first, 2), scale=2.0)) - shared
    np.testing.assert_allclose(total + shared, whole, atol=1e-5)
    spec = ref.layer_specs(c)[1]
    want, _ = ref._experts(a[0], w["layers"][1], spec, jnp.float32)
    lw = w["layers"][1]
    want = want + ref._swiglu(a[0], lw["sg"], lw["su"], lw["sd"])
    np.testing.assert_allclose(whole[0], want, atol=1e-5)


# ------------------------------------------- the cut, at published sizes
@pytest.fixture(scope="module")
def published():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "xing4.0-29b-a4b-serve-ep4-d20.json")) as f:
        conf = json.load(f)
    return conf, reader_config(model_kwargs(conf))


def test_parameters_held_by_hand(published):
    conf, cfg = published
    # W_qa 3584 x 768, its norm, W_qb 768 x 32 x 192, W_kva 3584 x 576,
    # the latent norm, W_kvb 512 x 32 x 256, W_o 4096 x 3584
    assert costs.attention_params(cfg) == 2_752_512 + 768 + 4_718_592 \
        + 2_064_384 + 512 + 4_194_304 + 14_680_064 == 28_411_136
    assert costs.mixing_params(cfg) == 14_336 * 24 + 24 + 3 == 344_091
    assert costs.expert_params(cfg) == 3 * 3584 * 1024 == 11_010_048
    assert costs.layer_params(cfg, True) == 28_411_136 + 7_168 \
        + 99_090_432 + 688_182 == 128_196_918
    assert costs.layer_params(cfg, False) == 28_411_136 + 7_168 + 229_440 \
        + 17 * 11_010_048 + 688_182 == 216_506_742
    assert costs.n_params(cfg) == 2 * 128_196_918 + 18 * 216_506_742 \
        + 2 * 32_768 * 3_584 + 3_584 == 4_388_399_800    # 8.777 GB
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert (conf["hc_mult"], conf["hc_sinkhorn_iters"]) == (4, 20)


def test_mixing_and_step_costs_by_hand(published):
    _, cfg = published
    assert costs.stream_row_bytes(cfg) == 28_672
    flops, stream, rest = costs.mhc_sublayer_cost(cfg, 384)
    # a row: the product 2 x 14336 x 24, the squares and the input 2 x
    # 14336 each, the update 2 x 20 x 3584; the stream thrice (in for
    # the coefficients, in and out for the update), the sublayer's input
    # out and its output in, phi once
    assert flops == 384 * (688_128 + 57_344 + 143_360)
    assert stream == 3 * 384 * 28_672
    assert rest == 2 * 384 * 7_168 + 14_336 * 24 * 2
    # ... as the program's own cost model counts the two kernels: the
    # stream letter for letter, the FLOPs too; its other operands are
    # the kernels' AS STORED (phi padded to 32 rows, the coefficients a
    # [T, 128] float32 register out of one kernel and into the other)
    from paddle_tpu.observability import costmodel as cm
    pre = cm.cost("mhc_pre", T=384, n=4, C=3584)
    post = cm.cost("mhc_post", T=384, n=4, C=3584)
    assert pre.breakdown["stream"] + post.breakdown["stream"] == stream
    assert pre.flops + post.flops == flops
    assert pre.hbm_bytes + post.hbm_bytes - stream - rest \
        == 8 * 14_336 * 2 + 32 * 128 * 4 + 2 * 384 * 128 * 4
    assert costs.sublayers(cfg) == 40
    # the step: weights once but the unhit experts and the embedding's
    # unread rows, 1,152 B a live token a layer
    wb = 2 * 4_388_399_800
    got = costs.serve_step_bytes(cfg, wb, new_tokens=300, kv_tokens=90_000,
                                 experts_hit=18 * 16 - 3)
    want = wb - 2 * ((32_768 - 300) * 3_584 + 3 * 11_010_048) \
        + 20 * 90_000 * 1_152
    assert got == want
