"""The Laguna configuration's own checks: ``lib/costs_laguna.py`` by
hand at the published sizes, and the cell's unified step compiled at its
REAL size for a described v5e, off the chip (what the compiler says it
needs fixed ``num_pages``: PERF.md, PR 28).

Nothing runs on a device here: a compile that passes is not a chip run.
Same rules as ``test_aot_compile.py`` (topology described inside a
module-scoped fixture, compile in the test's own process, persistent
cache off, the kernels' ``_interpret`` switches steered from here).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_laguna.py -s
"""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
HBM = 16 * 2 ** 30
NAME = "laguna-s-2.1-serve-ep8-d8"


def _config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    from benchmarks.systems.laguna_serving import model_kwargs
    return model_kwargs(_config())


# ------------------------------------------------------------- costs
def test_parameters_held_by_hand(cfg):
    from benchmarks.lib import costs_laguna as c
    # Wq, Wo 3072 x 48 x 128 each; Wk, Wv 3072 x 8 x 128 each; gate
    assert c.attention_params(cfg, 48) == 2 * 18_874_368 + 2 * 3_145_728 \
        + 147_456 == 44_187_648
    assert c.attention_params(cfg, 72) == 2 * 28_311_552 + 6_291_456 \
        + 221_184 == 63_135_744
    assert c.expert_params(cfg) == 3 * 3072 * 1024 == 9_437_184
    # layer 0: full attention + dense FFN 3 x 3072 x 12288 + two norms
    assert c.layer_params(cfg, 48, True) == 44_187_648 + 113_246_208 + 6144
    # a sparse layer: router 3072 x 256, 32 experts held, the shared one
    sparse = 786_432 + 32 * 9_437_184 + 9_437_184 + 6144
    assert c.layer_params(cfg, 48, False) == 44_187_648 + sparse
    assert c.layer_params(cfg, 72, False) == 63_135_744 + sparse
    total = (157_440_000 + 356_407_296 + 6 * 375_355_392
             + 2 * 12544 * 3072 + 3072)
    assert c.n_params(cfg) == total == 2_843_053_056


def test_attention_and_cache_costs_by_hand(cfg):
    from benchmarks.lib import costs_laguna as c
    assert c.kv_bytes_per_token_layer(cfg) == 4096
    # a decode token at position 999 of 1000: every key, or the window's
    assert c.attended_pairs(1, 1000) == 1000
    assert c.attended_pairs(1, 1000, 512) == 512
    assert c.attended_pairs(1, 300, 512) == 300
    # a 256-token chunk ending at 1000: causal pairs, and windowed ones
    assert c.attended_pairs(256, 1000) == 256 * 1000 - 256 * 255 // 2
    assert c.attended_pairs(256, 1000, 512) == 256 * 512
    # ... starting at 400: positions 400..511 see p + 1 keys, the rest 512
    assert c.attended_pairs(256, 656, 512) == \
        sum(range(401, 513)) + (656 - 512) * 512
    assert c.live_tokens(1, 1000) == 1000
    assert c.live_tokens(1, 1000, 512) == 512
    assert c.live_tokens(256, 1000, 512) == 767     # 233 .. 999
    assert c.live_tokens(256, 300, 512) == 300
    # one full layer, one decode token at 16 k: K and V of every token
    flops, byts = c.ragged_attention_cost(cfg, [(1, 16000)], 48, None)
    assert byts == 2 * 8 * 16000 * 128 * 2 + 2 * 48 * 128 * 2
    assert flops == 4 * 48 * 128 * 16000
    # the same token in a sliding layer: 512 tokens, 72 heads
    flops, byts = c.ragged_attention_cost(cfg, [(1, 16000)], 72, 512)
    assert byts == 2 * 8 * 512 * 128 * 2 + 2 * 72 * 128 * 2
    assert flops == 4 * 72 * 128 * 512
    per_layer = c.step_attention_cost(cfg, [(1, 16000), (0, 0)])
    assert len(per_layer) == 8 and per_layer[0] == per_layer[4] \
        and per_layer[1] == per_layer[7] != per_layer[0]
    # step bytes: the weights once, 2 full layers all 16 k tokens, 6
    # sliding layers 512
    assert c.serve_step_bytes(10, cfg, [(1, 16000)]) == \
        10 + 4096 * (2 * 16000 + 6 * 512)


def test_grouped_gemm_cost_by_hand(cfg):
    from benchmarks.lib import costs_laguna as c
    # 360 held pairs over 32 experts, all hit
    flops, byts = c.moe_gmm_cost(cfg, 360, 32)
    assert flops == 6 * 3072 * 1024 * 360
    assert byts == (32 * 9_437_184 + 2 * 360 * 3072) * 2
    peak = types.SimpleNamespace(bf16_flops=197e12, hbm_bytes_per_s=819e9)
    t, which = c.roofline_seconds(flops, byts, peak)
    assert which == "bytes" and abs(t - byts / 819e9) < 1e-12


def test_check_distances_by_hand():
    """TYPICAL is a median over positions of the root mean square over
    the vocabulary, by sample, over the yardstick's; WORST the run's
    largest distance over the logits' deviation.  One wild position
    moves WORST and not TYPICAL."""
    import numpy as np
    from benchmarks.systems.laguna_serving import _distances, _over
    want = [np.zeros((5, 4)), np.zeros((3, 4))]
    got = [np.full((5, 4), 0.5), np.full((3, 4), 0.25)]
    got[0][2] = [3.0, 0.0, 0.0, 4.0]             # rms 2.5, largest 4
    d = _distances(got, want)
    assert d["typical"] == [0.5, 0.25] and d["worst"] == 4.0
    over = _over(d, {"typical": [0.25, 0.25], "sd": 8.0})
    assert over == {"typical": 2.0, "by_sample": [2.0, 1.0], "worst": 0.5}


# ------------------------------------------------------ off-chip compile
@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from paddle_tpu.ops import (fused, pallas_megadecode, pallas_megafront,
                                pallas_ragged, quant)
    mp = pytest.MonkeyPatch()
    for mod in (fused, pallas_megadecode, pallas_megafront, pallas_ragged,
                quant):
        mp.setattr(mod, "_interpret", lambda: False)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield t
    mp.undo()
    jax.config.update("jax_default_matmul_precision", precision_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()


def test_unified_step_fits_one_chip(topo):
    """The whole configuration is built for real on the CPU (5.7 GB of
    bfloat16 weights, the two pools) and its jitted step is lowered
    with those shapes on one described chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmarks.systems import laguna_serving

    conf = _config()
    eng = laguna_serving.System(conf, False, seed=0).engine
    one = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    B, C = eng.max_slots, eng.prefill_chunk

    def i32(*d):
        return jax.ShapeDtypeStruct(d, jnp.int32, sharding=one)

    table = i32(B + 1, eng.pages_per_seq)
    compiled = eng._jit_unified.lower(
        jax.tree.map(sds, eng._w), i32(B + C), jax.tree.map(sds, eng._pools),
        i32(B + C), i32(B + 1), i32(B + 1), (table, table),
        (i32(B + C), i32(B + C)), i32(B + C)).compile()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    txt = compiled.as_text()
    rec = {"args_GB": ma.argument_size_in_bytes / 1e9,
           "out_GB": ma.output_size_in_bytes / 1e9,
           "temp_GB": ma.temp_size_in_bytes / 1e9,
           "alias_GB": ma.alias_size_in_bytes / 1e9, "need_GB": need / 1e9,
           "tpu_custom_call": txt.count(
               "custom_call_target=\"tpu_custom_call\""),
           "ragged_calls_named": txt.count("%kv_lengths")}
    print(f"\n[aot] laguna unified step, engine {conf['engine']}, paths "
          f"ragged={eng.ragged} megafront={eng.megafront} "
          f"megadecode={eng.megadecode}: {json.dumps(rec)}")
    out = os.path.join(REPO, ".scratch")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "laguna_step.hlo.txt"), "w") as f:
        f.write(txt)
    assert eng.ragged and rec["tpu_custom_call"] > 0
    assert need < HBM
