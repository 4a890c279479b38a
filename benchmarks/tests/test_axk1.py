"""The A.X-K1 configuration's own checks: the held parameters and
``lib/costs_axk1.py`` by hand at the published sizes, the cell's unified
step AND the reference's longest layer compiled at their REAL sizes for
a described v5e, off the chip (what the compiler says they need fixed
``num_pages``: PERF.md, PR 33), and the ``--rehearse`` run of the two
cells PR 33 added.

Nothing runs on a device here: a compile that passes is not a chip run.
Same rules as ``test_aot_compile.py`` (topology described inside a
module-scoped fixture, compile in the test's own process, persistent
cache off, the kernels' ``_interpret`` switches steered from here).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_axk1.py -s
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
NAME = "a.x-k1-serve-ep16-d6"


def _config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    from benchmarks.systems.axk1_serving import model_kwargs, reader_config
    return reader_config(model_kwargs(_config()))


# ------------------------------------------------------------- costs
def test_parameters_held_by_hand(cfg):
    from benchmarks.lib import costs_axk1 as c
    # W_qa 7168 x 1536, its norm, W_qb 1536 x 64 x 192, W_kva 7168 x 576,
    # the latent norm, W_kvb 512 x 64 x 256, W_o 8192 x 7168
    assert c.attention_params(cfg) == 11_010_048 + 1536 + 18_874_368 \
        + 4_128_768 + 512 + 8_388_608 + 58_720_256 == 101_124_096
    assert c.expert_params(cfg) == 3 * 7168 * 2048 == 44_040_192
    # layer 0: attention + dense FFN 3 x 7168 x 18432 + two norms
    assert c.layer_params(cfg, True) == 101_124_096 + 396_361_728 + 14_336 \
        == 497_500_160
    # an expert layer: router 7168 x 192, 12 experts held, the shared one
    assert c.layer_params(cfg, False) == 101_124_096 + 14_336 + 1_376_256 \
        + 13 * 44_040_192 == 675_037_184
    total = 497_500_160 + 5 * 675_037_184 + 2 * 20_480 * 7168 + 7168
    assert c.n_params(cfg) == total == 4_166_294_528      # 8.33 GB in bf16


def test_attention_and_cache_costs_by_hand(cfg):
    from benchmarks.lib import costs_axk1 as c
    assert c.latent_row_values(cfg) == 576
    # a decode row at 10,000: absorbed 2 x 64 x (576 + 512) a pair =
    # 139,264 x 10,000; unabsorbed builds 10,000 tokens' keys and values
    # (2 x 512 x 64 x 256 each) first: absorbed is the lesser
    flops, byts = c.mla_attention_cost(cfg, [(1, 10_000)])
    assert flops == 139_264 * 10_000 == 1_392_640_000
    assert byts == (10_000 * 576 + 64 * (576 + 512)) * 2 == 11_659_264
    # a chunk of 256 ending at 16,384: pairs 256 x 16,384 - 256 x 255 / 2
    pairs = 256 * 16_384 - 32_640
    absorbed = 139_264 * pairs
    unabsorbed = 2 * 64 * 320 * pairs + 2 * 16_384 * 512 * 64 * 256
    assert unabsorbed < absorbed
    flops, byts = c.mla_attention_cost(cfg, [(256, 16_384), (0, 0)])
    assert flops == unabsorbed == 445_339_664_384
    assert byts == (16_384 * 576 + 256 * 64 * 1088) * 2 == 54_525_952
    # the step: the weights once, 576 values a live token a layer
    assert c.serve_step_bytes(10 ** 9, cfg, [(1, 10_000), (256, 16_384),
                                             (0, 7)]) \
        == 10 ** 9 + 6 * 26_384 * 1152
    peak = types.SimpleNamespace(bf16_flops=197e12, hbm_bytes_per_s=819e9)
    t, which = c.roofline_seconds(flops, byts, peak)
    assert which == "flops" and abs(t - flops / 197e12) < 1e-12


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
    from paddle_tpu.ops import fused, pallas_ragged, quant
    mp = pytest.MonkeyPatch()
    for mod in (fused, pallas_ragged, quant):
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


def _need(compiled):
    ma = compiled.memory_analysis()
    return {"args_GB": ma.argument_size_in_bytes / 1e9,
            "out_GB": ma.output_size_in_bytes / 1e9,
            "temp_GB": ma.temp_size_in_bytes / 1e9,
            "alias_GB": ma.alias_size_in_bytes / 1e9,
            "need_GB": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes
                        - ma.alias_size_in_bytes) / 1e9}


def test_unified_step_and_reference_fit_one_chip(topo):
    """The whole configuration is built for real on the CPU (8.33 GB of
    bfloat16 weights, 4.03 GB of pools) and its jitted step is lowered
    with those shapes on one described chip, all 6 layers; then the
    reference's dense layer and an expert layer over the checked
    sample's 16,128 positions, which have to fit BESIDE the engine."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmarks.lib import reference_axk1 as ref
    from benchmarks.systems import axk1_serving

    conf = _config()
    system = axk1_serving.System(conf, False, seed=0)
    eng = system.engine
    one = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    B, C = eng.max_slots, eng.prefill_chunk

    def i32(*d):
        return jax.ShapeDtypeStruct(d, jnp.int32, sharding=one)

    compiled = eng._jit_unified.lower(
        jax.tree.map(sds, eng._w), i32(B + C), jax.tree.map(sds, eng._pools),
        i32(B + C), i32(B + 1), i32(B + 1), i32(B + 1, eng.pages_per_seq),
        i32(B + C), i32(B + C)).compile()
    txt = compiled.as_text()
    rec = dict(_need(compiled), tpu_custom_call=txt.count(
        "custom_call_target=\"tpu_custom_call\""),
        ragged_calls_named=txt.count("%kv_lengths"),
        pool_shape=list(eng._pools[0].shape))
    print(f"\n[aot] a.x-k1 unified step, engine {conf['engine']}, paths "
          f"ragged={eng.ragged}: {json.dumps(rec)}")
    out = os.path.join(REPO, ".scratch")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "axk1_step.hlo.txt"), "w") as f:
        f.write(txt)
    assert eng.ragged and rec["pool_shape"] == [1, 2049, 256, 640]
    # a row append and an attention call a layer
    assert rec["tpu_custom_call"] >= 2 * len(eng._pools)
    # the pools are updated in place and no pool-shaped copy is made
    pool_bytes = sum(p.size * 2 for p in eng._pools)
    assert rec["alias_GB"] * 1e9 >= pool_bytes
    assert " copy(" not in "".join(
        ln for ln in txt.splitlines() if "bf16[1,2049,256,640]" in ln)
    assert rec["need_GB"] * 1e9 < HBM

    # the reference beside the resident engine (weights + pools)
    resident = system.weight_bytes + pool_bytes
    S = 16_128
    specs = ref.layer_specs(system.cfg, **{
        k: conf["check"][k] for k in ("q_block", "head_block",
                                      "ffn_block")})
    cos = jax.ShapeDtypeStruct((S, 32), jnp.float32, sharding=one)
    for i, dtype in ((0, jnp.float32), (1, jnp.float32)):
        keys = ref.ATTN_KEYS + (ref.MOE_KEYS if specs[i].top_k
                                else ref.DENSE_KEYS)
        w = {k: sds(system._ref_weights["layers"][i][k]) for k in keys}
        x = jax.ShapeDtypeStruct((S, 7168), dtype, sharding=one)
        c = ref.layer.lower(x, w, cos, cos, spec=specs[i], dtype=dtype
                            ).compile()
        need = _need(c)
        # the layer's weights are the engine's own arrays: resident
        extra = need["need_GB"] * 1e9 - sum(
            v.size * 2 for v in system._ref_weights["layers"][i].values())
        print(f"[aot] reference layer {i} over {S} positions in "
              f"{jnp.dtype(dtype).name}: {json.dumps(need)}; beside the "
              f"engine {(resident + extra) / 1e9:.2f} GB")
        assert resident + extra < HBM


# ------------------------------------------------------------ rehearsal
@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearses(trace):
    from test_rehearsal import check_line, last_json, run_cell
    cell = "axk1-serve-longdoc-saturated"
    line = last_json(run_cell(cell, "--rehearse", "--trace", str(trace)))
    check_line(line, cell, bool(trace))
    if trace:
        got = line["metrics"]
        for name in ("engine_chunk_ctx_tokens", "moe_held_pair_share",
                     "moe_expert_rows_max_over_mean", "kv_pool_used_pct",
                     "ragged_live_page_share"):
            assert got[name]["value"] is not None, name
