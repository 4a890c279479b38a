"""The EvaByte configuration's own checks: the parameter count and
``lib/costs_evabyte.py`` by hand at the published sizes, the cell's
unified step AND the reference's layer compiled at their REAL sizes for
a described v5e, off the chip (what the compiler says they need fixed
``num_pages``: PERF.md, PR 35), and the ``--rehearse`` run of the cell.

Nothing runs on a device here: a compile that passes is not a chip run.
Same rules as ``test_aot_compile.py`` (topology described inside a
module-scoped fixture, compile in the test's own process, persistent
cache off, the kernels' ``_interpret`` switches steered from here).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_evabyte.py -s
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
NAME = "evabyte-6.5b-serve-pp4-d8"
CELL = "evabyte-serve-filectx-saturated"


def _config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    from benchmarks.systems.evabyte_serving import model_kwargs
    return model_kwargs(_config())


# ------------------------------------------------------------- costs
def test_parameters_by_hand(cfg):
    from benchmarks.lib import costs_evabyte as c
    # q, k, v, o 4096 x 4096; gate, up, down 4096 x 11008; two norm
    # offsets; phi and mu 32 x 128 each
    assert c.layer_params(cfg) == 4 * 16_777_216 + 3 * 45_088_768 \
        + 8_192 + 8_192 == 202_391_552
    total = 8 * 202_391_552 + 320 * 4096 + 4096 * 8 * 320 + 4096
    assert c.n_params(cfg) == total == 1_630_932_992 \
        == _config()["parameters"]                      # 3.262 GB in bf16
    assert c.row_bytes(cfg) == 2 * 32 * 128 * 2 == 16_384


def test_the_file_holds_the_published_config():
    """Every key of the catalog's entry, under its own name; only the
    depth differs, and it is the one key under ``reduced``."""
    published = {
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
        "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
        "hidden_act": "silu", "hidden_size": 4096,
        "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275,
        "intermediate_size": 11008, "lazy_init": True,
        "max_position_embeddings": 32768, "max_seq_length": 32768,
        "mixedp_attn": True, "model_type": "evabyte",
        "norm_add_unit_offset": True, "num_attention_heads": 32,
        "num_chunks": None, "num_hidden_layers": 32,
        "num_key_value_heads": 32, "num_pred_heads": 8,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320,
        "window_size": 2048}
    conf = _config()
    differ = [k for k, v in published.items() if conf[k] != v]
    assert differ == conf["reduced"] == ["num_hidden_layers"]
    assert conf["published"] == {"num_hidden_layers": 32}
    assert conf["num_hidden_layers"] == 8
    for k in ("assumed", "deployment", "reduced_notes", "engine_notes"):
        assert conf[k]


def test_attention_pool_and_step_costs_by_hand(cfg):
    from benchmarks.lib import costs_evabyte as c
    # a decode row whose new token is the 10,000th: windows 0-3 closed
    # (4 x 128 pooled rows), rows 8,192..9,999 of the fifth exact
    assert c.rows_read(cfg, 1, 10_000) == (512, 1_808)
    assert c.rows_read(cfg, 1, 2_048) == (0, 2_048)     # its own close
    assert c.rows_read(cfg, 1, 2_049) == (128, 1)
    assert c.rows_read(cfg, 0, 7) == (0, 0)
    flops, byts = c.eva_attention_cost(cfg, [(1, 10_000)])
    assert flops == 4 * 32 * 128 * 2_320 == 38_010_880
    assert byts == 2_320 * 16_384 + 2 * 32 * 128 * 2 == 38_027_264
    # a chunk of 256 ending at 20,480: 9 closed windows; each query sees
    # 1,152 pooled rows and the exact rows up to itself
    pairs = 256 * 1_152 + (256 * 2_048 - 32_640)
    flops, byts = c.eva_attention_cost(cfg, [(256, 20_480), (0, 0)])
    assert flops == 16_384 * pairs == 12_886_999_040
    assert byts == 3_200 * 16_384 + 2 * 256 * 32 * 128 * 2 == 56_623_104
    peak = types.SimpleNamespace(bf16_flops=197e12, hbm_bytes_per_s=819e9)
    assert c.roofline_seconds(flops, byts, peak)[1] == "bytes"
    # 17 chunks closed: 16 rows read and one written each, a layer
    assert c.eva_pool_cost(cfg, 17) == (6.0 * 32 * 128 * 16 * 17,
                                        17.0 * 17 * 16_384)
    assert c.serve_step_bytes(10 ** 9, cfg, [(1, 10_000), (256, 20_480),
                                             (0, 7)]) \
        == 10 ** 9 + 8 * (2_320 + 3_200) * 16_384


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
    from paddle_tpu.ops import fused, pallas_ragged
    mp = pytest.MonkeyPatch()
    for mod in (fused, pallas_ragged):
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
    """The whole configuration is built for real on the CPU (3.26 GB of
    bfloat16 weights, 9.13 GB of pools) and its jitted step is lowered
    with those shapes on one described chip, all 8 layers; then the
    reference's layer over the checked sample's 21,504 positions, which
    has to fit BESIDE the engine."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmarks.lib import reference_evabyte as ref
    from benchmarks.systems import evabyte_serving

    conf = _config()
    system = evabyte_serving.System(conf, False, seed=0)
    eng = system.engine
    one = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    B, C = eng.max_slots, eng.prefill_chunk
    P = B + C // conf["chunk_size"]

    def i32(*d):
        return jax.ShapeDtypeStruct(d, jnp.int32, sharding=one)

    compiled = eng._jit_unified.lower(
        jax.tree.map(sds, eng._w), i32(B + C), jax.tree.map(sds, eng._pools),
        i32(B + C), i32(B + 1), (i32(B + 1), i32(B + 1)),
        i32(B + 1, eng.pages_per_seq), (i32(B + C), i32(2, P)),
        (i32(B + C), i32(2, P))).compile()
    txt = compiled.as_text()
    shape = list(eng._pools[0][0].shape)
    rec = dict(_need(compiled), tpu_custom_call=txt.count(
        "custom_call_target=\"tpu_custom_call\""),
        attention_calls_named=txt.count("%eva_attention"),
        pool_calls_named=txt.count("%eva_pool"), pool_shape=shape)
    print(f"\n[aot] evabyte unified step, engine {conf['engine']}, paths "
          f"ragged={eng.ragged}: {json.dumps(rec)}")
    out = os.path.join(REPO, ".scratch")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "evabyte_step.hlo.txt"), "w") as f:
        f.write(txt)
    assert eng.ragged and shape == [32, 272, 256, 128]
    assert eng.pages_per_seq == 16
    # a layer: rope + append, the pooling, two row appends, attention
    assert rec["tpu_custom_call"] >= 5 * len(eng._pools)
    # the pools are updated in place and no pool-shaped copy is made
    pool_bytes = sum(p.size * 2 for kv in eng._pools for p in kv)
    assert pool_bytes == 8 * 2 * 272 * 2 ** 21
    assert rec["alias_GB"] * 1e9 >= pool_bytes
    assert " copy(" not in "".join(
        ln for ln in txt.splitlines() if "bf16[32,272,256,128]" in ln)
    assert rec["need_GB"] * 1e9 < HBM

    # the reference beside the resident engine (weights + pools)
    resident = system.weight_bytes + pool_bytes
    S = 21_504          # 20,470 + 24, in whole query blocks of 1,024
    cos = jax.ShapeDtypeStruct((S, 64), jnp.float32, sharding=one)
    x = jax.ShapeDtypeStruct((S, 4096), jnp.float32, sharding=one)
    w = {k: sds(v) for k, v in system._ref_weights["layers"][0].items()}
    for dtype in (jnp.float32, jnp.bfloat16):
        spec = ref.layer_spec(system.cfg, **conf["check"])
        c = ref.layer.lower(x, w, cos, cos, spec=spec, dtype=dtype).compile()
        need = _need(c)
        # the layer's weights are the engine's own arrays: resident
        extra = need["need_GB"] * 1e9 - sum(
            v.size * 2 for v in system._ref_weights["layers"][0].values())
        print(f"[aot] reference layer over {S} positions in "
              f"{jnp.dtype(dtype).name}: {json.dumps(need)}; beside the "
              f"engine {(resident + extra) / 1e9:.2f} GB")
        assert resident + extra < HBM


# ------------------------------------------------------------ rehearsal
@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearses(trace):
    from test_rehearsal import check_line, last_json, run_cell
    line = last_json(run_cell(CELL, "--rehearse", "--trace", str(trace)))
    check_line(line, CELL, bool(trace))
    if trace:
        got = line["metrics"]
        for name in ("kv_pool_used_pct", "kv_pool_used_pct.summary",
                     "kv_pool_used_pct.exact", "eva_summary_row_share",
                     "ragged_live_page_share"):
            assert got[name]["value"] is not None, name
