"""The Nemotron configuration's own checks: the parameter and state
counts and ``lib/costs_nemotron.py`` by hand at the published sizes (the
held 4,648,163,712 and the whole model's 120.67 B / 12.77 B active), the
cell's unified step and the reference's blocks compiled at their REAL
sizes for a described v5e, off the chip (weights held once, the state
pool updated in place, no pool-sized temporary), and the ``--rehearse``
run of the cell.

Nothing runs on a device here: a compile that passes is not a chip run.
Same rules as ``test_aot_compile.py`` (topology described inside a
module-scoped fixture, compile in the test's own process, persistent
cache off, the kernels' ``_interpret`` switches steered from here).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_nemotron.py -s
"""

import json
import os
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
HBM = 16 * 2 ** 30
NAME = "nemotron-3-super-serve-ep4-d11"
CELL = "nemotron3-super-serve-shortchat-steady"


def _config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    from benchmarks.systems.nemotron_serving import (model_kwargs,
                                                     reader_config)
    return reader_config(model_kwargs(
        {k: v for k, v in _config().items() if k != "rehearsal"}))


# ------------------------------------------------------------- costs
def test_parameters_by_hand(cfg):
    from benchmarks.lib import costs_nemotron as c
    assert c.kinds(cfg) == {"M": 5, "*": 1, "E": 5}
    # W_in 4096 x (8192 + 10240 + 128); conv 10240 x 4 + bias; dt_bias,
    # A_log, D; the gated norm's gain; W_out; the block's gain
    assert c.mamba_params(cfg) == 76_021_760 + 51_200 + 384 + 8_192 \
        + 33_554_432 + 4_096 == 109_640_064
    assert c.attention_params(cfg) == 2 * 16_777_216 + 2 * 1_048_576 \
        + 4_096 == 35_655_680
    assert c.expert_params(cfg) == 2 * 1_024 * 2_688 == 5_505_024
    assert c.moe_params(cfg, 0) == 2_097_152 + 512 + 8_388_608 \
        + 44_040_192 + 4_096 == 54_530_560
    assert c.n_params(cfg) == 5 * 109_640_064 + 35_655_680 \
        + 5 * (128 * 5_505_024 + 54_530_560) + 2 * 32_768 * 4_096 + 4_096 \
        == 4_648_163_712                                # 9.296 GB in bf16
    # the whole model by the same equations: its own name, 120B-A12B
    whole = dict(cfg, num_hidden_layers=88, vocab_size=131_072,
                 experts_held=None,
                 hybrid_override_pattern=_config()["hybrid_override_pattern"])
    assert c.kinds(whole) == {"M": 40, "*": 8, "E": 40}
    assert round(c.n_params(whole) / 1e9, 2) == 120.67
    assert round(c.n_params_active(whole) / 1e9, 2) == 12.77
    # a sequence's memory in one state-space block, whatever its length
    assert c.state_only_bytes(cfg) == 128 * 64 * 128 * 4 == 4_194_304
    assert c.state_bytes(cfg) == 4_194_304 + 3 * 10_240 * 2 == 4_255_744
    assert c.kv_row_bytes(cfg) == 1_024
    eng = _config()["engine"]
    assert (eng["max_slots"] + 1) * 5 * c.state_bytes(cfg) == 2_744_954_880


def test_costs_by_hand(cfg):
    from benchmarks.lib import costs_nemotron as c
    peak = types.SimpleNamespace(bf16_flops=197e12, hbm_bytes_per_s=819e9)
    # 128 live decode slots, no chunk: 1.07 GB of state, 5 FLOPs an
    # element: memory-bound 80 to 1
    row = (8_192 + 2_048) * 2 + 512 + 8_192 * 2
    flops, byts = c.ssm_scan_cost(cfg, 128, 0, False)
    assert byts == 128 * (2 * 4_194_304 + row) and row == 37_376
    assert flops == 5.0 * 128 * 64 * 128 * 128
    assert c.roofline_seconds(flops, byts, peak)[1] == "bytes"
    assert 1.31e-3 < byts / 819e9 < 1.32e-3
    # ... with a chunk of 200 rows that starts its sequence: its state
    # is written and not read; two scan chunks of 128
    f2, b2 = c.ssm_scan_cost(cfg, 127, 200, True)
    assert b2 == 127 * 2 * 4_194_304 + 4_194_304 + 327 * row
    assert f2 == 5.0 * 127 * 1_048_576 + 2 * (
        2.0 * 128 * 128 * 128 * 8 + 2.0 * 128 * 128 * 64 * 128
        + 4.0 * 128 * 128 * 64 * 128)
    # the grouped GEMMs in the latent: 704 held pairs over 128 experts
    flops, byts = c.moe_gmm_cost(cfg, 704, 127)
    assert flops == 4.0 * 1_024 * 2_688 * 704
    assert byts == (127 * 5_505_024 + 2 * 704 * 1_024) * 2
    assert c.roofline_seconds(flops, byts, peak)[1] == "bytes"
    # a whole step: 9.296 GB of weights less the embedding's unread rows
    # and one unhit expert, 128 slots' state in and out of 5 blocks, the
    # attention block's 40,000 cache tokens
    wb = 2 * 4_648_163_712
    got = c.serve_step_bytes(cfg, wb, 128, 128, 0, 40_000, 5 * 128 - 1)
    assert got == wb - 2 * (32_768 - 128) * 4_096 - 2 * 5_505_024 \
        + 5 * 4_255_744 * 256 + 1_024 * 40_000
    assert 17.6e-3 < got / 819e9 < 17.8e-3      # ~17.7 ms a step


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
    from paddle_tpu.ops import fused, pallas_ragged, pallas_ssm
    mp = pytest.MonkeyPatch()
    for mod in (fused, pallas_ragged, pallas_ssm):
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


@pytest.fixture(scope="module")
def system():
    """The whole configuration built for real on the CPU (9.30 GB of
    weights, 2.74 GB of state, 0.54 GB of pages) — once for the module."""
    from benchmarks.systems import nemotron_serving
    return nemotron_serving.System(_config(), False, seed=0)


def test_unified_step_fits_one_chip(topo, system):
    """The engine's own jitted step lowered with the real shapes on one
    described chip, all 11 blocks: weights held once, the state pools and
    the pages updated in place, no pool-sized temporary, under 15.75
    GB."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    conf = _config()
    eng = system.engine
    one = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    def i32(*d):
        return jax.ShapeDtypeStruct(d, jnp.int32, sharding=one)

    B, C = eng.max_slots, eng.prefill_chunk
    t0 = time.perf_counter()
    lowered = eng._jit_unified.lower(
        jax.tree.map(sds, eng._w), i32(B + C), jax.tree.map(sds, eng._pools),
        i32(B + C), i32(B + 1), (i32(B + 1), i32(B + 3)),
        i32(B + 1, eng.pages_per_seq), i32(B + C), i32(B + C))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    txt = compiled.as_text()
    rec = dict(_need(compiled), lower_s=round(t1 - t0, 1),
               compile_s=round(t2 - t1, 1), text_MB=round(len(txt) / 1e6, 2),
               tpu_custom_call=txt.count(
                   "custom_call_target=\"tpu_custom_call\""),
               conditionals=txt.count(" conditional("))
    print(f"\n[aot] nemotron unified step, engine {conf['engine']}, "
          f"paths ragged={eng.ragged}: {json.dumps(rec)}")
    out = os.path.join(REPO, ".scratch")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "nemotron_step.hlo.txt"), "w") as f:
        f.write(txt)
    assert eng.ragged and eng._family == "hybrid"
    state_shape = [B + 1, 64, 128, 128]
    assert [list(s.shape) for s, _ in eng._pools["ssm"]] == [state_shape] * 5
    assert len(eng._pools["kv"]) == 1
    acct = eng.hbm_accounting()
    assert acct["weights_bytes"] == 2 * 4_648_163_712       # 9.296 GB
    assert acct["state_pool_bytes"] == (B + 1) * 5 * 4_255_744
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(eng._pools))
    assert acct["page_pool_bytes"] == pool_bytes
    assert rec["args_GB"] * 1e9 < acct["weights_bytes"] + pool_bytes + 5e7
    # every pool is updated in place and no state-pool-shaped copy or
    # temporary is made (one block's pool is 0.54 GB)
    assert rec["alias_GB"] * 1e9 >= pool_bytes - 1e3
    shape = f"f32[{B + 1},64,128,128]"
    assert " copy(" not in "".join(
        ln for ln in txt.splitlines() if shape in ln.split(" = ")[-1][:40])
    one_pool = (B + 1) * 4_194_304
    assert rec["temp_GB"] * 1e9 < 2.5 * one_pool
    assert rec["need_GB"] * 1e9 < HBM


def test_reference_blocks_fit_beside_the_engine(topo, system):
    """The reference's blocks over the checked sample's 1,523 positions,
    which have to fit BESIDE the resident engine."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmarks.lib import reference_nemotron as ref

    conf = _config()
    one = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    resident = system.weight_bytes + sum(
        a.size * a.dtype.itemsize
        for a in jax.tree.leaves(system.engine._pools))
    S = 1_523
    specs = ref.specs(system.cfg, **conf["check"])
    for dtype in (jnp.float32, jnp.bfloat16):
        for blk in (0, 1, 7):                   # M, E, *
            w = {k: sds(v) for k, v in
                 system._ref_weights["layers"][blk].items()}
            x = jax.ShapeDtypeStruct((S, 4096), dtype, sharding=one)
            c = ref.block.lower(x, w, spec=specs[blk], dtype=dtype).compile()
            need = _need(c)
            extra = need["need_GB"] * 1e9 - sum(
                v.size * v.dtype.itemsize for v in
                system._ref_weights["layers"][blk].values())
            print(f"[aot] reference block {specs[blk].kind} over {S} "
                  f"positions in {jnp.dtype(dtype).name}: "
                  f"{json.dumps(need)}; beside the engine "
                  f"{(resident + extra) / 1e9:.2f} GB")
            assert resident + extra < HBM


# ------------------------------------------------------------ rehearsal
@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearses(trace):
    from test_rehearsal import check_line, last_json, run_cell
    line = last_json(run_cell(CELL, "--rehearse", "--trace", str(trace)))
    check_line(line, CELL, bool(trace))
    if trace:
        got = line["metrics"]
        for name in ("kv_pool_used_pct", "state_pool_used_pct",
                     "ssm_state_moved_share", "moe_held_pair_share",
                     "engine_rows_per_step.decode"):
            assert got[name]["value"] is not None, name
        assert got["ssm_state_moved_share"]["value"] == 100.0
