"""Checks of the Ling 3.0 configuration that need no chip: the
configuration file against the catalog's published keys, the held
parameters, the state's bytes and the cost functions by hand (the byte
count of the file: held 2,904,442,816 and the whole model's 124.05 B /
5.14 B active), the readers of the new per-layer metrics on made-up
records, the cell's unified step and the reference's blocks compiled at
their REAL sizes for a described v5e, off the chip (weights held once,
the state pools updated in place, no pool-sized temporary), and the
``--rehearse`` run of the cell.

Nothing runs on a device here: a compile that passes is not a chip run.
Same rules as ``test_aot_compile.py`` (topology described inside a
module-scoped fixture, compile in the test's own process, persistent
cache off, the kernels' ``_interpret`` switches steered from here).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_ling.py -s
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
NAME = "ling-3.0-flash-serve-ep8-d7"
CELL = "ling3-flash-serve-reason-steady"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    from benchmarks.systems.ling_serving import model_kwargs
    return model_kwargs(
        {k: v for k, v in _config().items() if k != "rehearsal"})


# ------------------------------------------------------------ the file
def test_the_file_keeps_every_published_number():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash")
    conf = _config()
    assert conf["source"] == row["source_url"]
    reduced = set(conf["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size"}
    for k, v in row["config"].items():
        if k in reduced:
            assert conf[k] != v and conf["published"][k] == v, k
        else:
            assert conf[k] == v, k
    assert conf["layers_held"] == [0, 2, 3, 4, 5, 6, 7]
    assert all(conf["expert_swiglu_limit_list"][i] == 0
               and conf["share_expert_swiglu_limit_list"][i] == 0
               for i in conf["layers_held"])
    assert "8 chips" in conf["deployment"] and "48 chips" in \
        conf["deployment"]


# ------------------------------------------------------------- costs
def test_parameters_by_hand(cfg):
    from benchmarks.lib import costs_ling as c
    assert c.kinds(cfg) == {"K": 6, "L": 1, "D": 1, "E": 6}
    # W_q, W_k, W_v 3 x 2560 x 4096; three convolutions 4096 x 4; W_f and
    # dt_bias; A_log; W_beta and W_g 2560 x 32; the heads' gain; W_o
    assert c.kda_params(cfg) == 31_457_280 + 49_152 + 10_485_760 + 4_096 \
        + 32 + 163_840 + 128 + 10_485_760 == 52_646_048
    # W_q 2560 x 32 x 192; W_kva 2560 x 576 and its gain; W_kvb 512 x 32
    # x 256; W_g; W_o
    assert c.latent_params(cfg) == 15_728_640 + 1_474_560 + 512 \
        + 4_194_304 + 81_920 + 10_485_760 == 31_965_696
    assert c.dense_params(cfg) == 3 * 2_560 * 6_144 == 47_185_920
    assert c.expert_params(cfg) == 3 * 2_560 * 768 == 5_898_240
    assert c.moe_params(cfg, 0) == 1_310_720 + 512 + 5_898_240
    assert c.n_params(cfg) == 6 * 52_646_048 + 31_965_696 + 47_185_920 \
        + 6 * (64 * 5_898_240 + 5_898_240 + 1_310_720 + 512) + 7 * 5_120 \
        + 2 * 39_296 * 2_560 + 2_560 == 2_904_442_816   # 5.809 GB in bf16
    # the whole model by the same equations: its own name, ~125B-A5.5B
    whole = dict(cfg, vocab_size=157_184, experts_held=None)
    whole.pop("layers_held")
    assert c.kinds(whole) == {"K": 35, "L": 7, "D": 2, "E": 40}
    assert round(c.n_params(whole) / 1e9, 2) == 124.05
    assert round(c.n_params_active(whole) / 1e9, 2) == 5.14
    # a sequence's memory in one KDA block, whatever its length
    assert c.state_only_bytes(cfg) == 32 * 128 * 128 * 4 == 2_097_152
    assert c.state_bytes(cfg) == 2_097_152 + 3 * 3 * 4_096 * 2 == 2_170_880
    assert c.kv_row_values(cfg) == 576 and c.kv_row_bytes(cfg) == 1_280
    eng = _config()["engine"]
    assert (eng["max_slots"] + 1) * 6 * c.state_bytes(cfg) == 5_014_732_800
    assert eng["num_pages"] * eng["page_size"] * 1_280 == 1_006_960_640


def test_costs_by_hand(cfg):
    from benchmarks.lib import costs_ling as c
    peak = types.SimpleNamespace(bf16_flops=197e12, hbm_bytes_per_s=819e9)
    # 384 live decode slots: 1.61 GB of state a block, 7 FLOPs an
    # element: memory-bound 40 to 1
    row = 32 * (4 * 128 + 1) * 4 + 32 * 128 * 4
    flops, byts = c.kda_update_cost(cfg, 384)
    assert byts == 384 * (2 * 2_097_152 + row) and row == 82_048
    assert flops == 7.0 * 384 * 32 * 128 * 128
    assert c.roofline_seconds(flops, byts, peak)[1] == "bytes"
    assert 2.00e-3 < byts / 819e9 < 2.01e-3     # 2 ms a block, 12 a step
    # a chunk of 200 rows that starts its sequence: its state is written
    # and not read; four sub-chunks of 64
    f2, b2 = c.kda_chunk_cost(cfg, 200, True)
    assert b2 == 2_097_152 + 256 * row
    assert f2 == 4 * 32 * (6.0 * 64 * 64 * 128 + 2.0 * 64 ** 3 / 3
                           + 6.0 * 64 * 128 * 128 + 4.0 * 64 * 64 * 128)
    assert c.roofline_seconds(f2, b2, peak)[1] == "bytes"
    assert c.kda_chunk_cost(cfg, 0, False) == (0.0, 0.0)
    _, b3 = c.kda_chunk_cost(cfg, 64, False)
    assert b3 == 2 * 2_097_152 + 64 * row
    # a whole step: 5.809 GB of weights less the embedding's unread rows
    # and two unhit experts, 384 slots' state in and out of 6 blocks, the
    # latent block's 300,000 cache tokens at 576 values
    wb = 2 * 2_904_442_816
    got = c.serve_step_bytes(cfg, wb, 384, 384, 0, 300_000, 6 * 64 - 2)
    assert got == wb - 2 * (39_296 - 384) * 2_560 - 2 * 2 * 5_898_240 \
        + 6 * 2_170_880 * 768 + 1_152 * 300_000
    assert 19.4e-3 < got / 819e9 < 19.6e-3      # ~19.5 ms a step


# ------------------------------------------- the new metrics' readers
def test_the_counter_reader_on_recorded_steps(cfg, monkeypatch):
    """`kda_state_moved_share` over step records as the engine writes
    them; the traced readers give nothing without a trace."""
    from benchmarks.run import load_reader
    from benchmarks.lib import ling_spans
    one = 6 * 2_097_152
    rows = [(384, 0, one * 768), (384, 1, one * 767), (200, 1, one * 399)]
    reader = load_reader("kda_state_moved_share")
    monkeypatch.setattr(reader, "counts", lambda h, *k: rows)
    h = types.SimpleNamespace(counters={"cfg": cfg}, reduced=None)
    assert reader.read(h) == 100.0
    rows[0] = (384, 0, one * 770)               # an idle slot was moved
    assert reader.read(h) < 100.0
    # another family's cfg: nothing to read
    other = types.SimpleNamespace(counters={"cfg": {"hidden_size": 4096}},
                                  reduced=None)
    assert reader.read(other) is None
    assert not ling_spans.kda(other) and ling_spans.kda(h)
    for name in ("kda_mixer_device_ms", "kda_update_device_ms",
                 "kda_update_roofline", "kda_chunk_roofline",
                 "serve_step_hbm_roofline.kda"):
        assert load_reader(name).read(h) is None, name
        assert load_reader(name).read(other) is None, name


def test_the_benchmark_lists_the_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == NAME
    new = ["kda_mixer_device_ms", "kda_update_device_ms",
           "kda_update_roofline", "kda_chunk_roofline",
           "kda_state_moved_share", "serve_step_hbm_roofline.kda"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for n in new:
        assert by_name[n]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           n + ".py"))
    assert [m["name"] for m in bench["per_layer"][-6:]] == new
    for m in bench["end_to_end"]:
        if m["name"] in ("tpot_p95_ms", "serve_tok_s"):
            assert m["workloads"][-1] == CELL


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
    from paddle_tpu.ops import fused, pallas_kda, pallas_ragged, pallas_ssm
    mp = pytest.MonkeyPatch()
    for mod in (fused, pallas_ragged, pallas_ssm, pallas_kda):
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
    """The whole configuration built for real on the CPU (5.81 GB of
    weights, 5.01 GB of state, 1.01 GB of pages) — once for the module."""
    from benchmarks.systems import ling_serving
    return ling_serving.System(_config(), False, seed=0)


def test_unified_step_fits_one_chip(topo, system):
    """The engine's own jitted step lowered with the real shapes on one
    described chip, all 14 blocks: weights held once, the state pools and
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
    print(f"\n[aot] ling unified step, engine {conf['engine']}, "
          f"paths ragged={eng.ragged}: {json.dumps(rec)}")
    out = os.path.join(REPO, ".scratch")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "ling_step.hlo.txt"), "w") as f:
        f.write(txt)
    assert eng.ragged and eng._family == "hybrid" and eng._latent
    state_shape = [B + 1, 32, 128, 128]
    assert [list(s.shape) for s, _ in eng._pools["ssm"]] == [state_shape] * 6
    assert [list(t.shape) for _, t in eng._pools["ssm"]] \
        == [[B + 1, 3, 12_288]] * 6
    assert [list(p.shape) for p in eng._pools["kv"]] \
        == [[1, conf["engine"]["num_pages"], 256, 640]]
    acct = eng.hbm_accounting()
    # the engine's tree: the model's arrays (the latent block's W_q and
    # W_kva in the kernel's rope order, the three convolutions side by
    # side: same sizes) and the rope table to max_context, float32; the
    # model keeps its own W_q / W_kva / convolutions beside it (35 MB)
    again = 2 * 2_048 * 32 * 4
    assert acct["weights_bytes"] == 2 * 2_904_442_816 + again  # 5.809 GB
    assert acct["state_pool_bytes"] == (B + 1) * 6 * 2_170_880
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(eng._pools))
    assert acct["page_pool_bytes"] == pool_bytes
    assert rec["args_GB"] * 1e9 < acct["weights_bytes"] + pool_bytes + 5e7
    # every pool is updated in place and no state-pool-shaped copy or
    # temporary is made (one block's pool is 0.81 GB)
    assert rec["alias_GB"] * 1e9 >= pool_bytes - 1e3
    shape = f"f32[{B + 1},32,128,128]"
    assert " copy(" not in "".join(
        ln for ln in txt.splitlines() if shape in ln.split(" = ")[-1][:40])
    one_pool = (B + 1) * 2_097_152
    assert rec["temp_GB"] * 1e9 < 2.5 * one_pool
    assert rec["need_GB"] * 1e9 < HBM


def test_reference_blocks_fit_beside_the_engine(topo, system):
    """The reference's blocks over the checked sample's 1,523 positions,
    which have to fit BESIDE the resident engine."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmarks.lib import reference_ling as ref

    conf = _config()
    one = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    resident = system.engine.hbm_accounting()["weights_bytes"] + sum(
        a.size * a.dtype.itemsize
        for a in jax.tree.leaves(system.engine._pools))
    S = 1_523
    specs = ref.specs(system.cfg, **conf["check"])
    for dtype in (jnp.float32, jnp.bfloat16):
        for blk in (0, 1, 3, 8):                # K, D, E, L
            w = {k: sds(v) for k, v in
                 system._ref_weights["layers"][blk].items()}
            x = jax.ShapeDtypeStruct((S, 2560), dtype, sharding=one)
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
    """(At toy widths one flipped expert is a logit's whole spread, so
    the rehearsal's check holds on this seed, not on every one.)"""
    from test_rehearsal import check_line, last_json, run_cell
    line = last_json(run_cell(CELL, "--rehearse", "--trace", str(trace)))
    check_line(line, CELL, bool(trace))
    if trace:
        got = line["metrics"]
        for name in ("kv_pool_used_pct", "state_pool_used_pct",
                     "kda_state_moved_share", "moe_held_pair_share",
                     "engine_rows_per_step.decode"):
            assert got[name]["value"] is not None, name
        assert got["kda_state_moved_share"]["value"] == 100.0
