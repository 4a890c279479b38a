"""The Falcon-H1 configuration's own checks: the file against the catalog
row's widths, the parameter and state counts and ``lib/costs_falcon.py``
by hand at the published sizes (the held 4,205,319,008 and the whole
model's 33.64 B), the cell's unified step at both row counts and the
reference's layer compiled at their REAL sizes for a described v5e, off
the chip (weights held once, the state pools and the pages updated in
place, 4,194,304 B of state a (slot, layer) AS STORED, no pool-sized
temporary), and the ``--rehearse`` run of the cell.

Nothing runs on a device here: a compile that passes is not a chip run.
Same rules as ``test_aot_compile.py`` (topology described inside a
module-scoped fixture, compile in the test's own process, persistent
cache off, the kernels' ``_interpret`` switches steered from here).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_falcon.py -s
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
NAME = "falcon-h1-34b-serve-pp8-d9"
CELL = "falcon-h1-serve-docchat-steady"


def _config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    from benchmarks.systems.falcon_serving import model_kwargs
    return model_kwargs({k: v for k, v in _config().items()
                         if k != "rehearsal"})


# ----------------------------------------------------------- the file
def test_the_file_keeps_every_published_width():
    """Against the catalog row beside the model-configs guide, where it
    is on this machine: every key of its ``config`` under the same name,
    nothing changed but what ``reduced`` lists."""
    conf = _config()
    assert conf["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert conf["published"] == {"num_hidden_layers": 72,
                                 "vocab_size": 261120}
    assert (conf["num_hidden_layers"], conf["vocab_size"]) == (9, 32640)
    assert conf["vocab_size"] * 8 == conf["published"]["vocab_size"]
    assert conf["pattern_as_run"] == "[M*]D" * 9
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in conf["reduced"]:
            assert conf[key] == value, key


# ------------------------------------------------------------- costs
def test_parameters_by_hand(cfg):
    from benchmarks.lib import costs_falcon as c
    assert c.conv_dim(cfg) == 4_096 + 2 * 2 * 256 == 5_120
    # W_in 5120 x (4096 + 5120 + 32); conv 5120 x 4 + bias; dt_bias,
    # A_log, D; the gated norm's gain; W_out
    assert c.mamba_params(cfg) == 47_349_760 + 25_600 + 96 + 4_096 \
        + 20_971_520 == 68_351_072
    assert c.attention_params(cfg) == 2 * 13_107_200 + 2 * 2_621_440 \
        == 31_457_280
    assert c.ffn_params(cfg) == 330_301_440
    assert c.layer_params(cfg) == 430_120_032
    assert c.n_params(cfg) == 9 * 430_120_032 + 2 * 32_640 * 5_120 + 5_120 \
        == 4_205_319_008                                # 8.411 GB in bf16
    whole = dict(cfg, num_hidden_layers=72, vocab_size=261_120)
    assert c.n_params(whole) == 33_642_516_224          # its own name: 34B
    # a sequence's memory in one layer, whatever its length
    assert c.state_only_bytes(cfg) == 32 * 128 * 256 * 4 == 4_194_304
    assert c.state_bytes(cfg) == 4_194_304 + 3 * 5_120 * 2 == 4_225_024
    assert c.kv_row_bytes(cfg) == 2_048
    eng = _config()["engine"]
    assert (eng["max_slots"] + 1) * 9 * c.state_bytes(cfg) == 2_471_639_040
    assert eng["num_pages"] * eng["page_size"] * 9 * c.kv_row_bytes(cfg) \
        == 3_024_617_472
    assert eng["max_context"] == 6_144 + 1_024


def test_costs_by_hand(cfg):
    from benchmarks.lib import costs_falcon as c
    peak = types.SimpleNamespace(bf16_flops=197e12, hbm_bytes_per_s=819e9)
    # 58 live decode slots in ONE layer: 486.5 MB of state, 5 FLOPs an
    # element: memory-bound
    row = (128 * 32 + 32) * 4 + 2 * 2 * 256 * 4 + 128 * 32 * 4
    flops, byts = c.ssm_update_cost(cfg, 58)
    assert byts == 58 * (2 * 4_194_304 + row) and row == 36_992
    assert flops == 5.0 * 58 * 32 * 128 * 256
    assert c.roofline_seconds(flops, byts, peak)[1] == "bytes"
    assert 0.596e-3 < byts / 819e9 < 0.598e-3
    # a chunk of 200 rows that starts its sequence: its state is written
    # and not read; two scan chunks of 128
    f2, b2 = c.ssm_chunk_scan_cost(cfg, 200, True)
    assert b2 == 4_194_304 + 200 * (5_120 * 2 + 128 + 4_096 * 2)
    assert f2 == 2 * (2.0 * 128 * 128 * 256 * 2 + 2.0 * 128 * 128 * 128 * 32
                      + 4.0 * 128 * 256 * 128 * 32)
    assert c.ssm_chunk_scan_cost(cfg, 0, False) == (0.0, 0.0)
    # attention, one layer: a decode row over 2,000 tokens reads 2,000 K
    # and V rows; a 256-row chunk that ends at 1,024
    flops, byts = c.attention_cost(cfg, [(1, 2_000), (0, 0)])
    assert byts == (2 * 4 * 128 * 2_000 + 2 * 20 * 128) * 2
    assert flops == 4.0 * 20 * 128 * 2_000
    assert c.roofline_seconds(flops, byts, peak)[1] == "bytes"
    flops, _ = c.attention_cost(cfg, [(256, 1_024)])
    assert flops == 4.0 * 20 * 128 * (256 * 1_024 - 256 * 255 / 2)
    # a whole step: 8.411 GB of weights less the embedding's unread
    # rows, 58 slots' state in and out of 9 layers, 116,000 cache tokens
    # in 9 layers
    wb = 2 * 4_205_319_008
    got = c.serve_step_bytes(cfg, wb, 58, 58, 0, 116_000)
    assert got == wb - 2 * (32_640 - 58) * 5_120 \
        + 9 * 4_225_024 * 116 + 9 * 2_048 * 116_000
    assert 17.8e-3 < got / 819e9 < 17.9e-3      # ~17.9 ms a step


# ------------------------------------------------------ off-chip compile
# the described topology and the compiled step's needs: Nemotron's, whose
# fixture steers the same three kernel modules
from test_nemotron import _need, topo  # noqa: E402,F401


@pytest.fixture(scope="module")
def system():
    """The whole configuration built for real on the CPU (8.41 GB of
    weights, 2.47 GB of state, 3.02 GB of pages) — once for the module."""
    from benchmarks.systems import falcon_serving
    return falcon_serving.System(_config(), False, seed=0)


@pytest.mark.parametrize("program", ["unified", "unified_nochunk"])
def test_unified_step_fits_one_chip(topo, system, program):
    """The engine's own jitted step lowered with the real shapes on one
    described chip, all 9 layers, at each of its two row counts: weights
    held once, the state pools and the pages updated in place, the state
    stored as 4,194,304 B a (slot, layer) — no lane padded, no
    pool-sized copy or temporary — under 16 GiB."""
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

    B = eng.max_slots
    T = B + (eng.prefill_chunk if program == "unified" else 0)
    t0 = time.perf_counter()
    lowered = eng._programs[program].lower(
        jax.tree.map(sds, eng._w), i32(T), jax.tree.map(sds, eng._pools),
        i32(T), i32(B + 1), (i32(B + 1), i32(B + 3)),
        i32(B + 1, eng.pages_per_seq), i32(T), i32(T))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    txt = compiled.as_text()
    rec = dict(_need(compiled), lower_s=round(t1 - t0, 1),
               compile_s=round(t2 - t1, 1), text_MB=round(len(txt) / 1e6, 2),
               tpu_custom_call=txt.count(
                   "custom_call_target=\"tpu_custom_call\""),
               conditionals=txt.count(" conditional("))
    print(f"\n[aot] falcon {program}, engine {conf['engine']}, paths "
          f"ragged={eng.ragged}: {json.dumps(rec)}")
    out = os.path.join(REPO, ".scratch")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"falcon_{program}.hlo.txt"), "w") as f:
        f.write(txt)
    assert eng.ragged and eng._family == "hybrid"
    state_shape = [B + 1, 32, 128, 256]                 # state-minor
    assert [list(s.shape) for s, _ in eng._pools["ssm"]] == [state_shape] * 9
    assert len(eng._pools["kv"]) == 9
    acct = eng.hbm_accounting()
    # 8.411 GB of parameters and the rope table to max_context (cos and
    # sin, [7168, 64] float32)
    assert acct["weights_bytes"] == 2 * 4_205_319_008 + 2 * 7_168 * 64 * 4
    assert acct["state_pool_bytes"] == (B + 1) * 9 * 4_225_024
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(eng._pools))
    assert acct["page_pool_bytes"] == pool_bytes
    # the arguments are the logical bytes: nothing is stored padded
    assert rec["args_GB"] * 1e9 < acct["weights_bytes"] + pool_bytes + 5e7
    # every pool is updated in place and no state-pool-shaped copy or
    # temporary is made (one layer's pool is 0.27 GB)
    assert rec["alias_GB"] * 1e9 >= pool_bytes - 1e3
    shape = f"f32[{B + 1},32,128,256]"
    assert " copy(" not in "".join(
        ln for ln in txt.splitlines() if shape in ln.split(" = ")[-1][:40])
    one_pool = (B + 1) * 4_194_304
    assert rec["temp_GB"] * 1e9 < 2.5 * one_pool
    assert rec["need_GB"] * 1e9 < HBM
    # per layer: rope + append, ragged attention, the state update (and,
    # with a chunk, the state put)
    assert rec["tpu_custom_call"] >= 9 * (4 if program == "unified" else 3)


def test_reference_layer_fits_beside_the_engine(topo, system):
    """The reference's layer over the checked sample's 6,153 positions,
    which has to fit BESIDE the resident engine."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmarks.lib import reference_falcon as ref

    conf = _config()
    one = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    resident = system.weight_bytes + sum(
        a.size * a.dtype.itemsize
        for a in jax.tree.leaves(system.engine._pools))
    S = 6_153
    blocks = {k: v for k, v in conf["check"].items() if k.endswith("block")}
    spec = ref.spec(system.cfg, **blocks)
    w0 = system._ref_weights["layers"][0]
    held = sum(v.size * v.dtype.itemsize for v in w0.values())
    for dtype in (jnp.float32, jnp.bfloat16):
        w = {k: sds(v) for k, v in w0.items()}
        x = jax.ShapeDtypeStruct((S, 5120), dtype, sharding=one)
        c = ref.layer.lower(x, w, spec=spec, dtype=dtype,
                            keep_state=True).compile()
        need = _need(c)
        extra = need["need_GB"] * 1e9 - held
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
        for name in ("kv_pool_used_pct", "state_pool_used_pct",
                     "ragged_live_page_share",
                     "engine_rows_per_step.decode"):
            assert got[name]["value"] is not None, name
