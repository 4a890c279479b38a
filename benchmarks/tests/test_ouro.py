"""The Ouro configuration's own checks: the parameter and cache-row
counts and ``lib/costs_ouro.py`` by hand at the published sizes, the
cell's unified step and the reference's layer compiled at their REAL sizes for a described v5e,
off the chip (what the compiler says they need fixed ``num_pages``: PERF.md, PR 39), and the ``--rehearse`` run of
the cell.

Nothing runs on a device here: a compile that passes is not a chip run.
Same rules as ``test_aot_compile.py`` (topology described inside a
module-scoped fixture, compile in the test's own process, persistent
cache off, the kernels' ``_interpret`` switches steered from here).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_ouro.py -s
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
NAME = "ouro-2.6b-serve-whole"
CELL = "ouro-serve-reason-saturated"


def _config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    from benchmarks.systems.ouro_serving import model_kwargs
    return model_kwargs(_config())


# ------------------------------------------------------------- costs
def test_parameters_and_cache_rows_by_hand(cfg):
    from benchmarks.lib import costs_ouro as c
    # q, k, v, o 2048 x 2048; gate, up, down 2048 x 5632; four gains
    assert c.layer_params(cfg) == 4 * 4_194_304 + 3 * 11_534_336 + 8_192 \
        == 51_388_416
    total = 48 * 51_388_416 + 2 * 49_152 * 2_048 + 2_048 + 2_049
    assert c.n_params(cfg) == total == 2_667_974_657 \
        == _config()["parameters"]                      # 5.336 GB in bf16
    assert c.slots(cfg) == 4 * 48 == 192
    assert c.row_bytes(cfg) == 2 * 16 * 128 * 2 == 8_192
    assert c.token_bytes(cfg) == 192 * 8_192 == 1_572_864
    eng = _config()["engine"]
    # a page id is 64 rows in every slot: 96 MiB; the pool 7.5 GiB
    assert eng["page_size"] * c.token_bytes(cfg) == 96 * 2 ** 20
    assert eng["num_pages"] * eng["page_size"] * c.token_bytes(cfg) \
        == 7.5 * 2 ** 30


def test_the_file_holds_the_published_config():
    """Every key of the catalog's entry, under its own name, and nothing
    cut: ``reduced`` is empty."""
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    conf = _config()
    assert [k for k, v in published.items() if conf[k] != v] == []
    assert conf["reduced"] == [] and conf["published"] == {}
    for k in ("source", "assumed", "deployment", "reduced_notes",
              "engine_notes", "check", "rehearsal"):
        assert conf[k]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == NAME)
    assert entry["reduced"] == [] and entry["source"] == conf["source"] \
        and entry["source"].endswith("Ouro-2.6B/blob/main/config.json")


def test_step_and_attention_costs_by_hand(cfg):
    from benchmarks.lib import costs_ouro as c
    # seven decoders at 400 tokens and an idle slot: the layers four
    # times, the last norm and the gate with them, the head once, seven
    # embedding rows, 2,800 tokens' rows in all 192 slots
    seqs = [(1, 400)] * 7 + [(0, 0)]
    loop = 48 * 51_388_416 + 2 * 2_048 + 1
    assert c.serve_step_bytes(cfg, seqs) == 2 * (
        4 * loop + 2_048 * 49_152 + 7 * 2_048) + 2_800 * 1_572_864 \
        == 24_338_558_984
    # one application: 7 pages of 64 a sequence, K and V, q in, o out
    flops, byts = c.ragged_attention_cost(cfg, seqs, 64)
    assert byts == 7 * (2 * 16 * 7 * 64 * 128 * 2 + 2 * 16 * 128 * 2) \
        == 25_747_456
    assert flops == 7 * 4 * 16 * 128 * 400
    peak = types.SimpleNamespace(bf16_flops=197e12, hbm_bytes_per_s=819e9)
    assert c.roofline_seconds(flops, byts, peak)[1] == "bytes"
    # the 272 rows a step computes: 2 FLOPs a matmul parameter a row a
    # pass, the gate, the head over 17 rows, attention in 192 slots
    matmul = 51_388_416 - 8_192
    assert c.serve_step_flops(cfg, 272, 17, seqs, 64) == \
        2.0 * 272 * 4 * (48 * matmul + 2_048) \
        + 2.0 * 17 * 2_048 * 49_152 + 192 * flops
    # 27.3 ms of FLOPs at 197 TF/s against 29.7 ms of bytes at 819 GB/s
    assert 5.3e12 < c.serve_step_flops(cfg, 272, 17, seqs, 64) < 5.5e12


# ------------------------------------------------------ off-chip compile
# the described v5e and what a compiled program needs: test_evabyte's
from test_evabyte import _need, topo  # noqa: E402,F401


@pytest.fixture(scope="module")
def system():
    """The whole configuration built for real on the CPU (5.34 GB of
    weights; the pools 8.05 GB) — once for the module."""
    from benchmarks.systems import ouro_serving
    return ouro_serving.System(_config(), False, seed=0)


def test_unified_step_fits_one_chip(topo, system):
    """The engine's own jitted step lowered with the real shapes on one
    described chip, all 48 layers x 4 passes (a compiled loop around one
    jitted layer): weights held once, the pools updated in place, no
    pool-sized temporary, under 15.75 GB."""
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
        i32(B + C), i32(B + 1), i32(B + 1), i32(B + 1, eng.pages_per_seq),
        i32(B + C), i32(B + C))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    txt = compiled.as_text()
    shape = list(eng._pools[0][0].shape)
    rec = dict(_need(compiled), lower_s=round(t1 - t0, 1),
               compile_s=round(t2 - t1, 1), text_MB=round(len(txt) / 1e6, 2),
               tpu_custom_call=txt.count(
                   "custom_call_target=\"tpu_custom_call\""),
               whiles=txt.count(" while("), pool_shape=shape)
    print(f"\n[aot] ouro unified step, engine {conf['engine']}, "
          f"paths ragged={eng.ragged}: {json.dumps(rec)}")
    out = os.path.join(REPO, ".scratch")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "ouro_step.hlo.txt"), "w") as f:
        f.write(txt)
    N, ps = conf["engine"]["num_pages"], conf["engine"]["page_size"]
    assert eng.ragged and shape == [16, 4 * N, ps, 128]
    # a layer application: rope + append, attention, four norms; ONE
    # pass in the text
    assert rec["whiles"] >= 1
    assert 6 <= rec["tpu_custom_call"] // len(eng._pools) <= 10
    # weights once: the arguments are the weights, the pools and tables
    pool_bytes = sum(p.size * 2 for kv in eng._pools for p in kv)
    assert pool_bytes == N * ps * 1_572_864
    acct = eng.hbm_accounting()
    assert acct["weights_bytes"] == pytest.approx(2 * 2_667_974_657, rel=1e-3)
    assert acct["page_pool_bytes"] == pool_bytes
    assert rec["args_GB"] * 1e9 < acct["weights_bytes"] + pool_bytes + 5e7
    # the pools are updated in place and no pool-shaped copy is made
    assert rec["alias_GB"] * 1e9 >= pool_bytes - 1e3
    assert " copy(" not in "".join(
        ln for ln in txt.splitlines()
        if f"bf16[16,{4 * N},{ps},128]" in ln.split(" = ")[-1][:40])
    assert rec["temp_GB"] * 1e9 < 0.25 * pool_bytes
    assert rec["need_GB"] * 1e9 < HBM


def test_reference_layer_fits_beside_the_engine(topo, system):
    """The reference's layer over the checked sample's 1,536 positions,
    which has to fit BESIDE the resident engine."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmarks.lib import reference_ouro as ref

    conf = _config()
    one = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    pool_bytes = sum(p.size * 2 for kv in system.engine._pools for p in kv)
    resident = system.weight_bytes + pool_bytes
    S = 1_536           # 1,500 + 23, in whole query blocks of 256
    cos = jax.ShapeDtypeStruct((S, 64), jnp.float32, sharding=one)
    w = {k: sds(v) for k, v in system._ref_weights["layers"][0].items()}
    for dtype in (jnp.float32, jnp.bfloat16):
        x = jax.ShapeDtypeStruct((S, 2048), dtype, sharding=one)
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
        for name in ("kv_pool_used_pct", "engine_resident_seqs",
                     "ragged_live_page_share",
                     "engine_rows_per_step.decode"):
            assert got[name]["value"] is not None, name
