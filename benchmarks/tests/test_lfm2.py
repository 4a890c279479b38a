"""The LFM2 configuration's own checks: the file against the catalog row
(every key; ``num_hidden_layers`` the only cut), the traffic as ISSUE 64
names it, parameters and costs by hand (5,267,090,176 held), the cell's
unified step at both row counts (1,216 and 192 flat rows) compiled at its
REAL sizes for a described v5e, off the chip — the first model whose
published head is 64 lanes, stored two heads a 128-lane row —, and the
``--rehearse`` run of the cell.

Nothing runs on a device here: a compile that passes is not a chip run.
Same rules as ``test_aot_compile.py``.  Builds 10.6 GB on the CPU (the
weights for real; the pools at 33 pages, their shapes at the file's
2,049 — a step is lowered from shapes).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_lfm2.py -s
"""

import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
HBM = 16 * 2 ** 30
NAME = "lfm2-24b-a2b-serve-pp4-d10"
CELL = "lfm2-serve-agent-0.8knee"
PARAMS = 5_267_090_176


def _config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


# ----------------------------------------------------------- the file
def test_the_file_keeps_every_published_key():
    conf = _config()
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["published"] == {"num_hidden_layers": 40}
    assert conf["num_hidden_layers"] == 10
    assert conf["layers_held"] == list(range(10))
    for key in ("source", "deployment", "assumed", "engine", "check",
                "rehearsal", "published"):
        assert conf[key], key
    assert conf["engine"] == {
        "max_slots": 192, "page_size": 256, "max_context": 9216,
        "prefill_chunk": 1024, "num_pages": conf["engine"]["num_pages"],
        "enable_prefix_cache": True, "prefix_sharing": False}
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == conf["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "agent-0.8knee", 1)
    # every per-layer metric keeps a list, and the cell is in 37 of them
    # (25 + 5 + its own six + `state_pool_used_pct`: a tail a slot)
    assert all(m.get("workloads") for m in bench["per_layer"])
    assert sum(CELL in m["workloads"] for m in bench["per_layer"]) == 37
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert conf[key] == value, key
    assert conf["layer_types"][:10] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv"]


def test_the_traffic_is_the_issues_letter_for_letter():
    with open(os.path.join(BENCH, "traffic", "agent-0.8knee.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "serve_open_loop"
    assert mix["arrivals"] == {"process": "poisson"}
    assert (mix["order"], mix["set_seed"], mix["drain_s"]) == (
        "fixed", 64, 60)
    assert mix["shared_prefix"] == {"tokens": 4096, "groups": 1}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 5120,
                                 "sigma": 0.18, "min": 4224, "max": 8192}
    assert mix["output_len"] == {"dist": "uniform", "min": 192, "max": 640}
    assert mix["check_prompt_lens"] == [8000, 1300, 61]
    assert mix["check_output_len"] == 24
    assert (mix["trace_after_share"], mix["trace_s"]) == (0.5, 3)
    assert "sweep" in mix["rate_note"] and mix["rate_per_s"] > 0
    from benchmarks.lib.traffic import open_loop
    conf = _config()
    draws = []
    for seed in (7, 2 ** 31 + 5):
        reqs = open_loop(mix, 50.0, seed, 65536,
                         conf["engine"]["max_context"])
        assert len(reqs) == round(50 * mix["rate_per_s"])
        assert all(4224 <= len(r.prompt) <= 8192 and 192 <= r.max_new <= 640
                   for r in reqs)
        # ONE prefix of 4,096 tokens in front of every prompt
        assert all((r.prompt[:4096] == reqs[0].prompt[:4096]).all()
                   for r in reqs)
        assert len({int(r.prompt[4096]) for r in reqs}) > 10
        draws.append([(len(r.prompt), r.max_new, r.due) for r in reqs])
    assert draws[0] == draws[1]     # one arrival trace every seed
    own = sorted(p - 4096 for p, _, _ in draws[0])
    assert 850 < own[len(own) // 2] < 1200


def test_parameters_and_costs_by_hand():
    from benchmarks.lib import costs_lfm2 as costs
    from benchmarks.lib.peaks import PEAKS
    from benchmarks.systems.lfm2_serving import model_kwargs, reader_config
    c = reader_config(model_kwargs(_config()))
    assert costs.kinds(c) == {"conv": 8, "attn": 2, "dense": 2, "moe": 8}
    assert costs.conv_params(c) == 2048 * 6144 + 2048 * 2048 + 2048 * 3 \
        == 16_783_360
    assert costs.attention_params(c) == 2 * 2048 * 2048 + 2 * 2048 * 512 \
        + 128 == 10_485_888
    assert costs.dense_ffn_params(c) == 72_351_744
    assert costs.expert_params(c) == 9_437_184
    assert costs.moe_params(c, 64) == 603_979_776 + 131_136
    # a dense layer, a routed conv layer, a routed attention layer
    assert 16_783_360 + 4096 + 72_351_744 == 89_139_200
    assert 16_783_360 + 4096 + 604_110_912 == 620_898_368
    assert 10_485_888 + 4096 + 604_110_912 == 614_600_896
    assert costs.n_params(c) == 2 * 89_139_200 + 2 * 614_600_896 \
        + 6 * 620_898_368 + 134_219_776 == PARAMS
    assert 2.2e9 < costs.n_params(dict(c, layers_held=list(range(40)),
                                       num_hidden_layers=40),
                                  active=True) < 2.4e9
    assert costs.n_params(dict(c, layers_held=list(range(40)))) \
        == 2 * 89_139_200 + 28 * 620_898_368 + 10 * 614_600_896 \
        + 134_219_776
    assert costs.kv_row_bytes(c) == 2048 and costs.tail_bytes(c) == 8192
    # one attention layer over 150 decode rows at 5,600 tokens and a
    # chunk of 1,024 behind 4,096: every cache token once, q and o a row
    seqs = [(1, 5600)] * 150 + [(1024, 5120)]
    flops, byts = costs.ragged_attention_cost(c, seqs)
    assert byts == (2 * 8 * (150 * 5600 + 5120)
                    + 2 * (150 + 1024) * 32) * 64 * 2
    assert flops == 4 * 32 * 64 * (150 * 5600 + 1024 * 5120
                                   - 1024 * 1023 // 2)
    # the grouped GEMMs of one layer at 1,216 rows, every expert hit
    flops, byts = costs.moe_gmm_cost(c, 4 * 1216, 64)
    assert flops == 6 * 2048 * 1536 * 4864
    assert byts == (64 * 9_437_184 + 2 * 4864 * 2048) * 2
    t, which = costs.roofline_seconds(flops, byts, PEAKS["TPU v5 lite"])
    assert which == "bytes" and 1.4e-3 < t < 1.6e-3
    # a launch: the weights once (every expert hit), every cache token
    # once an attention layer, 151 slots' tails in and out of 8 blocks,
    # four pages' snapshots written, one read
    assert costs.serve_step_bytes(c, 2 * PARAMS, 845_120, 151, 4, 1, 512) \
        == 2 * PARAMS + 2 * 2048 * 845_120 + 8 * 8192 * (302 + 5)
    assert costs.serve_step_bytes(c, 2 * PARAMS, 0, 0, 0, 0, 500) \
        == 2 * PARAMS - 12 * 2 * 9_437_184


# ------------------------------------------------------ off-chip compile
from test_nemotron import _need, topo  # noqa: E402,F401

POOL_PAGES = 33     # what the CPU holds; the step is lowered at the file's


@pytest.fixture(scope="module")
def system():
    """The configuration's weights built for real on the CPU (10.53 GB),
    the pools small — once for the module."""
    from benchmarks.systems import lfm2_serving
    conf = _config()
    conf["engine"] = dict(conf["engine"], num_pages=POOL_PAGES)
    return lfm2_serving.System(conf, False, seed=0)


def test_the_engine_holds_what_the_file_says(system):
    eng = system.engine
    assert system.weight_bytes == 2 * PARAMS
    # (the step's tree: the parameters and the rope tables to 9,216)
    assert eng.hbm_accounting()["weights_bytes"] == 2 * PARAMS \
        + 2 * 9216 * 32 * 4
    assert eng._launch_rows(1024) == 1216 and eng._launch_rows(0) == 192
    assert system.vocab == 65536 and system.max_total == 9216
    assert len(eng._pools["kv"]) == 2 and len(eng._pools["ssm"]) == 8
    # a stored row is TWO published heads of 64 side by side
    assert eng._pools["kv"][0][0].shape == (4, POOL_PAGES, 256, 128)
    assert eng._kv_geom == (4, 128) and eng._q_rep == 8
    assert [a.shape for a in eng._pools["ssm"][0]] == [
        (193, 2, 2048), (POOL_PAGES, 2, 2048)]
    assert eng.prefix_cache is not None and eng._tail_snapshots
    assert not eng.preemption and not eng.prefix_sharing
    assert "tail_snapshots_written" in eng._count_names
    assert "moe_experts_hit" in eng._count_names


@pytest.mark.parametrize("program", ["unified", "unified_nochunk"])
def test_unified_step_fits_one_chip(topo, system, program):
    """The engine's own jitted step lowered with the real shapes on one
    described chip, all 10 layers, at each of its two row counts: the
    ragged kernel and the rope + append over rows of two 64-lane heads
    (a pool of 64-lane rows does not lower: Mosaic refuses the slice)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    conf = _config()
    eng = system.engine
    pages = conf["engine"]["num_pages"]
    one = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    def i32(*d):
        return jax.ShapeDtypeStruct(d, jnp.int32, sharding=one)

    def bf16(*d):
        return jax.ShapeDtypeStruct(d, jnp.bfloat16, sharding=one)

    B = eng.max_slots
    T = eng._launch_rows(eng.prefill_chunk if program == "unified" else 0)
    pool = bf16(4, pages, 256, 128)
    pools = {"kv": [(pool, pool)] * 2,
             "ssm": [(bf16(B + 1, 2, 2048), bf16(pages, 2, 2048))] * 8}
    t0 = time.perf_counter()
    lowered = eng._programs[program].lower(
        jax.tree.map(sds, eng._w), i32(T), pools, i32(T), i32(B + 1),
        (i32(B + 1), i32(B + 4)), i32(B + 1, eng.pages_per_seq), i32(T),
        i32(T))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    txt = compiled.as_text()
    rec = dict(_need(compiled), lower_s=round(t1 - t0, 1),
               compile_s=round(t2 - t1, 1), text_MB=round(len(txt) / 1e6, 2),
               tpu_custom_call=txt.count(
                   "custom_call_target=\"tpu_custom_call\""))
    print(f"\n[aot] lfm2 {program} ({T} rows), engine {conf['engine']}: "
          f"{json.dumps(rec)}")
    out = os.path.join(REPO, ".scratch")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"lfm2_{program}.hlo.txt"), "w") as f:
        f.write(txt)
    pool_bytes = 2 * 2 * 8 * pages * 256 * 64 * 2 \
        + 8 * (B + 1 + pages) * 2 * 2048 * 2
    assert rec["args_GB"] * 1e9 < 2 * PARAMS + pool_bytes + 2e8
    # every pool, tail and plane is updated in place, and none is copied
    assert rec["alias_GB"] * 1e9 >= pool_bytes - 1e3
    assert " copy(" not in "".join(
        ln for ln in txt.splitlines()
        if f"bf16[4,{pages},256,128]" in ln.split(" = ")[-1][:48]
        or f"bf16[{pages},2,2048]" in ln.split(" = ")[-1][:48])
    assert rec["temp_GB"] * 1e9 < 1.5e9
    assert rec["need_GB"] * 1e9 < HBM - 2 * 0.06e9   # + a launch ahead
    # kernels: the norms, two q / k norms + the append + attention in
    # each of the two attention layers
    assert rec["tpu_custom_call"] >= 20 + 2 * 4


def test_reference_layers_fit_beside_the_engine(topo, system):
    """The reference's routed layer and its attention over the longest
    checked sample's 8,064 positions in float32, which have to fit BESIDE
    the resident engine."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmarks.lib import reference_lfm2 as ref

    conf = _config()
    one = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    pages = conf["engine"]["num_pages"]
    resident = 2 * PARAMS + 2 * 2 * 8 * pages * 256 * 64 * 2 \
        + 8 * (193 + pages) * 8192 + 3 * 0.05e9
    S = 8064
    spec = ref.Spec(32, 8, 1e-5, 4, True, 1.0, conf["check"]["q_block"],
                    conf["check"]["expert_block"], frozenset(), 0, 0)
    x = jax.ShapeDtypeStruct((S, 2048), jnp.float32, sharding=one)
    t = jax.ShapeDtypeStruct((S, 32), jnp.float32, sharding=one)
    layers = system._ref_weights["layers"]
    worst = 0.0
    for fn, keys, w, extra in (
            (ref.moe_ffn, ref.MOE_KEYS, layers[2], ()),
            (ref.attn_mixer, ref.ATTN_KEYS, layers[2], (t, t)),
            (ref.dense_ffn, ref.DENSE_KEYS, layers[0], ()),
            (ref.conv_mixer, ref.CONV_KEYS, layers[0], ())):
        ws = {k: sds(w[k]) for k in keys}
        c = fn.lower(x, ws, *extra, spec=spec, dtype=jnp.float32).compile()
        need = _need(c)
        held = sum(w[k].size * w[k].dtype.itemsize for k in keys)
        worst = max(worst, need["need_GB"] * 1e9 - held)
        print(f"[aot] reference {fn.__name__} over {S} positions in "
              f"float32: {json.dumps(need)}")
    print(f"[aot] beside the engine {(resident + worst) / 1e9:.2f} GB")
    assert resident + worst < HBM


# ------------------------------------------------------------ rehearsal
@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearses(trace):
    from test_rehearsal import check_line, last_json, run_cell
    line = last_json(run_cell(CELL, "--rehearse", "--trace", str(trace)))
    check_line(line, CELL, bool(trace))
    if trace:
        got = line["metrics"]
        for name in ("prefix_hit_token_share", "kv_pool_used_pct",
                     "moe_expert_rows_max_over_mean",
                     "engine_rows_per_step.decode"):
            assert got[name]["value"] is not None, name
        assert 0.3 < got["prefix_hit_token_share"]["value"] < 0.9
