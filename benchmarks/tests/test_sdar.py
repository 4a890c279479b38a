"""The SDAR configuration's own checks: the file against the catalog row
(every key; ``num_hidden_layers`` the only cut), the traffic as ISSUE 60
names it, parameters and costs by hand (4,361,055,744 held), the cell's
unified step at both row counts (768 and 512 flat rows) and the
reference's layer compiled at their REAL sizes for a described v5e, off
the chip, and the ``--rehearse`` run of the cell.

Nothing runs on a device here: a compile that passes is not a chip run.
Same rules as ``test_aot_compile.py``.  Builds 8.9 GB on the CPU (the
weights for real; the page pools at 33 pages, their shapes at the
file's 1,281 — a step is lowered from shapes).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_sdar.py -s
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
NAME = "sdar-30b-a3b-serve-pp8-d6"
CELL = "sdar-serve-blockgen-steady"
PARAMS = 4_361_055_744


def _config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


# ----------------------------------------------------------- the file
def test_the_file_keeps_every_published_key():
    conf = _config()
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["published"] == {"num_hidden_layers": 48}
    assert conf["num_hidden_layers"] == 6
    assert conf["generation"] == {
        "block_length": 4, "denoising_steps": 4,
        "remasking": "low_confidence_static", "temperature": 0,
        "mask_token_id": 151669}
    for key in ("source", "deployment", "assumed", "engine", "check",
                "rehearsal", "generation"):
        assert conf[key], key
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == conf["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "blockgen-steady", 1)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert conf[key] == value, key


def test_the_traffic_is_the_issues_letter_for_letter():
    with open(os.path.join(BENCH, "traffic", "blockgen-steady.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "serve_closed_loop"
    assert (mix["clients"], mix["pool"], mix["order"], mix["set_seed"]) == (
        128, 1024, "fixed", 60)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.8, "min": 64, "max": 4096}
    assert mix["output_len"] == {"dist": "uniform", "min": 512,
                                 "max": 1536}
    assert mix["shared_prefix"] is None
    assert mix["check_prompt_lens"] == [3003, 1030, 61]
    assert mix["check_output_len"] == 24
    assert (mix["trace_after_share"], mix["trace_s"]) == (0.5, 3)
    from benchmarks.lib.traffic import closed_loop
    conf = _config()
    draws = []
    for seed in (7, 2 ** 31 + 5):
        reqs = closed_loop(mix, 50.0, seed, 151669,
                           conf["engine"]["max_context"])
        assert len(reqs) == 1024
        assert all(64 <= len(r.prompt) <= 4096 and 512 <= r.max_new <= 1536
                   and len(r.prompt) + r.max_new <= 5632 for r in reqs)
        assert max(int(r.prompt.max()) for r in reqs) < 151669
        draws.append([(len(r.prompt), r.max_new) for r in reqs])
    assert draws[0] == draws[1]     # one arrival trace every seed
    # pages reserved at admission, prompt + budget in whole blocks
    pages = [-(-(-(-(p + o) // 4) * 4) // 256) for p, o in draws[0]]
    assert max(pages) <= 22 and 6.5 < sum(pages) / 1024 < 9


def test_parameters_and_costs_by_hand():
    from benchmarks.lib import costs_sdar as costs
    from benchmarks.lib.peaks import PEAKS
    from benchmarks.systems.sdar_serving import model_kwargs
    c = model_kwargs(_config())
    assert costs.attention_params(c) == 2048 * 4096 * 2 + 2 * 2048 * 512 \
        + 2 * 128 == 18_874_624
    assert costs.expert_params(c) == 4_718_592
    assert costs.layer_params(c) == 18_874_624 + 2 * 2048 + 262_144 \
        + 603_979_776 == 623_120_640
    assert costs.n_params(c) == 6 * 623_120_640 + 622_329_856 + 2048 \
        == PARAMS
    assert costs.kv_bytes_per_token_layer(c) == 2048
    # one layer's attention over 128 slots at 1,250 tokens + no chunk:
    # K and V once a sequence, 512 rows of q and of output
    flops, byts = costs.ragged_attention_cost(c, 160_000, 512)
    assert byts == 2 * 4 * 160_000 * 128 * 2 + 2 * 512 * 32 * 128 * 2
    assert flops == 4 * 32 * 128 * 4 * 160_000
    # the grouped GEMMs of one layer at 512 rows, every expert hit
    flops, byts = costs.moe_gmm_cost(c, 4096, 128)
    assert flops == 6 * 2048 * 768 * 4096
    assert byts == (128 * 4_718_592 + 2 * 4096 * 2048) * 2
    t, which = costs.roofline_seconds(flops, byts, PEAKS["TPU v5 lite"])
    assert which == "bytes" and 1.4e-3 < t < 1.6e-3
    # a launch: the weights once, every cache token once a layer
    assert costs.serve_step_bytes(2 * PARAMS, c, 160_000) == \
        2 * PARAMS + 6 * 160_000 * 2048


# ------------------------------------------------------ off-chip compile
from test_nemotron import _need, topo  # noqa: E402,F401

POOL_PAGES = 33     # what the CPU holds; the step is lowered at the file's


@pytest.fixture(scope="module")
def system():
    """The configuration's weights built for real on the CPU (8.72 GB),
    the page pools small — once for the module."""
    from benchmarks.systems import sdar_serving
    conf = _config()
    conf["engine"] = dict(conf["engine"], num_pages=POOL_PAGES)
    return sdar_serving.System(conf, False, seed=0)


def test_the_engine_holds_what_the_file_says(system):
    eng = system.engine
    assert system.weight_bytes == 2 * PARAMS
    # (the step's tree: the parameters and the rope tables to 5,632)
    assert eng.hbm_accounting()["weights_bytes"] == 2 * PARAMS \
        + 2 * 5632 * 64 * 4
    assert (eng._block, eng._diff_steps, eng._mask_id) == (4, 4, 151669)
    assert eng._launch_rows(256) == 768 and eng._launch_rows(0) == 512
    assert system.vocab == 151669 and system.max_total == 5632
    assert len(eng._pools) == 6
    assert eng._pools[0][0].shape == (4, POOL_PAGES, 256, 128)
    assert eng.prefix_cache is None and not eng.preemption
    assert "diffusion_passes_denoise" in eng._count_names
    assert "moe_experts_hit" in eng._count_names


@pytest.mark.parametrize("program", ["unified", "unified_nochunk"])
def test_unified_step_fits_one_chip(topo, system, program):
    """The engine's own jitted step lowered with the real shapes on one
    described chip, all 6 layers, at each of its two row counts."""
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

    B = eng.max_slots
    T = eng._launch_rows(eng.prefill_chunk if program == "unified" else 0)
    pool = jax.ShapeDtypeStruct((4, pages, 256, 128), jnp.bfloat16,
                                sharding=one)
    t0 = time.perf_counter()
    lowered = eng._programs[program].lower(
        jax.tree.map(sds, eng._w), i32(T), [(pool, pool)] * 6, i32(T),
        i32(B + 1), (i32(B + 1), i32(B)), i32(B + 1, eng.pages_per_seq),
        i32(T), i32(T))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    txt = compiled.as_text()
    rec = dict(_need(compiled), lower_s=round(t1 - t0, 1),
               compile_s=round(t2 - t1, 1), text_MB=round(len(txt) / 1e6, 2),
               tpu_custom_call=txt.count(
                   "custom_call_target=\"tpu_custom_call\""))
    print(f"\n[aot] sdar {program} ({T} rows), engine {conf['engine']}: "
          f"{json.dumps(rec)}")
    out = os.path.join(REPO, ".scratch")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"sdar_{program}.hlo.txt"), "w") as f:
        f.write(txt)
    pool_bytes = 6 * 2 * 4 * pages * 256 * 128 * 2
    assert rec["args_GB"] * 1e9 < 2 * PARAMS + pool_bytes + 2e8
    # every pool is updated in place, and none is copied
    assert rec["alias_GB"] * 1e9 >= pool_bytes - 1e3
    assert " copy(" not in "".join(
        ln for ln in txt.splitlines()
        if f"bf16[4,{pages},256,128]" in ln.split(" = ")[-1][:48])
    # the block rows' float32 logits (0.31 GB) twice and the routed
    # layers' sorted rows
    assert rec["temp_GB"] * 1e9 < 1.5e9
    assert rec["need_GB"] * 1e9 < HBM - 2 * 0.32e9   # + a launch ahead
    # kernels a layer: the norms, two q / k norms, the append, attention
    assert rec["tpu_custom_call"] >= 6 * 6


def test_reference_layer_fits_beside_the_engine(topo, system):
    """The reference's layer over the longest checked sample's 3,072
    positions in float32, which has to fit BESIDE the resident engine."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmarks.lib import reference_sdar as ref

    conf = _config()
    one = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    pages = conf["engine"]["num_pages"]
    resident = 2 * PARAMS + 6 * 2 * 4 * pages * 256 * 128 * 2 + 3 * 0.32e9
    S = 3072
    spec = ref.layer_specs(system.ref_cfg, conf["check"]["q_block"],
                           conf["check"]["expert_block"])[0]
    w = {k: sds(v) for k, v in system._ref_weights["layers"][0].items()}
    x = jax.ShapeDtypeStruct((S, 2048), jnp.float32, sharding=one)
    t = jax.ShapeDtypeStruct((S, 64), jnp.float32, sharding=one)
    c = ref.layer.lower(x, w, t, t, spec=spec,
                        dtype=jnp.float32).compile()
    need = _need(c)
    held = sum(v.size * v.dtype.itemsize
               for v in system._ref_weights["layers"][0].values())
    extra = need["need_GB"] * 1e9 - held
    print(f"[aot] reference layer over {S} positions in float32: "
          f"{json.dumps(need)}; beside the engine "
          f"{(resident + extra) / 1e9:.2f} GB")
    assert resident + extra < HBM
    h = ref.head_logits.lower(
        jax.ShapeDtypeStruct((4, 2048), jnp.float32, sharding=one),
        sds(system._ref_weights["norm"]), sds(system._ref_weights["head"]),
        eps=1e-6, dtype=jnp.float32,
        vocab_block=conf["check"]["vocab_block"]).compile()
    assert (_need(h)["temp_GB"]) * 1e9 < 0.4e9


# ------------------------------------------------------------ rehearsal
@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearses(trace):
    from test_rehearsal import check_line, last_json, run_cell
    line = last_json(run_cell(CELL, "--rehearse", "--trace", str(trace)))
    check_line(line, CELL, bool(trace))
    if trace:
        got = line["metrics"]
        for name in ("diffusion_passes_per_token",
                     "diffusion_masked_row_share", "kv_pool_used_pct",
                     "moe_expert_rows_max_over_mean",
                     "engine_rows_per_step.decode"):
            assert got[name]["value"] is not None, name
        assert 1.25 <= got["diffusion_passes_per_token"]["value"] < 2.0
