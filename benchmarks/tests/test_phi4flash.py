"""The Phi-4-mini-flash configuration's own checks: the file against the
catalog row (every key, nothing reduced), the parameter and state counts
by hand and by `hbm_accounting` (3,852,562,944 parameters, 327,680 B of
state a (slot, layer) AS STORED, ONE full pool of 1,217 pages), the
cell's unified step at both row counts and the reference's layers
compiled at their REAL sizes for a described v5e, off the chip (weights
held once, every pool updated in place, 8 appends and 16 attention
launches, no 9th full pool, no pool-sized temporary), and the
``--rehearse`` run of the cell.

Nothing runs on a device here: a compile that passes is not a chip run.
Same rules as ``test_aot_compile.py``.  Builds 10.8 GB on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_phi4flash.py -s
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
NAME = "phi-4-mini-flash-serve-whole"
CELL = "phi4flash-serve-longreason-saturated"


def _config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


# ----------------------------------------------------------- the file
def test_the_file_keeps_every_published_key():
    """Against the catalog row beside the model-configs guide, where it
    is on this machine: every key of its ``config`` under the same name
    with the same value; nothing is reduced."""
    conf = _config()
    assert conf["reduced"] == [] and conf["published"] == {}
    assert conf["pattern_as_run"] == "SD*D" * 9 + "G32DX34D" * 7
    for key in ("source", "deployment", "assumed", "engine", "check",
                "rehearsal"):
        assert conf[key], key
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == [] and entry["source"] == conf["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "longreason-saturated", 1)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert conf[key] == value, key


def test_the_traffic_is_the_issues_letter_for_letter():
    with open(os.path.join(BENCH, "traffic",
                           "longreason-saturated.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "serve_closed_loop"
    assert (mix["clients"], mix["pool"], mix["order"], mix["set_seed"]) == (
        32, 32, "seeded", 56)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                 "sigma": 0.5, "min": 1024, "max": 12288}
    assert mix["output_len"] == {"dist": "uniform", "min": 3072,
                                 "max": 3584}
    assert mix["shared_prefix"] is None
    assert mix["check_prompt_lens"] == [11590, 4100, 1030]
    assert mix["check_output_len"] == 24
    # the draw the issue states: one wave, the same multiset every seed
    import numpy as np
    from benchmarks.lib.traffic import closed_loop
    for seed in (7, 2 ** 31 + 5):
        reqs = closed_loop(mix, 50.0, seed, 200064, 16384)
        lens = sorted(len(r.prompt) for r in reqs)
        assert (len(reqs), lens[0], lens[-1], sum(lens)) == (
            32, 1233, 11595, 166819)
        assert sum(-(-n // 256) for n in lens) == 669
        assert sum(-(-(len(r.prompt) + r.max_new) // 256)
                   for r in reqs) == 1089
        assert np.min([r.max_new for r in reqs]) >= 3072


# ------------------------------------------------------ off-chip compile
from test_nemotron import _need, topo  # noqa: E402,F401


@pytest.fixture(scope="module")
def system():
    """The whole configuration built for real on the CPU (7.71 GB of
    weights, 2.99 GB of pages, 0.11 GB of state) — once for the module."""
    from benchmarks.systems import phi4flash_serving
    return phi4flash_serving.System(_config(), False, seed=0)


def test_the_engine_holds_what_the_file_says(system):
    import jax
    eng = system.engine
    conf = _config()
    acct = eng.hbm_accounting()
    B = eng.max_slots
    assert system.weight_bytes == 2 * 3_852_562_944
    # the step's own tree: every parameter once (the attention
    # projections it reads are stored [heads, D, in] BESIDE the module's,
    # `generation._heads_w`: 0.33 GB more is resident, 9 x q, k, v + 7 x q)
    assert acct["weights_bytes"] == 2 * 3_852_562_944
    assert eng._blocks == tuple(
        ["S", "D", "*", "D"] * 9 + ["G32", "D", "X34", "D"] * 7)
    assert sum(b[0] in "GX" for b in eng._blocks) == 14
    assert len(eng._pools["kv"]) == 9 and len(eng._pools["ssm"]) == 9
    assert eng._layer_kind == [1] * 8 + [0]
    assert eng._pool_readers == [1] * 8 + [8]
    # ONE full pool of 1,217 pages in the pair layout; 8 window pools
    assert eng.num_pages == conf["engine"]["num_pages"] == 1217
    assert eng._pools["kv"][8][0].shape == (10, 1217, 256, 128)
    assert eng.num_window_pages == 133
    assert eng._pools["kv"][0][0].shape == (10, 133, 256, 128)
    # 327,680 B of state a (slot, layer) as stored
    for state, tail in eng._pools["ssm"]:
        assert state.shape == (B + 1, 1, 16, 5120)
        assert tail.shape == (B + 1, 3, 5120)
    assert acct["state_pool_bytes"] == (B + 1) * 9 * (327_680 + 30_720)
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(eng._pools))
    assert acct["page_pool_bytes"] == pool_bytes == (
        1217 + 8 * 133) * 1_310_720 + (B + 1) * 9 * 358_400


@pytest.mark.parametrize("program", ["unified", "unified_nochunk"])
def test_unified_step_fits_one_chip(topo, system, program):
    """The engine's own jitted step lowered with the real shapes on one
    described chip, all 32 layers, at each of its two row counts."""
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
    table = i32(B + 1, eng.pages_per_seq)
    t0 = time.perf_counter()
    lowered = eng._programs[program].lower(
        jax.tree.map(sds, eng._w), i32(T), jax.tree.map(sds, eng._pools),
        i32(T), i32(B + 1), (i32(B + 1), i32(B + 3)), (table, table),
        (i32(T), i32(T)), i32(T))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    txt = compiled.as_text()
    rec = dict(_need(compiled), lower_s=round(t1 - t0, 1),
               compile_s=round(t2 - t1, 1), text_MB=round(len(txt) / 1e6, 2),
               tpu_custom_call=txt.count(
                   "custom_call_target=\"tpu_custom_call\""),
               conditionals=txt.count(" conditional("))
    print(f"\n[aot] phi4flash {program}, engine {conf['engine']}, paths "
          f"ragged={eng.ragged}: {json.dumps(rec)}")
    out = os.path.join(REPO, ".scratch")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"phi4flash_{program}.hlo.txt"), "w") as f:
        f.write(txt)
    acct = eng.hbm_accounting()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(eng._pools))
    # the arguments are the logical bytes: nothing is stored padded (the
    # state 327,680 B a (slot, layer), the pages 1,310,720 B)
    assert rec["args_GB"] * 1e9 < acct["weights_bytes"] + pool_bytes + 5e7
    # every pool is updated in place: 9 page pools, 9 state pools
    assert rec["alias_GB"] * 1e9 >= pool_bytes - 1e3
    # no copy of the shared pool or of a state pool
    for shape in ("bf16[10,1217,256,128]", f"f32[{B + 1},1,16,5120]"):
        assert " copy(" not in "".join(
            ln for ln in txt.splitlines()
            if shape in ln.split(" = ")[-1][:48]), shape
    assert rec["temp_GB"] * 1e9 < 0.6e9
    assert rec["need_GB"] * 1e9 < HBM
    # kernels: 9 appends (8 window layers + layer 17) and 16 attention
    # launches; 9 state updates (+ 9 scans and 9 puts with a chunk);
    # LayerNorms, 65
    chunk = program == "unified"
    assert rec["tpu_custom_call"] >= 9 + 16 + 9 * (3 if chunk else 1) + 65


def test_reference_layers_fit_beside_the_engine(topo, system):
    """The reference's layers over the longest checked sample's 11,613
    positions, which have to fit BESIDE the resident engine."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmarks.lib import reference_phi4flash as ref

    conf = _config()
    one = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    eng = system.engine
    resident = eng.hbm_accounting()["weights_bytes"] + sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(eng._pools))
    S = 11_613
    blocks = {k: v for k, v in conf["check"].items()
              if k in ("q_block", "ffn_block")}
    spec = ref.spec(system.cfg, **blocks)
    layers = system._ref_weights["layers"]
    f32 = jnp.float32
    for l, kind in ((16, "S"), (17, "F"), (18, "G"), (19, "X")):
        w = {k: sds(v) for k, v in layers[l].items()}
        x = jax.ShapeDtypeStruct((S, 2560), f32, sharding=one)
        m = jax.ShapeDtypeStruct((S, 5120), f32, sharding=one) \
            if kind == "G" else None
        kv = (jax.ShapeDtypeStruct((S, 20, 64), f32, sharding=one),) * 2 \
            if kind == "X" else None
        lam = jax.ShapeDtypeStruct((), f32, sharding=one)
        c = ref.layer.lower(x, w, lam, m, kv, kind=kind, spec=spec,
                            dtype=f32).compile()
        need = _need(c)
        held = sum(v.size * v.dtype.itemsize for v in layers[l].values())
        # beside it: the stream, the memory and layer 17's k, v
        carried = S * (2560 + 5120 + 2 * 1280) * 4
        extra = need["need_GB"] * 1e9 - held + carried
        print(f"[aot] reference layer {l} ({kind}) over {S} positions in "
              f"float32: {json.dumps(need)}; beside the engine "
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
        for name in ("kv_pool_used_pct.full", "kv_pool_used_pct.window",
                     "state_pool_used_pct", "window_live_page_pct",
                     "engine_rows_per_step.decode"):
            assert got[name]["value"] is not None, name
