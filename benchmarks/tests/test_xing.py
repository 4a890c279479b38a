"""The Xing configuration's own checks: the held parameters against the
engine's own accounting at the published sizes, the cell's unified step
AND the reference's layers compiled at their REAL sizes for a described
v5e, off the chip (what the compiler says they need: PERF.md, PR 50),
the planted faults and the blocked reference at a toy size through the
engine, and the ``--rehearse`` run of the cell.

Nothing runs on a device here: a compile that passes is not a chip run.
Same rules as ``test_aot_compile.py`` (topology described inside a
module-scoped fixture, compile in the test's own process, persistent
cache off, the kernels' ``_interpret`` switches steered from here).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_xing.py -s
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))
HBM = 16 * 2 ** 30
NAME = "xing4.0-29b-a4b-serve-ep4-d20"


def _config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def test_the_file_holds_every_published_number():
    """Every number of the catalog's entry under the same key, but the
    three under ``reduced``."""
    conf = _config()
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Xing4.0-29B-A4B")
    assert conf["source"] == entry["source_url"]
    for k, v in entry["config"].items():
        if k in conf["reduced"]:
            assert conf["published"][k] == v, k
        else:
            assert conf[k] == v, k


# ------------------------------------------------ toy size, the engine
@pytest.fixture(scope="module")
def served():
    """2 dense + 2 routed layers through the engine: (weights, cfg,
    prompt, tokens, the engine's logits rows)."""
    import importlib.util
    import numpy as np
    from paddle_tpu.serving import ServingEngine
    # (the tier-1 file of the same name, by path: this one shadows it)
    spec = importlib.util.spec_from_file_location(
        "tier1_test_xing", os.path.join(REPO, "tests", "test_xing.py"))
    tier1 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tier1)
    m, w, c = tier1.seeded(num_hidden_layers=4, first_k_dense_replace=2,
                     experts_held=(4, 4))
    eng = ServingEngine(m, max_slots=2, page_size=8, max_context=64,
                        prefill_chunk=16, num_pages=20,
                        enable_prefix_cache=False)
    rows = []
    eng.on_logits = lambda req, row: rows.append(row.copy())
    p = np.random.default_rng(5).integers(0, 256, 37, dtype=np.int32)
    r = eng.add_request(p, max_new_tokens=9)
    eng.run_to_completion()
    return w, c, p, np.asarray(r.tokens), np.stack(rows)


def _reference(w, c, p, tokens, **kw):
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.lib import reference_xing as ref
    fed = jnp.asarray(np.concatenate([p, tokens[:-1]]), jnp.int32)
    return np.asarray(ref.logits(fed, w, c, **kw))[len(p) - 1:]


def test_engine_matches_the_reference_blocked_or_not(served):
    import numpy as np
    w, c, p, tokens, got = served
    want = _reference(w, c, p, tokens)
    np.testing.assert_allclose(got, want, atol=5e-5)
    blocked = _reference(w, c, p, tokens, q_block=16, head_block=2,
                         ffn_block=32)
    np.testing.assert_allclose(blocked, want, atol=5e-5)


@pytest.mark.parametrize("fault", ["sinkhorn_1", "hpost_1", "coef_bf16",
                                   "bias"])
def test_a_planted_fault_shows(served, fault):
    """The engine's logits against the reference WITH one fault: what an
    engine with that fault would read, far outside the tolerance."""
    import numpy as np
    w, c, p, tokens, got = served
    off = _reference(w, c, p, tokens, ablate=frozenset([fault]))
    far = np.abs(got - off).max()
    assert far > 1e-3, far      # 20 x the tolerance at the least


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
    from paddle_tpu.ops import (fused, pallas_gmm, pallas_mhc,
                                pallas_ragged, quant)
    mp = pytest.MonkeyPatch()
    for mod in (fused, pallas_ragged, pallas_mhc, pallas_gmm, quant):
        if hasattr(mod, "_interpret"):
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
    """The whole configuration is built for real on the CPU (8.78 GB of
    bfloat16 weights, 4.20 GB of pools) and its jitted step is lowered
    with those shapes on one described chip, all 20 layers; then the
    reference's dense layer and a routed layer over the checked
    sample's 1,536 positions, which have to fit BESIDE the engine."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmarks.lib import costs_xing as costs, reference_xing as ref
    from benchmarks.systems import xing_serving

    conf = _config()
    system = xing_serving.System(conf, False, seed=0)
    eng = system.engine
    # 4,388,399,800 parameters held, by hand (tests/test_xing.py) and by
    # the engine's own accounting: its tree holds the model's arrays,
    # phi turned into 32 rows of which 24 are used, a float32 [32, 128]
    # register a sublayer for (a, b), and a rope table
    held = costs.n_params(system.cfg)
    assert held == 4_388_399_800 and system.weight_bytes == 2 * held
    acct = eng.hbm_accounting()
    mixing = 40 * ((32 - 24) * 14_336 * 2 + 32 * 128 * 4 - (24 + 3) * 2)
    rope = 2 * conf["engine"]["max_context"] * 32 * 4
    assert acct["weights_bytes"] == 2 * held + mixing + rope
    assert acct["residual_stream_bytes"] == 384 * 28_672
    assert acct["page_pool_bytes"] == 20 * 641 * 256 * 640 * 2

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
    print(f"\n[aot] xing unified step, engine {conf['engine']}, paths "
          f"ragged={eng.ragged}: {json.dumps(rec)}")
    out = os.path.join(REPO, ".scratch")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "xing_step.hlo.txt"), "w") as f:
        f.write(txt)
    assert eng.ragged and rec["pool_shape"] == [1, 641, 256, 640]
    # a layer: a row append, an attention call, two mhc_pre, two mhc_post
    assert rec["tpu_custom_call"] >= 6 * len(eng._pools)
    # the pools are updated in place and no pool-shaped copy is made
    pool_bytes = sum(p.size * 2 for p in eng._pools)
    assert rec["alias_GB"] * 1e9 >= pool_bytes
    assert " copy(" not in "".join(
        ln for ln in txt.splitlines() if "bf16[1,641,256,640]" in ln)
    # ... and no copy of the stream (mhc_post writes it in place)
    assert " copy(" not in "".join(
        ln for ln in txt.splitlines() if " = bf16[384,14336]" in ln)
    assert rec["need_GB"] * 1e9 < HBM

    # the reference beside the resident engine (weights + pools)
    resident = system.weight_bytes + pool_bytes
    S = 1_536
    specs = ref.layer_specs(system.cfg, **{
        k: conf["check"][k] for k in ("q_block", "head_block",
                                      "ffn_block")})
    cos = jax.ShapeDtypeStruct((S, 32), jnp.float32, sharding=one)
    for i in (0, 2):
        keys = ref.ATTN_KEYS + ref.HC_KEYS + (
            ref.MOE_KEYS if specs[i].top_k else ref.DENSE_KEYS)
        w = {k: sds(system._ref_weights["layers"][i][k]) for k in keys}
        x = jax.ShapeDtypeStruct((S, 4, 3584), jnp.float32, sharding=one)
        c = ref.layer.lower(x, w, cos, cos, spec=specs[i],
                            dtype=jnp.float32).compile()
        need = _need(c)
        extra = need["need_GB"] * 1e9 - sum(
            v.size * 2 for v in system._ref_weights["layers"][i].values())
        print(f"[aot] reference layer {i} over {S} positions in float32: "
              f"{json.dumps(need)}; beside the engine "
              f"{(resident + extra) / 1e9:.2f} GB")
        assert resident + extra < HBM


# ------------------------------------------------------------ rehearsal
@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearses(trace):
    from test_rehearsal import check_line, last_json, run_cell
    cell = "xing4-serve-assistant-steady"
    line = last_json(run_cell(cell, "--rehearse", "--trace", str(trace)))
    check_line(line, cell, bool(trace))
    if trace:
        got = line["metrics"]
        for name in ("engine_chunk_ctx_tokens", "moe_held_pair_share",
                     "moe_expert_rows_max_over_mean", "kv_pool_used_pct",
                     "ragged_live_page_share", "mhc_colsum_err_max"):
            assert got[name]["value"] is not None, name
        # half the experts are held at rehearsal size
        assert 35 < got["moe_held_pair_share"]["value"] < 65
