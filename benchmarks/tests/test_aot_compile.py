"""Compile each cell's step at its REAL size for a described v5e:2x2,
off the chip, and record what the compiler says it needs.

Nothing runs here: a compile that passes is not a chip run.  The file
keeps the on-chip-measurement guide's rules: the topology is described
inside a module-scoped, non-autouse fixture, compiles happen in the
test's own process, the persistent cache is off around them, and the
kernels' ``_interpret`` switches are steered from here.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_compile.py -s

``num_pages`` / ``prefill_chunk`` of the serving configuration and the
depth / remat / ce_chunks of the training configuration were fixed from
this file's output (PERF.md, Findings of PR 23).
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
HBM = 16 * 2 ** 30


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


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
    from paddle_tpu.ops import (flash_attention, fused, pallas_flash,
                                pallas_megadecode, pallas_megafront,
                                pallas_ragged, quant)
    mp = pytest.MonkeyPatch()
    # code that asks jax for its backend sees the CPU here and would take
    # its CPU branch: the test steers it, not an option of the program
    mp.setattr(flash_attention, "_tpu_flash_available", lambda: True)
    for mod in (fused, pallas_flash, pallas_megadecode, pallas_megafront,
                pallas_ragged, quant):
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


def _report(tag, compiled):
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    txt = compiled.as_text()
    rec = {"args_GB": ma.argument_size_in_bytes / 1e9,
           "out_GB": ma.output_size_in_bytes / 1e9,
           "temp_GB": ma.temp_size_in_bytes / 1e9,
           "alias_GB": ma.alias_size_in_bytes / 1e9,
           "need_GB": need / 1e9,
           "tpu_custom_call": txt.count("custom_call_target=\"tpu_custom_call\"")}
    print(f"\n[aot] {tag}: {json.dumps(rec)}")
    return need, rec, txt


def serving_step_compiled(topo, **engine_overrides):
    """The unified step of the serving configuration, lowered with
    shapes only: a depth-1 engine is built for real (on the CPU), and
    its jitted step is handed the whole depth's shapes on one described
    chip.  The step's body walks the layers it is given."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmarks.systems import llama_serving

    cfg = _config("mistral-7b-v0.3-serve-d16")
    depth = cfg["num_hidden_layers"]
    eng_args = dict(cfg["engine"], **engine_overrides)
    one_layer = dict(cfg, num_hidden_layers=1,
                     engine=dict(eng_args, num_pages=40))
    eng = llama_serving.System(one_layer, False, seed=0).engine
    one = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    w = jax.tree.map(sds, eng._w)
    w["layers"] = w["layers"] * depth
    kp = eng._pools[0][0]
    pool = jax.ShapeDtypeStruct(
        (kp.shape[0], eng_args["num_pages"]) + kp.shape[2:], kp.dtype,
        sharding=one)
    pools = [(pool, pool)] * depth
    B, C = eng.max_slots, eng.prefill_chunk

    def i32(*d):
        return jax.ShapeDtypeStruct(d, jnp.int32, sharding=one)

    compiled = eng._jit_unified.lower(
        w, i32(B + C), pools, i32(B + C), i32(B + 1), i32(B + 1),
        i32(B + 1, eng.pages_per_seq), i32(B + C), i32(B + C)).compile()
    return eng, compiled


def test_serving_unified_step_fits_one_chip(topo):
    eng, compiled = serving_step_compiled(topo)
    need, rec, _ = _report(
        f"serve unified step, num_pages={_config('mistral-7b-v0.3-serve-d16')['engine']['num_pages']}, "
        f"paths ragged={eng.ragged} megafront={eng.megafront} "
        f"megadecode={eng.megadecode}", compiled)
    assert rec["tpu_custom_call"] > 0
    assert need < HBM


def test_train_step_fits_four_chips(topo):
    """The training configuration's step as ``run_pretrain.run`` builds
    it, on the described 2x2 mesh, from shapes only."""
    from benchmarks.systems import llama_pretrain
    compiled = llama_pretrain.compile_for(
        _config("mistral-7b-v0.3-train-zero2-mp2"), topo.devices)
    need, rec, txt = _report("train step sharding 2 x mp 2", compiled)
    assert rec["tpu_custom_call"] > 0
    assert need < HBM
    for coll in ("all-reduce", "all-gather", "reduce-scatter"):
        print(f"[aot]   {coll}: {txt.count(coll + '(') + txt.count(coll + '-start(')}")


def test_reference_backward_fits_beside_the_training_state(topo):
    """The plain reference's heaviest program, one layer's input
    gradient in float32 over one whole sequence, on the chip that also
    holds its share of the training state."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmarks.lib import reference_llama as ref
    c = _config("mistral-7b-v0.3-train-zero2-mp2")
    t = c["trainer"]
    one = SingleDeviceSharding(topo.devices[0])
    H, I, S = c["hidden_size"], c["intermediate_size"], t["seq_len"]
    nq, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])

    def f32(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one)

    w = {"ln1": f32(H), "ln2": f32(H), "wq": f32(H, nq * d),
         "wk": f32(H, nkv * d), "wv": f32(H, nkv * d),
         "wo": f32(nq * d, H), "wg": f32(H, I), "wu": f32(H, I),
         "wd": f32(I, H)}
    with ref.highest():
        compiled = ref.layer_input_grad.lower(
            f32(1, S, H), w, f32(S, d // 2), f32(S, d // 2), f32(1, S, H),
            nq=nq, nkv=nkv, d=d, eps=c["rms_norm_eps"], dtype=jnp.float32,
            head_block=t["reference_head_block"]).compile()
    need, rec, _ = _report(
        f"reference layer_input_grad, head_block "
        f"{t['reference_head_block']}", compiled)
    # beside it: the state's share (6.04 GB, the train step's arguments),
    # sequence 0's layer inputs in both types and the other sequences
    held = 6.04e9 + (c["num_hidden_layers"] + 1) * S * H * 6 + 3 * S * H * 4
    print(f"[aot]   held beside it {held / 1e9:.2f} GB, together "
          f"{(need + held) / 1e9:.2f} GB of {HBM / 1e9:.2f}")
    assert need + held < 0.9 * HBM
