"""The Mellum2 TRAINING cell's own checks, off the chip: the file
against the catalog and its byte count, the costs by hand, the check's
limits against six planted faults at the rehearsal size, and the
full-width step AND the plain reference's value_and_grad compiled for a
described v5e chip at the timed sizes (nothing runs there: a compile
that passes is not a chip run).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_mellum.py -q -s
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
HBM = 16 * 2 ** 30
NAME = "mellum2-12b-a2.5b-train-ep4-d4"
CELL = "mellum2-train-ep4share-8k"
PARAMS = 595_153_152


def _config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


# ----------------------------------------------------------- the file
def test_the_file_keeps_every_published_key():
    conf = _config()
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert conf["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                 "vocab_size": 98304}
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["experts_held"], conf["vocab_size"]) == (
                4, 16, [0, 16], 24576)
    for key in ("source", "deployment", "assumed", "trainer", "check",
                "rehearsal", "reduced_notes"):
        assert conf[key], key
    assert "9.52 GB" in conf["deployment"] and "28 chips" in \
        conf["deployment"]
    assert conf["trainer"] == {
        "parallel": {}, "seq_len": 8192,
        "global_batch": conf["trainer"]["global_batch"], "remat": "full",
        "scan_layers": False, "ce_chunks": 4, "synthetic_steps": 64,
        "trace_steps": 3}
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == conf["reduced"]
    assert entry["source"] == conf["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "pretrain-8k", 1)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["train_tok_s_chip"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    mine = sorted(m["name"] for m in bench["per_layer"]
                  if CELL in m.get("workloads", ()))
    assert len(mine) == 15 and "flash_roofline.train" not in mine \
        and not any(n.startswith("collective_") for n in mine)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in conf["reduced"]:
            assert conf[key] == value, key


def test_parameters_and_costs_by_hand():
    from benchmarks.lib import costs_mellum as costs
    from benchmarks.lib.peaks import PEAKS
    c = _config()
    outside, expert = costs.layer_params(c)
    assert outside == 2304 * 128 * 40 + 4096 * 2304 + 2304 * 64 + 4608 \
        == 21_385_728
    assert expert == 3 * 2304 * 896 == 6_193_152
    assert costs.held_params(c) == 4 * (21_385_728 + 16 * 6_193_152) \
        + 2 * 24_576 * 2_304 + 2_304 == PARAMS        # x 16 B = 9.52 GB
    assert round(PARAMS * 16 / 1e9, 2) == 9.52
    # the whole model by the same equations: its own name, 12B-A2.5B
    whole = 28 * (outside + 64 * expert) + 2 * 98_304 * 2_304 + 2_304
    active = 28 * (outside + 8 * expert) + 2 * 98_304 * 2_304 + 2_304
    assert (round(whole / 1e9, 2), round(active / 1e9, 2)) == (12.15, 2.44)
    # visible pairs: query i sees min(i + 1, W) keys
    assert costs.visible_pairs(8192, None) == 8192 * 8193 // 2
    assert costs.visible_pairs(8192, 1024) == sum(
        min(i + 1, 1024) for i in range(8192)) == 7_864_832
    assert costs.visible_pairs(64, 1024) == 64 * 65 // 2
    # a token's FLOPs at a uniform router (2 of its 8 pairs held): the
    # matmul parameters, the head, attention over the visible pairs
    per_tok = costs.train_flops_per_token(c, 8192, 2.0)
    matmul = outside - 4608
    pairs = (3 * 7_864_832 + 8192 * 8193 // 2) / 8192
    assert per_tok == pytest.approx(
        6.0 * (4 * (matmul + 2 * expert) + 24_576 * 2_304)
        + 12.0 * 32 * 128 * pairs)
    assert 1.45e9 < per_tok < 1.55e9        # ISSUE 66: forward ~0.50 GFLOP
    # one sliding layer's kernels over 2 x 8,192 tokens: compute-bound
    flops, byts = costs.flash_band_cost(c, 2, 8192, 1024)
    assert flops == 3.5 * 4 * 2 * 32 * 128 * 7_864_832
    q, kv = 2 * 32 * 8192 * 128 * 2, 2 * 4 * 8192 * 128 * 2
    assert byts == 6 * q + 6 * kv
    t, which = costs.roofline_seconds(flops, byts, PEAKS["TPU v5 lite"])
    assert which == "flops"
    full, _ = costs.flash_band_cost(c, 2, 8192, None)
    assert full / flops == pytest.approx(8193 * 4096 / 7_864_832)
    # the grouped GEMMs of a step at 4 layers x 16,384 x 2 held pairs
    held = 4 * 16_384 * 2
    flops, byts = costs.moe_gmm_cost(c, held)
    assert flops == 3 * 2 * held * 3 * 2304 * 896
    assert byts == 3 * 4 * 16 * expert * 2 \
        + 3 * held * (2 * (2304 + 896) + 896 + 2304) * 2
    assert costs.roofline_seconds(flops, byts,
                                  PEAKS["TPU v5 lite"])[1] == "flops"


# ---------------------------------------------- the check's four faults
def test_each_planted_fault_fails_a_limit(monkeypatch):
    """At the rehearsal size, on the step the timed path makes: the
    check holds against the plain reference, and fails at least one of
    its limits against a reference with full attention on the sliding
    layers, a first-choice-only F_e, the share [4, 4] instead of [0, 4],
    or the weights normalised over the held choices alone; and against
    the clean reference with the optimiser's step undone on the stacks
    or the bfloat16 copy left as it was."""
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import reference_mellum as ref
    from benchmarks.systems import mellum_pretrain
    system = mellum_pretrain.System(_config(), True, 3000000001,
                                    jax.devices()[:1])
    ids, labels = next(system.batches())
    route, balance = ref.route, ref.load_balance

    def first_choice_only(g, topi):
        return balance(g, topi[:, :1])

    def over_held_alone(h2, wr, top_k):
        g, topi, w = route(h2, wr, top_k)
        first, count = system.ref_kw["held"]
        mine = (topi >= first) & (topi < first + count)
        w = jnp.where(mine, w, 0.0)
        return g, topi, w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    wants = {"none": system.reference(ids, labels)}
    for fault, patch in (
            ("full attention on sliding layers",
             lambda m: m.setitem(system.ref_kw, "sliding_window", None)),
            ("first-choice-only F_e",
             lambda m: m.setattr(ref, "load_balance", first_choice_only)),
            ("share [4, 4] for [0, 4]",
             lambda m: m.setitem(system.ref_kw, "held", (4, 4))),
            ("weights over the held choices alone",
             lambda m: m.setattr(ref, "route", over_held_alone))):
        with monkeypatch.context() as m:
            patch(m)
            wants[fault] = system.reference(ids, labels)
    got = system.first_step(ids, labels, wants["none"]["rows"])
    verdicts = {k: system.judge(w, got, labels.size)
                for k, w in wants.items()}
    # and two in the state the step left: the optimiser's step undone on
    # the router and expert stacks, the bfloat16 copy not refreshed
    from benchmarks.tools.mellum_limit import state_faults
    for fault, broken in state_faults(got).items():
        verdicts[fault] = system.judge(wants["none"], broken, labels.size)
    for k, v in verdicts.items():
        print(k, {n: float(f"{x:.4g}") if isinstance(x, float) else x
                  for n, x in v.items()})
    assert verdicts.pop("none")["ok"]
    for fault, v in verdicts.items():
        assert not v["ok"], fault
    assert verdicts["update_skipped"]["failed"] == [
        "router_update", "expert_down_update"]
    assert verdicts["stale_copy"]["failed"] == [
        "embed_copy", "router_copy", "expert_down_copy"]
    assert np.isfinite(got["loss"])


# ------------------------------------------------------ off-chip compile
from test_aot_compile import _report, topo  # noqa: E402,F401


def test_the_step_fits_one_chip_at_the_timed_sizes(topo):
    """The trainer's step for the file as it stands — 2 x 8,192 tokens,
    D = 128, W = 1,024 in 512-blocks, 16 experts' stacks, remat "full"
    keeping nothing (a described device reports no limit) — compiled
    for ONE described v5e chip: every kernel tiles, and state + step fit
    under 15.75 GiB."""
    from benchmarks.systems import mellum_pretrain
    conf = {k: v for k, v in _config().items() if k != "rehearsal"}
    compiled = mellum_pretrain.compile_for(conf, topo.devices)
    need, rec, txt = _report("mellum2 train step", compiled)
    # three layers' band launches and one full layer's, forward, remat
    # forward, dq and dk / dv; none from flashmask
    assert rec["tpu_custom_call"] >= 16
    assert need < 15.75 * 2 ** 30, rec
    # the arguments it reads: float32 master weights and two moments,
    # 12 B a parameter (the bfloat16 copy is an output, the bfloat16
    # gradients are scratch)
    assert rec["args_GB"] * 1e9 == pytest.approx(PARAMS * 12, rel=1e-3)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_reference_fits_beside_the_state(topo, dtype):
    """The plain reference's value_and_grad at 16,384 tokens, its
    weights the trainer's own master tree (9.52 GB of state stay where
    they are, 7.14 GB of them are not its arguments), compiled for the
    same chip: scratch and outputs fit in what the state leaves."""
    import jax.numpy as jnp
    from benchmarks.systems import mellum_pretrain
    conf = {k: v for k, v in _config().items() if k != "rehearsal"}
    compiled = mellum_pretrain.compile_reference_for(
        conf, topo.devices, jnp.dtype(dtype))
    need, rec, _ = _report(f"mellum2 reference {dtype}", compiled)
    state = PARAMS * 14                 # bf16 copy + master + two moments
    mine = PARAMS * 4                   # the master weights it reads
    assert state - mine + need < 15.75 * 2 ** 30, rec
