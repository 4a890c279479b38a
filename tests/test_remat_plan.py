"""`remat` "full" keeps what the chip has room for (ISSUE 63): a layer's
checkpoint saves named residuals chosen by bytes against what the device
reports free beyond the program that keeps nothing."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.distributed.mesh import global_device_put
from paddle_tpu.models.llama import llama_tiny_config
from paddle_tpu.observability import attribution as at
from paddle_tpu.ops import flash_attention
from paddle_tpu.trainer import pretrain
from paddle_tpu.trainer.pretrain import (PretrainConfig,
                                         build_llama_pretrain_step,
                                         choose_remat_plan,
                                         make_hybrid_mesh_for, remat_order)

MB = 1 << 20
#: the four-chip cell's bytes a layer a chip (ISSUE 63: 68 / 134 / 101 /
#: 470 MB), eight layers
CELL = {"flash_o": 64 * MB, "flash_lse": 1 * MB, "attn_out": 128 * MB,
        "qkv": 96 * MB, "gate_up": 448 * MB}
FLASH = ("flash_o", "flash_lse")
ALL_BUT_GATE = FLASH + ("attn_out", "qkv")


def _whole(plan, names):
    """`names` taken for every layer."""
    return all(set(names) <= set(kept) for kept in plan)


@pytest.mark.parametrize("case, headroom, unrolled, mp, want", [
    ("nothing free", 0, True, 2, [()] * 8),
    ("less than nothing free", -5 * MB, True, 2, [()] * 8),
    ("one flash pair fits: a layer unrolled", 70 * MB, True, 2,
     [FLASH] + [()] * 7),
    ("one flash pair fits: none under scan", 70 * MB, False, 2, [()] * 8),
    ("flash whole, attn_out for three", (8 * 65 + 3 * 128 + 100) * MB, True,
     2, [FLASH + ("attn_out",)] * 3 + [FLASH] * 5),
    ("flash whole, attn_out not whole: scan stops", (8 * 65 + 900) * MB,
     False, 2, [FLASH] * 8),
    ("the cell: three whole, gate_up for one",
     (8 * (65 + 128 + 96) + 448 + 300) * MB, True, 2,
     [ALL_BUT_GATE + ("gate_up",)] + [ALL_BUT_GATE] * 7),
    ("the cell under scan: three whole, no gate_up",
     (8 * (65 + 128 + 96) + 448 + 300) * MB, False, 2, [ALL_BUT_GATE] * 8),
    ("room for all", 8 * 800 * MB, True, 2,
     [ALL_BUT_GATE + ("gate_up",)] * 8),
    ("room for all under scan", 8 * 800 * MB, False, 2,
     [ALL_BUT_GATE + ("gate_up",)] * 8),
    # no tensor parallelism: attn_out repeats no all-reduce and ranks
    # with the matmuls, behind qkv
    ("mp 1: qkv before attn_out", (8 * (65 + 96) + 2 * 128 + 10) * MB, True,
     1, [FLASH + ("qkv", "attn_out")] * 2 + [FLASH + ("qkv",)] * 6),
    ("mp 2: attn_out before qkv", (8 * (65 + 128) + 2 * 96 + 10) * MB, True,
     2, [FLASH + ("attn_out", "qkv")] * 2 + [FLASH + ("attn_out",)] * 6),
])
def test_the_choice(case, headroom, unrolled, mp, want):
    plan = choose_remat_plan(CELL, 8, headroom, unrolled, remat_order(mp))
    assert plan == want, case
    # deterministic, and never beyond the headroom
    assert plan == choose_remat_plan(CELL, 8, headroom, unrolled,
                                     remat_order(mp))
    assert sum(CELL[n] for kept in plan for n in kept) <= max(headroom, 0)
    # the order is kept: a later entry is nowhere unless every earlier
    # one is everywhere
    order = remat_order(mp)
    for before, after in zip(order, order[1:]):
        if any(set(after) & set(kept) for kept in plan):
            assert _whole(plan, before), (case, before, after)


def test_the_order_names_the_vocabulary_once():
    for mp in (1, 2, 8):
        # a dense layer's FFN keeps `gate_up`, a routed one's (ISSUE 66)
        # the sorted pair rows' `moe_gate_up`: the two orders between
        # them name every residual, each its own once
        flat = [n for names in remat_order(mp) for n in names]
        routed = [n for names in remat_order(mp, routed=True) for n in names]
        assert sorted(flat) == sorted(set(at.RESIDUALS) - {"moe_gate_up"})
        assert sorted(routed) == sorted(set(at.RESIDUALS) - {"gate_up"})
        assert flat[:2] == routed[:2] == list(FLASH)
        assert routed[:-1] == flat[:-1]
    with pytest.raises(ValueError):
        at.residual(jnp.zeros(2), "swiglu")


@pytest.mark.parametrize("case, over, unrolled, want_gate, want_rest", [
    # under scan, or with nothing to reckon from, the entry goes whole
    ("scan: whole", 10 * MB, False, 0, ALL_BUT_GATE),
    ("the compiler refused it: whole", None, True, 0, ALL_BUT_GATE),
    # unrolled: the last holders let go, as many as the excess is worth
    ("a little over: one layer", 10 * MB, True, 4, ALL_BUT_GATE),
    ("one layer's worth: one", 448 * MB, True, 4, ALL_BUT_GATE),
    ("a byte more: two", 448 * MB + 1, True, 3, ALL_BUT_GATE),
    ("more than it holds: whole, and no more", 9000 * MB, True, 0,
     ALL_BUT_GATE),
])
def test_what_does_not_fit_is_taken_back_from_the_last_entry(
        case, over, unrolled, want_gate, want_rest):
    order = remat_order(2)
    plan = [ALL_BUT_GATE + ("gate_up",)] * 5 + [ALL_BUT_GATE] * 3
    got = pretrain._take_back(plan, order, CELL, over, unrolled)
    assert got == [want_rest + ("gate_up",)] * want_gate \
        + [want_rest] * (8 - want_gate), case
    # entry by entry down to the floor
    plan = [ALL_BUT_GATE] * 4
    for want in ([FLASH + ("attn_out",)] * 4, [FLASH] * 4, [()] * 4):
        plan = pretrain._take_back(plan, order, CELL, None, unrolled)
        assert plan == want


def test_the_sequence_layout_halves_what_a_chip_keeps_of_the_row_product(
        monkeypatch):
    """`nbytes["attn_out"]` a chip under `mp` 2 is half of `mp` 1's (the
    residual is named AFTER the constraint: `[B, S/mp, H]`), and at the
    four-chip cell's shapes the memory that frees keeps the gate / up
    product in two more layers — arithmetic on the cell's numbers (PR 65,
    the step compiled for a described v5e 2x2), no device."""
    plans = {}
    for mp in (1, 2):
        _, _, meta, _ = _build(monkeypatch, None, mp=mp, sharding=2)
        plans[mp] = meta["remat_plan"]
    assert plans[2]["seq_sharded"] and not plans[1]["seq_sharded"]
    assert plans[2]["nbytes"]["attn_out"] * 2 \
        == plans[1]["nbytes"]["attn_out"]
    for name in ("flash_o", "flash_lse", "qkv", "gate_up"):
        assert plans[2]["nbytes"][name] * 2 == plans[1]["nbytes"][name]
    # the cell: 2 x 8192 rows a chip, hidden 4096, bf16, eight layers
    rows, item = 2 * 8192, 2
    cell = {"flash_o": rows * 16 * 128 * item, "flash_lse": rows * 16 * 4,
            "attn_out": rows * 4096 * item, "qkv": rows * 3072 * item,
            "gate_up": rows * 14336 * item}
    sharded = dict(cell, attn_out=cell["attn_out"] // 2)
    # the floor program's need fell by the sixteen [2, 8192, 4096]
    # tensors a chip no longer holds whole (eight checkpoint inputs; the
    # eight kept row products are the plan's own bytes) and more
    limit, margin = 16_909_334_528, pretrain.REMAT_MARGIN_BYTES
    parent = choose_remat_plan(cell, 8, limit - margin - 12_727_522_816,
                               True, remat_order(2))
    found = 8 * cell["attn_out"] // 2
    change = choose_remat_plan(sharded, 8,
                               limit - margin - 12_727_522_816 + found,
                               True, remat_order(2))
    gate = lambda plan: sum("gate_up" in kept for kept in plan)  # noqa: E731
    assert gate(parent) == 1
    assert gate(change) >= gate(parent) + 2


def test_a_name_is_an_identity_outside_a_checkpoint_that_keeps_it():
    x = jnp.arange(4.0)
    assert at.residual(x, "qkv") is x and not at.keeps("qkv")
    with at.keeping(("qkv",)):
        assert at.keeps("qkv") and not at.keeps("attn_out")
        assert at.residual(x, "attn_out") is x
        text = jax.make_jaxpr(lambda a: at.residual(a, "qkv"))(x)
        assert "name=qkv" in str(text)
    assert not at.keeps("qkv")


# ---------------------------------------------------------------- the step
needs_4 = pytest.mark.skipif(len(jax.devices()) < 4,
                             reason="needs 4 (virtual) devices")


def _build(monkeypatch, limit, plan=None, needs=None, **kw):
    """The toy step of test_op_scopes.py at widths the flash kernel
    takes (one 128-row block a head, interpreted here), float32 so that
    two programs differ by nothing but what they keep."""
    paddle.seed(5)
    mc = llama_tiny_config(num_hidden_layers=2, max_position_embeddings=128,
                           num_attention_heads=2, num_key_value_heads=2,
                           head_dim=64, fuse_attention_qkv=True,
                           fuse_attention_ffn=True, fuse_pack_groups=2)
    base = dict(global_batch=4, seq_len=128, sharding=2, mp=2, remat="full",
                scan_layers=False, ce_chunks=2, param_dtype="float32")
    base.update(kw)
    cfg = PretrainConfig(mc, **base)
    n = cfg.dp * cfg.mp * cfg.pp * cfg.sharding * cfg.sep
    mesh = make_hybrid_mesh_for(cfg, devices=jax.devices()[:n])
    # the test steers what the program reads from the device
    monkeypatch.setattr(flash_attention, "_tpu_flash_available",
                        lambda: True)
    monkeypatch.setattr(pretrain, "_bytes_limit", lambda mesh: limit)
    if plan is not None:
        monkeypatch.setattr(pretrain, "choose_remat_plan",
                            lambda *a, **k: [tuple(p) for p in plan])
    if needs is not None:
        it = iter(needs)
        monkeypatch.setattr(pretrain, "_program_need", lambda c: next(it))
    state, step, meta = build_llama_pretrain_step(cfg, mesh)
    ids = global_device_put(jnp.asarray(np.random.RandomState(0).randint(
        0, mc.vocab_size, (4, 128)), jnp.int32), meta["data_sharding"])
    return state, step, meta, ids


def _one_step(built):
    state, step, meta, ids = built
    state, m = step(state, ids, ids)
    table = at.op_scopes(meta["compiled_programs"](state))
    return {"loss": float(m["loss"]), "gnorm": float(m["grad_norm"]),
            "grad": jax.tree.map(np.asarray, state.opt_state.moment1),
            "master": jax.tree.map(np.asarray, state.master),
            "remat": {(r.scope, r.kind, r.opcode) for r in table.values()
                      if r.direction == "remat"},
            "plan": meta["remat_plan"]}


def _ledger_since(t0):
    """The set-up ledger's spans and program records from `t0` on."""
    from paddle_tpu.observability import tracing
    got = tracing.recorder().setup()
    return {"spans": [sp for sp in got["spans"] if sp["start_ns"] >= t0],
            "programs": [r for r in got["programs"]
                         if r["start_ns"] >= t0]}


@pytest.fixture(scope="module")
def floor_step():
    mp = pytest.MonkeyPatch()
    t0 = time.perf_counter_ns()
    built = _build(mp, None)
    ledger = _ledger_since(t0)      # the build's, before the first step
    out = dict(_one_step(built), ledger=ledger)
    mp.undo()
    return out


@needs_4
def test_the_build_is_sections_of_the_set_up_ledger(floor_step):
    # ISSUE 68: `trainer.build` over the builder, its sections disjoint
    # children in the builder's own order
    spans = floor_step["ledger"]["spans"]
    (whole,) = [sp for sp in spans if sp["name"] == "trainer.build"]
    assert whole["parent"] is None and whole["step"] is None
    kids = [sp for sp in spans if sp["parent"] == "trainer.build"]
    assert [sp["name"] for sp in kids] == [
        "trainer.build" + s for s in (".model", ".state", ".step", ".plan")]
    edges = [whole["start_ns"]] + [t for sp in kids for t in (
        sp["start_ns"], sp["end_ns"])] + [whole["end_ns"]]
    assert edges == sorted(edges)
    # what the build traced says which section it was under (the eager
    # float32 model is one-primitive programs: those this process has
    # not met before); no device reports a limit here, so the plan
    # compiled nothing and has no floor
    under = {r["span"] for r in floor_step["ledger"]["programs"]
             if whole["start_ns"] <= r["start_ns"] < whole["end_ns"]}
    assert under and under <= {sp["name"] for sp in kids}
    assert "trainer.build.plan" not in under
    assert not [sp for sp in spans if sp["parent"] == "trainer.build.plan"]


PARTIAL = [ALL_BUT_GATE + ("gate_up",), ALL_BUT_GATE]


@needs_4
@pytest.mark.parametrize("case", ["every name, every layer",
                                  "gate_up in the first layer only",
                                  "every name under scan"])
def test_a_step_that_keeps_equals_the_step_that_keeps_nothing(
        case, floor_step, monkeypatch):
    kw = {"every name, every layer": dict(limit=1 << 40),
          "gate_up in the first layer only": dict(limit=1 << 40,
                                                  plan=PARTIAL),
          "every name under scan": dict(limit=1 << 40, scan_layers=True)}
    got = _one_step(_build(monkeypatch, **kw[case]))
    want = floor_step
    assert want["plan"]["layers"] == [(), ()]
    layers = got["plan"]["layers"]
    assert _whole(layers, ALL_BUT_GATE) and "gate_up" in layers[0]
    # exact: a kept residual is the value the recomputation would have
    # produced (the tolerance of test_zero_placement.py, where unrolled
    # and scanned programs sum in different orders)
    np.testing.assert_allclose(got["loss"], want["loss"], rtol=1e-6)
    np.testing.assert_allclose(got["gnorm"], want["gnorm"], rtol=1e-5)
    for tree in ("grad", "master"):
        for a, b in zip(jax.tree.leaves(got[tree]),
                        jax.tree.leaves(want[tree])):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)
    if case != "every name under scan":
        # the floor recomputes the kernel, the three products and the
        # output projection's all-reduce over mp ...
        kernels = {(s, o) for s, k, o in want["remat"]
                   if o in ("while", "custom-call")}
        assert ("attention", "while") in kernels \
            or ("attention", "custom-call") in kernels, want["remat"]
        assert ("attn_out", "collective", "all-reduce") in want["remat"]
        for scope in ("qkv_proj", "attn_out", "ffn"):
            assert (scope, "compute", "dot") in want["remat"], scope
        # ... and a layer that kept them does none of it again
        assert not [r for r in got["remat"] if r[0] == "attention"
                    and r[2] in ("while", "custom-call", "dot")]
        assert ("attn_out", "collective", "all-reduce") not in got["remat"]
        for scope in ("qkv_proj", "attn_out"):
            assert (scope, "compute", "dot") not in got["remat"], scope
        # the gate / up product is computed again exactly where it was
        # not kept
        assert (("ffn", "compute", "dot") in got["remat"]) \
            == (case == "gate_up in the first layer only")


@needs_4
def test_the_plan_is_reported(monkeypatch):
    reg = obs.registry()
    t0 = time.perf_counter_ns()
    _, _, meta, _ = _build(monkeypatch, 1 << 40, plan=PARTIAL)
    # the set-up ledger (ISSUE 68): the floor program and the one try
    # are spans under `.plan`, each with the step program it compiled
    ledger = _ledger_since(t0)
    tries = [sp["name"] for sp in ledger["spans"]
             if sp["parent"] == "trainer.build.plan"]
    assert tries == ["trainer.build.plan.floor", "trainer.build.plan.try"]
    for name in tries:
        compiled = [r for r in ledger["programs"]
                    if r["span"] == name and r["cache"] is not None]
        assert [r["name"] for r in compiled] == ["jit(train_step)"], name
    plan = meta["remat_plan"]
    assert plan["layers"] == PARTIAL
    assert plan["limit"] == 1 << 40
    assert plan["margin"] == pretrain.REMAT_MARGIN_BYTES
    assert plan["headroom"] == plan["limit"] - plan["margin"] \
        - plan["floor_need"]
    assert plan["need"] >= plan["floor_need"] > 0
    # bytes a chip by shapes: 4 x 128 tokens over sharding 2, heads,
    # columns and (the sequence layout) the row product's rows over mp
    # 2, float32
    rows = 2 * 128
    per = {"flash_o": rows * 1 * 64 * 4, "flash_lse": rows * 1 * 4,
           "attn_out": rows * 128 * 4 // 2, "qkv": rows * (6 * 64 // 2) * 4,
           "gate_up": rows * (2 * 256 // 2) * 4}
    assert plan["saved_bytes"] == 2 * sum(per.values()) - per["gate_up"]
    assert plan["seq_sharded"] is True and plan["nbytes"] == per
    snap = reg.snapshot()
    assert snap["trainer.remat.saved_bytes"]["series"][0]["value"] \
        == plan["saved_bytes"]
    layers = {tuple(s["labels"].values())[0]: s["value"]
              for s in snap["trainer.remat.saved_layers"]["series"]}
    # every residual of the vocabulary, a routed family's at 0 here
    assert layers == {"flash_o": 2, "flash_lse": 2, "attn_out": 2,
                      "qkv": 2, "gate_up": 1, "moe_gate_up": 0}


@needs_4
def test_a_program_that_does_not_fit_is_cut_back_to_one_that_does(
        monkeypatch):
    """The chosen program's own need is held to the limit: the last
    taken entries go until it fits, and the floor always remains."""
    GB = 1 << 30
    # floor 10, then gate_up + all: 16 (no), all but gate_up: 15.5 (no),
    # flash + attn_out: 14 (fits under 16 - 1)
    _, _, meta, _ = _build(monkeypatch, 16 * GB,
                           needs=[10 * GB, 16 * GB, int(15.5 * GB), 14 * GB])
    plan = meta["remat_plan"]
    assert plan["layers"] == [FLASH + ("attn_out",)] * 2
    assert (plan["floor_need"], plan["need"]) == (10 * GB, 14 * GB)
    # nothing fits: today's program
    _, _, meta, _ = _build(monkeypatch, 16 * GB,
                           needs=[10 * GB] + [17 * GB] * 4)
    assert meta["remat_plan"]["layers"] == [(), ()]
    assert meta["remat_plan"]["need"] == 10 * GB


def _text(built):
    state, step, meta, ids = built
    return step.lower(state, ids, ids).as_text()


@needs_4
@pytest.mark.parametrize("case", ["no limit", "no room", "pp 2", "dots"])
def test_where_nothing_is_kept_the_program_is_todays(case, monkeypatch):
    """A device that reports no `bytes_limit` (tier-1's CPU, a described
    device), one with nothing free, and the configurations the plan does
    not reach build the program as if no residual had a name."""
    kw = {"no limit": dict(limit=None),
          "no room": dict(limit=1 << 40, needs=[1 << 40]),
          "pp 2": dict(limit=1 << 40, pp=2, mp=1, n_microbatches=2),
          "dots": dict(limit=1 << 40, remat="dots")}[case]
    built = _build(monkeypatch, **kw)
    assert built[2]["remat_plan"]["layers"] == [(), ()]
    assert built[2]["remat_plan"]["saved_bytes"] == 0
    got = _text(built)
    # today's: no limit read, and every name an identity whoever asks
    monkeypatch.setattr(at, "keeps", lambda name: False)
    from paddle_tpu.models import llama
    from paddle_tpu.ops import pallas_flash
    monkeypatch.setattr(llama, "_keeps", lambda name: False)
    monkeypatch.setattr(pallas_flash, "_keeps", lambda name: False)
    kw.pop("needs", None)
    want = _text(_build(monkeypatch, **dict(kw, limit=None)))
    assert got == want
