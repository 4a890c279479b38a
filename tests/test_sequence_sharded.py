"""The trainer's activations stay sequence-sharded over `mp` between a
row-parallel product and the next column-parallel one (ISSUE 65): the
layout is chosen from the mesh and the sequence length, by no switch;
the mathematics is the unsharded step's; where it does not engage (one
chip, `mp` 1, a length `mp` does not divide, the timetable pipeline
executor) the lowered program is the parent's, text for text."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.distributed import parallel_layers as pl
from paddle_tpu.distributed.mesh import (build_hybrid_mesh, global_device_put,
                                         mesh_context)
from paddle_tpu.models.llama import LlamaConfig, llama_tiny_config
from paddle_tpu.ops.on_mesh import kernel_mesh
from paddle_tpu.trainer.pretrain import (PretrainConfig,
                                         build_llama_pretrain_step,
                                         make_hybrid_mesh_for)


def _build(seed=5, layers=2, seq=16, sequence_parallel=None, **kw):
    paddle.seed(seed)
    flag = {} if sequence_parallel is None \
        else {"sequence_parallel": sequence_parallel}
    mc = llama_tiny_config(num_hidden_layers=layers,
                           max_position_embeddings=64,
                           fuse_attention_qkv=True, fuse_attention_ffn=True,
                           fuse_pack_groups=2, **flag)
    base = dict(global_batch=4, seq_len=seq, remat="full", scan_layers=False,
                ce_chunks=2)
    base.update(kw)
    cfg = PretrainConfig(mc, **base)
    n = cfg.dp * cfg.mp * cfg.pp * cfg.sharding * cfg.sep
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} (virtual) devices")
    mesh = make_hybrid_mesh_for(cfg, devices=jax.devices()[:n])
    state, step, meta = build_llama_pretrain_step(cfg, mesh)
    ids = global_device_put(jnp.asarray(np.random.RandomState(0).randint(
        0, mc.vocab_size, (4, seq)), jnp.int32), meta["data_sharding"])
    return state, step, meta, ids


def _one_step(**kw):
    state, step, meta, ids = _build(param_dtype="float32", **kw)
    state, m = step(state, ids, ids)
    return {"loss": float(m["loss"]), "gnorm": float(m["grad_norm"]),
            "master": jax.tree.map(np.asarray, state.master),
            "grad": jax.tree.map(np.asarray, state.opt_state.moment1),
            "engaged": meta["remat_plan"]["seq_sharded"]}


_unsharded = {}


def _mp1(kw):
    """The same step with `mp` at 1 (and so without the layout)."""
    kw = {k: v for k, v in kw.items()
          if k not in ("mp", "sequence_parallel")}
    key = tuple(sorted(kw.items()))
    if key not in _unsharded:
        _unsharded[key] = _one_step(**kw)
    return _unsharded[key]


#: the step under `mp` 2 against the same step with `mp` at 1: which
#: rows of an activation a chip holds changes nothing but the order of
#: the sums (float32 throughout).  The keyword the configuration still
#: accepts rides along in both of its values: it selects nothing.
EQUIVALENT = {
    "mp2": (dict(mp=2), True),
    "zero2-mp2": (dict(sharding=2, mp=2), True),
    "dp2-mp2": (dict(dp=2, mp=2), True),
    "mp2-scan": (dict(mp=2, scan_layers=True), True),
    "mp2-pp2-compiled": (dict(mp=2, pp=2, n_microbatches=2), True),
    "mp2-keyword-false": (dict(mp=2, sequence_parallel=False), True),
    "mp2-keyword-true": (dict(mp=2, sequence_parallel=True), True),
    # the identity paths: a length mp does not divide, a sep axis that
    # already splits the sequence, the timetable executor's branches
    "mp2-seq15": (dict(mp=2, seq=15), False),
    "mp2-sep2": (dict(mp=2, sep=2), False),
    "mp2-pp2-1f1b": (dict(mp=2, pp=2, n_microbatches=2,
                          pp_schedule="1F1B"), False)}


@pytest.mark.parametrize("case", sorted(EQUIVALENT))
def test_one_step_equals_the_step_without_mp(case):
    kw, engages = EQUIVALENT[case]
    got, want = _one_step(**kw), _mp1(kw)
    assert got["engaged"] is engages and want["engaged"] is False
    np.testing.assert_allclose(got["loss"], want["loss"], rtol=1e-6)
    np.testing.assert_allclose(got["gnorm"], want["gnorm"], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got["grad"]),
                    jax.tree.leaves(want["grad"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)
    # AdamW's first step moves every weight by lr x sign(gradient): a
    # gradient within rounding of zero may take either sign
    lr = 3e-4
    for a, b in zip(jax.tree.leaves(got["master"]),
                    jax.tree.leaves(want["master"])):
        off = np.abs(a - b) > 1e-6
        assert off.mean() < 1e-3 and np.abs(a - b).max() <= 2.01 * lr


#: sha256 of the lowered step's text where the layout does not engage
#: (toy widths, this file's `_build`), recorded at PR 65's PARENT
#: (5330949): those programs are the parent's letter for letter
LOWERED_AT_PARENT = {
    # (`mp 1` and `mp 2, length 15` are tests/test_zero_placement.py's)
    "zero2-mp1": (dict(sharding=2),
                  "9cb7fa58ea8d79169a9eaddd6084c758b741cb679f7d73ffafcd155095"
                  "6de42c"),
    "mp2-pp2-1f1b": (dict(mp=2, pp=2, n_microbatches=2, pp_schedule="1F1B"),
                     "a53cde1f3a46ed91bc326ed875664f5c5f66a71fcadb622b57ffdb3"
                     "b84dc3ce8")}


def _lowered(state, step, meta, ids):
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(
            meta["mesh"], a.sharding.spec if hasattr(a.sharding, "spec")
            else P())), state)
    return step.lower(shapes, ids, ids).as_text()


@pytest.mark.parametrize("case", sorted(LOWERED_AT_PARENT))
def test_where_it_does_not_engage_the_step_lowers_to_the_parents_text(case):
    kw, want = LOWERED_AT_PARENT[case]
    built = _build(**kw)
    assert built[2]["remat_plan"]["seq_sharded"] is False
    assert hashlib.sha256(_lowered(*built).encode()).hexdigest() == want


def test_the_keyword_selects_nothing():
    """`LlamaConfig.sequence_parallel` is accepted and inert: both of its
    values lower to one text, engaged (`mp` 2) or not (`mp` 1)."""
    assert LlamaConfig(sequence_parallel=False).sequence_parallel is False
    for kw in (dict(mp=2), dict()):
        texts = {flag: _lowered(*_build(sequence_parallel=flag, **kw))
                 for flag in (True, False)}
        assert texts[True] == texts[False]


# ------------------------------------------------------- who chooses it
@pytest.mark.parametrize("axes, seq, want", [
    (dict(mp_degree=2), 16, True),
    (dict(mp_degree=2), 15, False),
    (dict(mp_degree=1), 16, False),
    (dict(sharding_degree=2), 16, False),
    (dict(mp_degree=2, sharding_degree=2), 16, True),
    (dict(mp_degree=2, sep_degree=2), 16, False),
    (dict(mp_degree=4), 18, False),
    (dict(mp_degree=4), 20, True)])
def test_the_layout_is_read_from_the_mesh_and_the_length(axes, seq, want):
    n = int(np.prod(list(axes.values())))
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} (virtual) devices")
    mesh = build_hybrid_mesh(devices=jax.devices()[:n], **axes)
    assert pl.seq_sharded_on(mesh, seq) is want
    assert pl.seq_sharded_on(None, seq) is False
    # the executor's trace-time fact switches it off, and only there
    with pl.suppress_sequence_parallel_annotations():
        assert pl.seq_sharded_on(mesh, seq) is False
    assert pl.seq_sharded_on(mesh, seq) is want
    # the annotation is the identity wherever the layout does not engage,
    # on the step's mesh (the trainer names it) or the ambient one
    x = paddle.rand([2, seq, 8])
    for ctx in (kernel_mesh(mesh), mesh_context(mesh)):
        with ctx:
            out = pl.annotate_sequence_parallel(x)
            assert pl.seq_layout_engages(x) is want
            assert (out is x) is (not want)
            np.testing.assert_allclose(out.numpy(), x.numpy())
            if want:
                assert out._data.sharding.spec[1] == "mp"
            flat = paddle.rand([seq, 8])        # no [B, S, H]: never
            assert pl.annotate_sequence_parallel(flat) is flat
    assert pl.annotate_sequence_parallel(x) is x    # no mesh at all


def test_row_parallel_layers_scatter_where_the_layout_engages():
    """`RowParallelLinear` / `VocabParallelEmbedding` under an ambient
    mesh: `[B, S/mp, H]` where `mp` divides S, replicated as before where
    it does not; the values are the plain layers' either way."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 (virtual) devices")
    mesh = build_hybrid_mesh(mp_degree=2, devices=jax.devices()[:2])
    paddle.seed(3)
    with mesh_context(mesh):
        row = pl.RowParallelLinear(8, 6, has_bias=False)
        emb = pl.VocabParallelEmbedding(10, 6)
        for seq, spec in ((4, P(None, "mp")), (3, P())):
            x = paddle.rand([2, seq, 8])
            out = row(x)
            np.testing.assert_allclose(
                out.numpy(), x.numpy() @ row.weight.numpy(), rtol=1e-5)
            got = tuple(out._data.sharding.spec) + (None,) * 3
            assert got[:3] == (tuple(spec) + (None,) * 3)[:3], (seq, got)
            ids = paddle.to_tensor(np.arange(2 * seq).reshape(2, seq) % 10)
            e = emb(ids)
            np.testing.assert_allclose(
                e.numpy(), emb.weight.numpy()[ids.numpy()], rtol=1e-6)
            got = tuple(e._data.sharding.spec) + (None,) * 3
            assert got[:3] == (tuple(spec) + (None,) * 3)[:3], (seq, got)


# ------------------------------------------------ the compiled toy step
_INSTR = re.compile(r"= (\S+?)(?:\{[^}]*\})? ([\w\-]+)\(")


def _scoped(text, scope, opcode):
    """Result dims of the `opcode` instructions whose op_name passes
    through `scope`."""
    out = []
    for line in text.splitlines():
        m = _INSTR.search(line)
        name = re.search(r'op_name="([^"]*)"', line)
        if m and m.group(2) == opcode and name and scope in name.group(1):
            dims = re.search(r"\[([\d,]*)\]", m.group(1))
            out.append(tuple(int(d) for d in dims.group(1).split(",") if d))
    return out


def test_the_compiled_step_holds_its_rows_and_counts_them():
    """`sharding 2 x mp 2` at toy widths on the CPU's partitioner (which
    spells a reduce-scatter as an all-reduce and a slice; the TPU
    compiler's text is tests/test_tpu_aot_compile.py's): the norms and
    the residual adds run on S/mp rows, the gathers answer to the scope
    that uses them, and the step says that it engaged."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    state, step, meta, ids = _build(sharding=2, mp=2)
    state, _ = step(state, ids, ids)
    text = meta["compiled_programs"](state)["train_step"].as_text()
    B, S, H = 4 // 2, 16, 128
    # a norm's reduction over the hidden dim: [B, S/mp] a chip, never
    # [B, S], in the layers and in the head
    for scope in ("attn_norm", "ffn_norm", "head_loss"):
        rows = {d for d in _scoped(text, f"/{scope}/", "reduce")
                + _scoped(text, f"({scope})", "reduce") if len(d) >= 2
                and d[0] == B and d[-1] in (S, S // 2)}
        assert rows and all(d[-1] == S // 2 for d in rows), (scope, rows)
    # the gathers of [B, S/mp, H] -> [B, S, H] sit at the column products
    gathers = {sc: _scoped(text, f"/{sc}/", "all-gather")
               for sc in ("attn_norm", "ffn_norm", "qkv_proj", "ffn")}
    assert (B, S, H) in gathers["qkv_proj"] and (B, S, H) in gathers["ffn"]
    assert (B, S, H) not in gathers["attn_norm"] + gathers["ffn_norm"]
    plan = meta["remat_plan"]
    assert plan["seq_sharded"] is True
    item = 2                                    # bf16
    assert plan["nbytes"]["attn_out"] == B * S * H * item // 2
    assert plan["nbytes"]["qkv"] == B * S * (8 * 32 // 2) * item
    snap = obs.registry().snapshot()
    assert snap["trainer.mp.seq_sharded"]["series"][0]["value"] == 1
    _build()                                    # one chip: the gauge says 0
    snap = obs.registry().snapshot()
    assert snap["trainer.mp.seq_sharded"]["series"][0]["value"] == 0
