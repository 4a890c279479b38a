"""The program's names for the parts of its steps, read back from the
compiled program (ISSUE 37): `observability.attribution.scope`,
`op_scopes`, `compile_named`; `ServingEngine.compiled_programs()` and
the trainer's `meta["compiled_programs"]`.

Everything here compiles for the CPU: which instructions a fusion
holds differs on the chip, what a name resolves to does not."""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.observability import attribution as at
from paddle_tpu.serving import ServingEngine

from test_engine_programs import _laguna, _tiny


# ------------------------------------------------------------ toy program
def _layer(x, w1, w2):
    with at.scope("attn_norm"):
        h = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-5)
    with at.scope("ffn"):
        y = jnp.tanh(h @ w1) @ w2
    return x + y


def _loss(w1, w2, x, remat):
    f = jax.checkpoint(_layer) if remat else _layer
    for _ in range(2):
        x = f(x, w1, w2)
    with at.scope("head_loss"):
        return (x * x).sum()


def _toy_args():
    return (jnp.ones((128, 256)), jnp.ones((256, 128)), jnp.ones((64, 128)))


def _by(table):
    got = collections.defaultdict(set)
    for rec in table.values():
        got[rec.scope].add(rec.direction)
    return got


def test_forward_ops_take_their_scope():
    fn = jax.jit(lambda w1, w2, x: _layer(x, w1, w2))
    table = at.op_scopes(fn.lower(*_toy_args()).compile())
    got = _by(table)
    assert {"attn_norm", "ffn"} <= set(got)
    assert got["ffn"] == {"-"}          # no transformation: no direction
    dots = [r for r in table.values() if r.opcode == "dot"]
    assert len(dots) == 2 and {r.scope for r in dots} == {"ffn"}


@pytest.mark.parametrize("remat", [False, True], ids=["grad", "checkpoint"])
def test_backward_and_recomputed_ops_say_so(remat):
    fn = jax.jit(jax.grad(functools.partial(_loss, remat=remat),
                          argnums=(0, 1)))
    table = at.op_scopes(fn.lower(*_toy_args()).compile())
    got = _by(table)
    assert {"fwd", "bwd"} <= got["ffn"]
    assert ("remat" in got["ffn"]) == remat
    assert ("remat" in got["attn_norm"]) == remat
    assert "bwd" in got["head_loss"]
    # the weight gradients are backward matmuls of the ffn
    grads = [r for r in table.values()
             if r.opcode == "dot" and r.shape in ("f32[128,256]",
                                                  "f32[256,128]")]
    assert grads and {(r.scope, r.direction) for r in grads} \
        == {("ffn", "bwd")}


def test_a_fusion_over_two_scopes_keeps_both_names():
    def f(x):
        with at.scope("attn_norm"):
            a = x * 2.0
        with at.scope("ffn"):
            return jnp.tanh(a) + 1.0

    table = at.op_scopes(jax.jit(f).lower(jnp.ones((8, 128))).compile())
    fusions = [r for r in table.values() if r.opcode == "fusion"]
    assert len(fusions) == 1
    assert fusions[0].scope == "ffn"            # its root's
    assert fusions[0].scopes == ("attn_norm", "ffn")


def test_collectives_take_the_scope_that_needed_them():
    devs = np.array(jax.devices()[:4])
    if devs.size < 4:
        pytest.skip("needs 4 (virtual) devices")
    mesh = Mesh(devs, ("x",))

    def f(x, w):
        with at.scope("attn_out"):      # contraction over the sharded
            y = x @ w                   # axis: the partitioner's psum
        with at.scope("head"):
            z = jax.shard_map(lambda t: jax.lax.psum(t.sum(), "x"),
                              mesh=mesh, in_specs=P("x"), out_specs=P())(y)
        return y, z

    x = jax.ShapeDtypeStruct((16, 64), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, "x")))
    w = jax.ShapeDtypeStruct((64, 32), jnp.float32,
                             sharding=NamedSharding(mesh, P("x", None)))
    table = at.op_scopes(jax.jit(f).lower(x, w).compile())
    coll = {(r.scope, r.shape) for r in table.values()
            if r.kind == "collective"}
    assert ("attn_out", "f32[16,32]") in coll       # inserted by GSPMD
    assert ("head", "f32[]") in coll                # the explicit psum
    assert all(r.opcode.startswith("all-reduce") for r in table.values()
               if r.kind == "collective")


# ------------------------------------------------------- names and keys
def test_scope_refuses_a_name_outside_the_vocabulary():
    with pytest.raises(ValueError, match="step scopes"):
        at.scope("rope")
    assert set(at.SCOPE_ALIASES.values()) <= set(at.SCOPES)
    assert not set(at.SCOPE_ALIASES) & set(at.SCOPES)


@pytest.mark.parametrize("path,want", [
    ("jit(step)/attention/pallas_call", ("attention", "-")),
    ("jit(step)/attention/eva_attention/pallas_call", ("attention", "-")),
    ("jit(step)/mla_kv/cache_write/pallas_call", ("cache_write", "-")),
    ("jit(step)/mla_kv/dot_general", ("qkv_proj", "-")),
    ("jit(step)/cache_write/eva_pool/pallas_call", ("cache_write", "-")),
    ("jit(loss)/jvp(ffn)/dot_general", ("ffn", "fwd")),
    ("jit(loss)/transpose(jvp(jvp()))/checkpoint/ffn/transpose",
     ("ffn", "bwd")),
    ("jit(loss)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "attn_norm/mul", ("attn_norm", "remat")),
    # a state-space mixer's parts and the latent projections (PR 43)
    ("jit(step)/ssm_in_proj/dot_general", ("qkv_proj", "-")),
    ("jit(step)/ssm_conv/mul", ("cache_write", "-")),
    ("jit(step)/ssm_scan/pallas_call", ("attention", "-")),
    ("jit(step)/ssm_out/dot_general", ("attn_out", "-")),
    ("jit(step)/routed_ffn/latent_proj/dot_general", ("routed_ffn", "-")),
    # a KDA (delta-rule) mixer's parts (PR 47): two kernels, two names
    ("jit(step)/kda_in_proj/dot_general", ("qkv_proj", "-")),
    ("jit(step)/kda_conv/mul", ("cache_write", "-")),
    ("jit(step)/kda_state_update/pallas_call", ("attention", "-")),
    ("jit(step)/kda_chunk_scan/cond/dot_general", ("attention", "-")),
    ("jit(step)/kda_out/dot_general", ("attn_out", "-")),
    # a residual of several streams (PR 50): the mixing answers as the
    # norms do, under three names of its own
    ("jit(step)/mhc_pre/pallas_call", ("attn_norm", "-")),
    ("jit(step)/mhc_post/pallas_call", ("ffn_norm", "-")),
    ("jit(step)/mhc_merge/reduce_sum", ("ffn_norm", "-")),
    ("jit(update)/jit(head)/mul", (None, "-")),     # function names
    ("jit(step)/jit(main)/add", (None, "-")),
])
def test_a_path_gives_its_innermost_name_and_direction(path, want):
    assert at._path_scope(path) == want


@pytest.mark.parametrize("path,want", [
    ("jit(step)/ssm_scan/pallas_call", "ssm_scan"),
    ("jit(step)/kda_state_update/pallas_call", "kda_state_update"),
    ("jit(step)/mhc_pre/pallas_call", "mhc_pre"),
    ("jit(step)/mhc_post/pallas_call", "mhc_post"),
    ("jit(step)/mhc_merge/reduce_sum", "mhc_merge"),
    ("jit(step)/kda_chunk_scan/cond/dot_general", "kda_chunk_scan"),
    ("jit(step)/routed_ffn/latent_proj/dot_general", "latent_proj"),
    ("jit(step)/attention/pallas_call", "attention"),
    ("jit(step)/jit(main)/add", "")])
def test_a_path_keeps_the_name_the_program_wrote(path, want):
    """`OpScope.own`: an alias answers to the vocabulary AND stays
    readable, so a reader can tell the mixer's scan from attention."""
    assert at._path_own(path) == want


def test_the_key_is_the_same_from_the_text_and_from_the_trace():
    """The device trace prints operand types and tilings,
    `Compiled.as_text()` does not; the key is what both agree on."""
    text = ("  ROOT %convert_reduce_fusion.3 = (f32[]{:T(128)}, bf16[2048,"
            "4096]{1,0:T(8,128)(2,1)}) fusion(%param, %copy-done), "
            "kind=kOutput, calls=%fused_computation, metadata={op_name="
            "\"jit(step)/ffn/dot_general\"}")
    event = ("%convert_reduce_fusion.3 = (f32[]{:T(128)}, bf16[2048,4096]"
             "{1,0:T(8,128)(2,1)}) fusion(bf16[2048,4096]{1,0:T(8,128)(2,1)}"
             " %param, bf16[4096,4096]{1,0:T(8,128)(2,1)S(1)} %copy-done), "
             "kind=kOutput, calls=%fused_computation")
    assert at.op_key(text) == at.op_key(event) \
        == "%convert_reduce_fusion.3 f32[]"
    assert at.op_key("bench.engine.step") is None


HLO = """HloModule jit_step

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/ffn/mul"}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %g = f32[8]{0} get-tuple-element(%t), index=1
  %e = f32[8]{0} exponential(%g), metadata={op_name="jit(step)/attention/while/body/exp"}
  %i = s32[] get-tuple-element(%t), index=0
  ROOT %r = (s32[], f32[8]{0}) tuple(%i, %e)
}

ENTRY %main (w: f32[8], x: f32[8]) -> f32[8] {
  %w = f32[8]{0} parameter(0)
  %x = f32[8]{0} parameter(1)
  %copy.1 = f32[8]{0} copy(%w)
  %fusion.1 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/ffn/mul"}
  %init = (s32[], f32[8]{0}) tuple(%c, %fusion.1)
  %while.1 = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body
  %out = f32[8]{0} get-tuple-element(%while.1), index=1
  %ar = f32[8]{0} all-reduce(%out), to_apply=%sum, metadata={op_name="jit(step)/head/psum"}
  ROOT %neg = f32[8]{0} negate(%x)
}
"""


def test_the_table_of_a_program_by_hand():
    t = at.op_scopes({"toy": HLO})
    assert set(t) == {"%e f32[8]", "%copy.1 f32[8]", "%fusion.1 f32[8]",
                      "%while.1 s32[]", "%ar f32[8]", "%neg f32[8]"}
    # a copy of a weight has no name of its own: it answers to what
    # reads it, and says which parameter it copies
    assert t["%copy.1 f32[8]"][:3] == ("ffn", "-", "copy")
    assert t["%copy.1 f32[8]"].inherited and t["%copy.1 f32[8]"].reads == "w"
    assert not t["%fusion.1 f32[8]"].inherited
    # a loop's event spans its body's: the readers leave it out of sums
    assert t["%while.1 s32[]"].kind == "control"
    assert t["%e f32[8]"].scope == "attention"
    assert t["%ar f32[8]"][:3] == ("head", "-", "collective")
    assert t["%neg f32[8]"].scope is None       # said as unscoped
    assert {r.program for r in t.values()} == {"toy"}


def test_two_programs_that_disagree_on_a_key_keep_neither_name():
    other = HLO.replace("jit(step)/head/psum", "jit(step)/update/psum")
    t = at.op_scopes({"a": HLO, "b": other})
    assert t["%ar f32[8]"].scope is None
    assert t["%ar f32[8]"].program == "a+b"
    assert t["%fusion.1 f32[8]"].scope == "ffn"     # they agree here


class _Stub:
    """A lowered / compiled / jitted stand-in that hands out texts."""

    def __init__(self, lowered, compiled):
        self.texts, self.lowers = (lowered, compiled), 0

    def lower(self, *args):
        self.lowers += 1
        return self

    def compile(self):
        return self

    def as_text(self, debug_info=False):
        return self.texts[0] if debug_info else self.texts[1]


def test_names_the_compile_cache_lost_are_compiled_again():
    """The cache's key leaves names out: an entry written before the
    program named its parts answers the program that does."""
    lowered = ('loc("jit(step)/attn_norm/mul") loc("jit(step)/ffn/dot") '
               'loc("jit(step)/head/mul") loc("jit(step)/attention/exp")')
    stale = HLO.replace("ffn/", "").replace("head/", "")
    hit, again = _Stub(lowered, stale), _Stub(lowered, HLO)
    was = jax.config.jax_enable_compilation_cache
    assert at.compile_named(hit, (), lambda: again) is again
    assert (hit.lowers, again.lowers) == (1, 1)
    assert jax.config.jax_enable_compilation_cache == was
    # names found: the answer stands, nothing is compiled again
    fine = _Stub(lowered, HLO)
    assert at.compile_named(fine, (), lambda: again) is fine
    assert again.lowers == 1
    # a program that names nothing has nothing to lose
    plain = _Stub('loc("jit(feed)/select_n")', stale)
    assert at.compile_named(plain, (), lambda: again) is plain


# --------------------------------------------- the serving steps' cover
def _eva():
    from paddle_tpu.models.evabyte import (EvaByteForCausalLM,
                                           evabyte_tiny_config)
    paddle.seed(0)
    m = EvaByteForCausalLM(evabyte_tiny_config())
    m.eval()
    return m


def _ouro():
    from paddle_tpu.models.ouro import OuroForCausalLM, ouro_tiny_config
    paddle.seed(0)
    m = OuroForCausalLM(ouro_tiny_config(max_position_embeddings=64,
                                         rope_positions=64))
    m.eval()
    return m


def _nemotron():
    from paddle_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                              nemotron_h_tiny_config)
    paddle.seed(0)
    m = NemotronHForCausalLM(nemotron_h_tiny_config(
        max_position_embeddings=64))
    m.eval()
    return m


def _falcon():
    from test_falcon_h1 import seeded
    return seeded(max_position_embeddings=64)[0]


def _phi4flash():
    from test_phi4flash import seeded
    return seeded(max_position_embeddings=64)[0]


def _sdar():
    from test_sdar import seeded
    return seeded(max_position_embeddings=64)[0]


def _lfm2():
    from test_lfm2 import seeded
    return seeded(max_position_embeddings=64)[0]


#: family -> names its unified step must show
FAMILIES = {
    "llama": {"ffn"}, "moe": {"routed_ffn", "shared_expert"},
    "mla": {"routed_ffn"}, "gpt": {"ffn"},
    "laguna": {"routed_ffn", "shared_expert"}, "eva": {"ffn"},
    "looped": {"ffn", "loop_norm"},
    "hybrid": {"routed_ffn", "shared_expert"},
    # both mixers of a block on one norm, a dense FFN: every name of a
    # layer (the state-space parts answer through SCOPE_ALIASES)
    "hybrid_two_mixers": {"ffn"},
    # blocks that own no memory beside blocks that do, LayerNorm, window
    # pages: every name of a layer (through SCOPE_ALIASES)
    "hybrid_borrowed": {"ffn"},
    # generation by diffusion over blocks: the q / k norms and the
    # transfer rule under names of their own
    "block_diffusion": {"routed_ffn", "qk_norm", "unmask"},
    # a mixer whose memory is a tail only, the prefix cache on: its
    # parts and the snapshot's copy under names of their own (through
    # SCOPE_ALIASES), a dense and a routed FFN, the q / k norms
    "hybrid_tail_only": {"ffn", "routed_ffn", "qk_norm"},
}
EVERY_STEP = {"embed", "attn_norm", "qkv_proj", "cache_write", "attention",
              "attn_out", "ffn_norm", "head"}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_op_of_a_serving_step_answers_to_a_name(family):
    m = _laguna() if family == "laguna" else _eva() if family == "eva" \
        else _ouro() if family == "looped" \
        else _nemotron() if family == "hybrid" \
        else _falcon() if family == "hybrid_two_mixers" \
        else _phi4flash() if family == "hybrid_borrowed" \
        else _sdar() if family == "block_diffusion" \
        else _lfm2() if family == "hybrid_tail_only" else _tiny(family)
    kw = dict(max_slots=3, page_size=8, max_context=256, prefill_chunk=8,
              num_pages=64) if family == "eva" else \
        dict(max_slots=2, page_size=8, max_context=64, prefill_chunk=8)
    eng = ServingEngine(m, **kw)
    assert eng.ragged
    eng.add_request(np.arange(5, dtype=np.int32), max_new_tokens=3)
    eng.run_to_completion()
    launches, pools = eng.launches, eng._pools
    programs = eng.compiled_programs()
    # nothing was launched, no pool taken, no jit retraced
    assert eng.launches == launches and eng._pools is pools
    assert not any(p.is_deleted() for p in jax.tree.leaves(pools))
    assert set(programs) == {"unified", "feed", "unified_nochunk",
                             "feed_nochunk"}
    assert all(n == 1 for n in eng.program_cache_sizes().values())
    # the step at each of its row counts, lowered at its own shapes
    for name, rows in (
            ("unified", eng._launch_rows(kw["prefill_chunk"])),
            ("unified_nochunk", eng._launch_rows(0))):
        (_, tok, *_), _ = programs[name].args_info
        assert tok.shape == (rows,), (name, tok)
        step = list(at.op_scopes({name: programs[name]}).values())
        names = {r.scope for r in step}
        assert names - {None} <= set(at.SCOPES)
        assert EVERY_STEP | FAMILIES[family] <= names, (name, sorted(
            EVERY_STEP | FAMILIES[family] - names))
        scoped = sum(r.scope is not None for r in step)
        assert scoped >= 0.9 * len(step), (name, scoped, len(step), [
            r for r in step if r.scope is None][:10])
        if family == "hybrid_two_mixers":
            # the mixers' own names, and no arithmetic outside a name:
            # each multiplier is applied inside the scope of the
            # operation it scales
            assert {"ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_out"} \
                <= {r.own for r in step}
            # (what has none: the zero state and zero y of a launch
            # whose chunk is absent, the hybrid body's `chunk_state`)
            fills = {"f32[4,8,128]", "f32[8,4,8]"}
            assert not [r for r in step if r.scope is None
                        and r.kind == "compute" and r.shape not in fills]
        if family == "hybrid_borrowed":
            # every mixer's own names — a launch over borrowed pages
            # told from one over a block's own — and no arithmetic
            # outside a name (what has none: `chunk_state`'s fills)
            assert {"ssm1_in_proj", "ssm1_conv", "ssm1_scan", "ssm1_out",
                    "gmu", "attention", "shared_attention",
                    "diff_combine"} <= {r.own for r in step}
            fills = {"f32[1,16,128]", "f32[8,128]"}
            assert not [r for r in step if r.scope is None
                        and r.kind == "compute" and r.shape not in fills]
        if family == "hybrid_tail_only":
            # the snapshot is taken where there is a chunk, and only there
            own = {r.own for r in step}
            assert {"lfm_in_proj", "lfm_conv", "lfm_out"} <= own
            assert ("tail_snapshot" in own) == (name == "unified")
            assert not [r for r in step if r.scope is None
                        and r.kind == "compute"]
    # ... and read together, as a trace's reader does: a key the two
    # answer differently keeps neither scope, and few do
    table = at.op_scopes(programs)
    scoped = sum(r.scope is not None for r in table.values())
    assert scoped >= 0.9 * len(table), (scoped, len(table), [
        k for k, r in table.items() if r.scope is None][:10])
    # the engine still serves
    eng.add_request(np.arange(4, dtype=np.int32), max_new_tokens=2)
    assert len(eng.run_to_completion()) == 1


def test_every_op_of_the_trainer_step_answers_to_a_name():
    from paddle_tpu.distributed.mesh import global_device_put
    from paddle_tpu.models.llama import llama_tiny_config
    from paddle_tpu.trainer.pretrain import (PretrainConfig,
                                             build_llama_pretrain_step,
                                             make_hybrid_mesh_for)
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    paddle.seed(5)
    mc = llama_tiny_config(num_hidden_layers=2, max_position_embeddings=64,
                           fuse_attention_qkv=True, fuse_attention_ffn=True,
                           fuse_pack_groups=2)
    cfg = PretrainConfig(mc, global_batch=4, seq_len=16, sharding=2, mp=2,
                         remat="full", scan_layers=False, ce_chunks=2)
    mesh = make_hybrid_mesh_for(cfg, devices=jax.devices()[:4])
    state, step, meta = build_llama_pretrain_step(cfg, mesh)
    ids = global_device_put(jnp.asarray(np.random.RandomState(0).randint(
        0, mc.vocab_size, (4, 16)), jnp.int32), meta["data_sharding"])
    state, m = step(state, ids, ids)
    assert np.isfinite(float(m["loss"]))
    table = at.op_scopes(meta["compiled_programs"](state))
    got = _by(table)
    assert {"embed", "attn_norm", "qkv_proj", "attention", "attn_out",
            "ffn_norm", "ffn", "head_loss", "update"} <= set(got)
    for name in ("qkv_proj", "attention", "attn_out", "ffn"):
        assert got[name] == {"fwd", "remat", "bwd"}, (name, got[name])
    assert got["update"] == {"-"}
    scoped = sum(r.scope is not None for r in table.values())
    assert scoped >= 0.9 * len(table), (scoped, len(table))
    # the sequence layout (PR 65; mp 2 divides the 16 rows): a
    # row-parallel matmul's partial sums leave under the matmul's name,
    # forward and recomputed (a reduce-scatter; the CPU's partitioner
    # spells it as an all-reduce and a slice) ...
    coll = {(r.scope, r.direction) for r in table.values()
            if r.kind == "collective"}
    assert {("attn_out", "fwd"), ("attn_out", "remat"),
            ("ffn", "fwd")} <= coll
    act = "[2,16,128]"              # [B, S, H] a chip, whatever the type
    scattered = {(r.scope, r.direction) for r in table.values()
                 if r.kind == "collective" and r.opcode == "all-reduce"
                 and r.shape.endswith(act)}
    assert scattered == {("embed", "fwd"), ("attn_out", "fwd"),
                         ("attn_out", "remat"), ("ffn", "fwd"),
                         ("qkv_proj", "bwd"), ("ffn", "bwd")}, scattered
    # ... and the gathers of [B, S/mp, H] rows answer to the scope that
    # USES them — the column matmuls, whose weight gradients want them
    # again, and the row matmuls' backward — never to a norm, never to
    # no name
    weights = {(r.scope, r.direction) for r in table.values()
               if r.kind == "collective" and r.opcode == "all-gather"
               and r.scope not in ("head_loss", "update")
               and not r.shape.endswith(act)}
    rows = {(r.scope, r.direction) for r in table.values()
            if r.kind == "collective" and r.opcode == "all-gather"
            and r.shape.endswith(act)}
    assert rows == {("qkv_proj", "fwd"), ("qkv_proj", "remat"),
                    ("ffn", "fwd"), ("ffn", "remat"), ("ffn", "bwd"),
                    ("attn_out", "bwd"), ("embed", "bwd")}, rows
    # ZeRO's traffic: a layer's weights are gathered under the name of
    # the part that uses them, forward only (never recomputed, never in
    # the backward); a weight gradient's sum over the axis answers to
    # its matmul, backward; the update keeps the norm's scalars
    assert weights == {(s, "fwd") for s in (
        "embed", "attn_norm", "qkv_proj", "attn_out", "ffn_norm", "ffn")}, \
        weights
    assert {("qkv_proj", "bwd"), ("attn_out", "bwd"), ("ffn", "bwd")} <= coll
    assert {r.shape for r in table.values() if r.kind == "collective"
            and r.scope == "update"} == {"f32[]"}
    # the state the step was handed is still the caller's
    state, m = step(state, ids, ids)
    assert np.isfinite(float(m["loss"]))
