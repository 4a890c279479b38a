"""The join of the trace's seconds with the program's names
(``lib/scoped_ops.py``) and the clocks' offset (``lib/phase_idle.py``),
on hand-made events; and the key's form against the event names of the
small trace recorded on the chip (``lib/testdata/small.xplane.pb``)."""

import os
import types

import pytest

from benchmarks.lib import phase_idle, scoped_ops, trace as tr
from benchmarks.lib.trace import Event, Trace

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "lib", "testdata", "small.xplane.pb")

#: a step program as ``Compiled.as_text()`` prints it (operands bare)
PROGRAM = """HloModule jit_step

ENTRY %main (w: bf16[64,64], x: bf16[8,64]) -> bf16[8,64] {
  %w = bf16[64,64]{1,0} parameter(0)
  %x = bf16[8,64]{1,0} parameter(1)
  %copy.5 = bf16[64,64]{1,0:T(8,128)(2,1)} copy(%w)
  %fusion.1 = bf16[8,64]{1,0} fusion(%x, %copy.5), kind=kOutput, calls=%fc, metadata={op_name="jit(step)/qkv_proj/dot_general"}
  %attention.2 = bf16[8,64]{1,0} custom-call(%fusion.1, %kv_lengths), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/attention/pallas_call"}
  %fusion.3 = bf16[8,64]{1,0} fusion(%attention.2), kind=kLoop, calls=%fd, metadata={op_name="jit(step)/ffn/mul"}
  %while.4 = (s32[], bf16[8,64]{1,0}) while(%t), condition=%c, body=%b
  ROOT %fusion.9 = bf16[8,64]{1,0} fusion(%x), kind=kLoop, calls=%fe
}
"""


def _event(name, rest, start, end):
    """An event named as the device trace names it: the whole line,
    operand types and tilings printed."""
    return Event(f"%{name} = bf16[8,64]{{1,0:T(8,128)(2,1)}} {rest}",
                 start, end)


def _harness(programs=PROGRAM):
    # window 0..10, two steps of 5; a step runs: copy 0.25, qkv fusion
    # 1, attention 2, ffn fusion 1, an unnamed fusion 0.25 = 4.5 busy
    dev, host = [], [Event("bench.window", 0, 10)]
    for k in (0, 5):
        dev += [
            Event("%copy.5 = bf16[64,64]{1,0:T(8,128)(2,1)} copy(bf16[64,64]"
                  "{1,0} %w)", k, k + 0.25),
            _event("fusion.1", "fusion(bf16[8,64]{1,0} %x, bf16[64,64]{1,0} "
                   "%copy.5), kind=kOutput, calls=%fc", k + 0.25, k + 1.25),
            _event("attention.2", "custom-call(bf16[8,64]{1,0} %fusion.1, "
                   "s32[9]{0} %kv_lengths), custom_call_target=\"tpu_custom_"
                   "call\"", k + 1.25, k + 3.25),
            _event("fusion.3", "fusion(bf16[8,64]{1,0} %attention.2), "
                   "kind=kLoop, calls=%fd", k + 3.25, k + 4.25),
            _event("fusion.9", "fusion(bf16[8,64]{1,0} %x), kind=kLoop, "
                   "calls=%fe", k + 4.25, k + 4.5)]
        host.append(Event("bench.engine.step", k, k + 5))
    red = tr.reduce_trace(Trace({"/device:TPU:0": dev}, host))
    engine = types.SimpleNamespace(
        compiled_programs=lambda: {"unified": programs})
    return types.SimpleNamespace(
        reduced=red, counters={"system": types.SimpleNamespace(engine=engine)})


def test_the_join_by_the_programs_names():
    h = _harness()
    ms = {p: scoped_ops.serve_ms(h, p) for p in scoped_ops.SERVE_PARTS}
    # device-busy inside a step span: 4.5 s -> "ms a step" 4500
    assert ms["attention"] == pytest.approx(2000)
    assert ms["proj"] == pytest.approx(1250)    # the fusion + ITS copy
    assert ms["ffn"] == pytest.approx(1000)
    assert ms["cache_write"] == ms["head"] == 0
    assert scoped_ops.serve_scoped_pct(h) == pytest.approx(
        100 * 4.25 / 4.5)
    j = scoped_ops.joined(h)
    assert sum(j.by_scope.values()) == pytest.approx(j.total_s) \
        == pytest.approx(9.0)
    assert j.by_scope[scoped_ops.UNSCOPED] == pytest.approx(0.5)
    copy = next(r for r in j.rows if r.rec.kind == "copy")
    assert copy.rec.scope == "qkv_proj" and copy.rec.inherited
    assert copy.rec.reads == "w" and "of %w" in scoped_ops.short(copy)
    # the five and the said remainder close against the step's time
    assert sum(ms.values()) + 250 == pytest.approx(4500)


def test_a_loop_is_not_counted_beside_its_body():
    h = _harness()
    h.reduced.op_seconds["%while.4 = (s32[], bf16[8,64]{1,0}) while((s32[],"
                         " bf16[8,64]{1,0}) %t), condition=%c, body=%b"] = 4.0
    j = scoped_ops.joined(h)
    assert j.total_s == pytest.approx(9.0)


def test_an_event_the_table_lacks_is_said_not_dropped():
    h = _harness()
    h.reduced.op_seconds["%fusion.77 = bf16[8,64]{1,0} fusion(bf16[8,64]"
                         "{1,0} %p), kind=kLoop, calls=%zz"] = 1.0
    j = scoped_ops.joined(h)
    assert j.by_scope[scoped_ops.UNKNOWN] == pytest.approx(1.0)
    assert scoped_ops.serve_scoped_pct(h) == pytest.approx(
        100 * 8.5 / 10.0)


@pytest.mark.parametrize("system", [
    types.SimpleNamespace(engine=types.SimpleNamespace()),  # a parent's
    types.SimpleNamespace(meta={}),                         # a parent's
    None])
def test_without_a_table_nothing_is_reported(system):
    h = _harness()
    h.counters["system"] = system
    assert scoped_ops.table(h) is None
    assert scoped_ops.serve_ms(h, "attention") is None
    assert scoped_ops.serve_scoped_pct(h) is None
    assert scoped_ops.train_ms(h, "attn") is None
    assert scoped_ops.exposed_ms(h, "update") is None


def test_without_a_trace_the_programs_are_not_even_asked():
    asked = []
    engine = types.SimpleNamespace(
        compiled_programs=lambda: asked.append(1) or {})
    h = types.SimpleNamespace(reduced=None, counters={
        "system": types.SimpleNamespace(engine=engine)})
    assert scoped_ops.serve_ms(h, "ffn") is None and not asked


# ---------------------------------------------------------------- training
TRAIN = """HloModule jit_train_step

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%a, metadata={op_name="jit(train_step)/jvp(ffn)/mul"}
  %all-reduce.1 = f32[8]{0} all-reduce(%fusion.1), to_apply=%s, metadata={op_name="jit(train_step)/jvp(ffn)/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%all-reduce.1), kind=kLoop, calls=%b, metadata={op_name="jit(train_step)/jvp(attn_norm)/mul"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%c, metadata={op_name="jit(train_step)/transpose(jvp())/checkpoint/rematted_computation/ffn/mul"}
  %all-reduce.2 = f32[8]{0} all-reduce(%fusion.3), to_apply=%s, metadata={op_name="jit(train_step)/transpose(jvp(ffn))/dot_general"}
  %reduce-scatter.1 = f32[4]{0} reduce-scatter(%all-reduce.2), to_apply=%s, metadata={op_name="jit(train_step)/update/add"}
  ROOT %fusion.4 = f32[8]{0} fusion(%reduce-scatter.1), kind=kLoop, calls=%d, metadata={op_name="jit(train_step)/update/mul"}
}
"""


def test_training_parts_and_the_exposed_collectives_split():
    f32 = "f32[8]{0}"
    dev = [Event(f"%fusion.1 = {f32} fusion({f32} %p), kind=kLoop", 0, 2),
           Event(f"%all-reduce.1 = {f32} all-reduce({f32} %fusion.1)", 2, 3),
           # compute that READS a collective: the accepted metric's
           # pattern counts it as one
           Event(f"%fusion.2 = {f32} fusion({f32} %all-reduce.1)", 3, 4),
           Event(f"%fusion.3 = {f32} fusion({f32} %fusion.2)", 4, 6),
           Event(f"%all-reduce.2 = {f32} all-reduce({f32} %fusion.3)", 6, 7.5),
           Event("%reduce-scatter.1 = f32[4]{0} reduce-scatter(f32[8]{0} "
                 "%all-reduce.2)", 7.5, 8),
           Event(f"%fusion.4 = {f32} fusion(f32[4]{{0}} %reduce-scatter.1)",
                 8, 9)]
    host = [Event("bench.window", 0, 10), Event("bench.train_step", 0, 10)]
    red = tr.reduce_trace(Trace({"/device:TPU:0": dev}, host))
    system = types.SimpleNamespace(
        state="the state", meta={"compiled_programs": lambda state: {
            "train_step": TRAIN} if state == "the state" else None})
    h = types.SimpleNamespace(reduced=red, counters={"system": system})
    assert scoped_ops.train_ms(h, "mlp") == pytest.approx(6500)
    assert scoped_ops.train_ms(h, "update") == pytest.approx(1500)
    assert scoped_ops.train_ms(h, "attn") == 0
    assert scoped_ops.train_pct(h, "remat_pct") == pytest.approx(100 * 2 / 9)
    assert scoped_ops.train_pct(h, "scoped_pct") == pytest.approx(100)
    # the accepted metric: everything whose LINE names a collective
    assert red.collective_exposed_s == pytest.approx(1 + 1 + 1.5 + 0.5 + 1)
    got = {g: scoped_ops.exposed_ms(h, g)
           for g in ("layers_fwd", "layers_bwd", "update")}
    assert got == {"layers_fwd": pytest.approx(2000),
                   "layers_bwd": pytest.approx(1500),
                   "update": pytest.approx(1500)}
    assert sum(got.values()) == pytest.approx(
        1e3 * red.collective_exposed_s)


# ------------------------------------------------------------ the clocks
def _records(offset_ns, jitter_ns=()):
    """Two step records on a clock ``offset_ns`` behind the trace's."""
    out = []
    for k, base in enumerate((1_000_000_000, 6_000_000_000)):
        j = jitter_ns[k] if jitter_ns else 0
        a = base - offset_ns + j
        names = ("admit", "build", "launch", "sync", "sample", "account")
        cuts = [0, 100, 300, 1000, 3900, 3950, 4000]    # ms inside a step
        out.append({"seq": k, "start_ns": a, "end_ns": a + 4_000_000_000,
                    "phases": [("serving.engine." + n,
                                a + cuts[i] * 1_000_000,
                                a + cuts[i + 1] * 1_000_000)
                               for i, n in enumerate(names)]})
    return out


def test_the_offset_between_the_clocks():
    spans = [Event("bench.engine.step", 1.0 - 1e-5, 5.0 + 1e-5),
             Event("bench.engine.step", 6.0 - 1e-5, 10.0 + 1e-5)]
    off = phase_idle.clock_offset(spans, _records(250_000_000))
    assert off["offset_s"] == pytest.approx(0.25, abs=1e-9)
    assert off["spread_s"] < 1e-9 and off["pairs"] == 2
    far = phase_idle.clock_offset(
        spans, _records(250_000_000, jitter_ns=(0, 400_000)))
    assert far["spread_s"] > phase_idle.MAX_OFFSET_SPREAD_S
    assert phase_idle.clock_offset(spans[:1], _records(0)) is None


def test_idle_by_the_programs_phase():
    # steps at 1-5 and 6-10 on the trace's clock; the device runs
    # 1.05-2.5 and 3-4.9 in the first, 6.2-9.9 in the second
    busy = [(1.05, 2.5), (3.0, 4.9), (6.2, 9.9)]
    by = phase_idle.idle_by_phase(busy, 0.0, 10.0, _records(250_000_000),
                                  0.25)
    # step 1: admit 1-1.1 (idle 0.05), sync 2-4.9 (idle 2.5-3 = 0.5),
    # sample/account 4.9-5 idle 0.1; step 2: admit + build 6-6.3 idle
    # 0.2, account 9.95-10 ... sample 9.9-9.95
    assert by["admit"] == pytest.approx(0.05 + 0.1)
    assert by["build"] == pytest.approx(0.1)
    assert by["sync"] == pytest.approx(0.5)
    assert by["sample"] == pytest.approx(0.05 + 0.05)
    assert by["account"] == pytest.approx(0.05 + 0.05)
    assert by["(between calls)"] == pytest.approx(1.0 + 1.0)
    assert sum(by.values()) == pytest.approx(
        tr.measure(tr.gaps(busy, 0.0, 10.0)))


# ------------------------------------------------- the recorded trace
def test_every_recorded_event_has_a_key():
    from paddle_tpu.observability.attribution import op_key
    trace = tr.load_xplane(DATA)
    names = {e.name for evs in trace.device_ops.values() for e in evs}
    assert names
    keys = {op_key(n) for n in names}
    assert None not in keys
    assert keys == {"%copy-start bf16[4096,4096]",
                    "%copy-done bf16[4096,4096]",
                    "%convert_reduce_fusion f32[]", "%all-reduce f32[]"}


def test_the_recorded_events_are_found_in_the_program_compiled_here():
    """``tools/record_small_trace.py``'s step, compiled for four
    DESCRIBED chips: the keys of its instructions are the keys of the
    events the chips recorded."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.observability.attribution import op_key, op_scopes, scope
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mesh = Mesh(topo.devices, ("x",))
    x = jax.ShapeDtypeStruct((4 * 2048, 4096), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("x", None)))
    w = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))

    def step(x, w):
        with scope("ffn"):
            y = jnp.tanh(x @ w)
        with scope("head"):
            return y, y.astype(jnp.float32).sum()

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        table = op_scopes(jax.jit(step).lower(x, w).compile())
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    trace = tr.load_xplane(DATA)
    keys = {op_key(e.name) for evs in trace.device_ops.values() for e in evs}
    assert keys <= set(table)
    assert table["%all-reduce f32[]"][:3] == ("head", "-", "collective")
    # the weight's prefetch has no name of its own: the matmul's
    assert table["%copy-done bf16[4096,4096]"].scope == "ffn"
    assert table["%convert_reduce_fusion f32[]"].scopes == ("ffn", "head")
