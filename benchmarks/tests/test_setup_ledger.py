"""The readers of the program's set-up ledger (``lib/setup_ledger.py``
and the ``layer_metrics`` files over it, ISSUE 68): on two ledgers
recorded from rehearsals (``lib/testdata/setup_ledger_{serve,train}.json``:
``recorder().setup()`` as a run left it — the serving cell's second run in
its checkout, the training cell's first —, the 48 longest records kept,
the totals whole), on hand-made ones, and one serving and one training cell's
``--rehearse --trace 1`` line."""

import json
import os
import types

import pytest

from benchmarks.lib import setup_ledger as sl
from benchmarks.tests.test_program_spans import reader
from benchmarks.tests.test_rehearsal import (BENCHMARK, check_line,
                                             last_json, run_cell)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1_000_000      # ns
SERVE = ["setup_ms.engine_construct", "setup_ms.first_launches"]
TRAIN = ["setup_ms.trainer_build", "setup_ms.trainer_build.model"]
BOTH = ["setup_ms.trace_lower", "setup_ms.compile",
        "setup_cache_miss_programs", "setup_named_pct"]
NEW = SERVE + TRAIN + BOTH


def recorded(kind):
    with open(os.path.join(BENCH, "lib", "testdata",
                           f"setup_ledger_{kind}.json")) as f:
        return json.load(f)


def harness(led):
    """A run whose ledger was read already (`setup_ledger.ledger` keeps
    it with the run)."""
    return types.SimpleNamespace(counters={"setup_ledger": led})


def of(kind):
    d = recorded(kind)
    return harness(sl.build(d["recorded"], d["start_ns"], d["window_ns"]))


def program(name, start_ms, trace=0, lower=0, backend=0, cache=None,
            read=0, span=None, step=None):
    return {"name": name, "start_ns": start_ms * MS,
            "end_ns": (start_ms + trace + lower + backend) * MS,
            "trace_ns": trace * MS, "lower_ns": lower * MS,
            "compile_ns": backend * MS, "cache": cache,
            "cache_read_ns": read * MS, "span": span, "step": step}


def span(name, a_ms, b_ms, parent=None, step=None, **more):
    return dict(name=name, start_ns=a_ms * MS, end_ns=b_ms * MS,
                parent=parent, step=step, **more)


def totals_of(programs):
    out = {}
    for r in programs:
        t = out.setdefault(r["span"] or "", dict.fromkeys(
            ("programs", "hits", "misses", "trace_ns", "lower_ns",
             "compile_ns", "cache_read_ns"), 0))
        t["programs"] += 1
        for k in ("trace_ns", "lower_ns", "compile_ns", "cache_read_ns"):
            t[k] += r[k]
        if r["cache"] in ("hit", "miss"):
            t["hits" if r["cache"] == "hit" else "misses"] += 1
    return out


@pytest.fixture
def hand_made():
    """Process start at 0; the import 100..1100; an engine built
    2000..3000 whose feed compiles (a miss) inside it; a warm-up step
    4000..5500 holding the step program's first launch (a hit); a
    reference jit 5200..6200 that overlaps it; the window from 7000; a
    reader's own lowering after it."""
    c = "serving.engine.construct"
    programs = [
        program("jit(feed)", 2500, 10, 20, 300, "miss", span=c + ".programs"),
        program("kernel", 4100, 50, span="serving.engine.launch", step=3),
        program("jit(step)", 4050, 400, 300, 100, "hit", 80,
                "serving.engine.launch", 3),
        program("jit(reference)", 5200, 100, 100, 800, "hit", 700),
        program("jit(step)", 9000, 400, 300, 100, "hit", 80,
                "serving.engine.compiled_programs"),
        program("jit(stray)", 9600, 1, 1, 5, "miss")]
    spans = [
        span("paddle_tpu.import", 100, 1100),
        span(c, 2000, 3000),
        span(c + ".weights", 2000, 2400, c),
        span(c + ".programs", 2400, 3000, c),
        span("serving.engine.step", 4000, 5500, step=3,
             phases=[["serving.engine.launch", 4040, 5000]]),
        span("serving.engine.compiled_programs", 8990, 9900)]
    rec = {"spans": spans, "programs": programs,
           "totals": totals_of(programs)}
    return harness(sl.build(rec, 0, 7000 * MS))


def test_the_union_counts_overlapping_intervals_once():
    assert sl.union_ns([]) == 0
    assert sl.union_ns([(0, 10), (5, 12), (20, 30), (22, 25), (30, 31)]) \
        == 12 + 11
    assert sl.union_ns([(5, 6), (0, 10)]) == 10


def test_the_window_is_the_last_quiet_stretch():
    busy = [(0, 10), (12, 20), (80, 90), (91, 95)]
    assert sl.last_quiet_stretch(busy, 200, 50) == 95   # after it all
    assert sl.last_quiet_stretch(busy, 120, 50) == 20   # the window
    assert sl.last_quiet_stretch(busy, 120, 70) is None


def test_the_hand_made_ledger(hand_made, capsys):
    h = hand_made
    led = h.counters["setup_ledger"]
    # the window and the reader's own lowering are not set-up
    assert led.end_ns == 6200 * MS and len(led.programs) == 4
    assert reader("setup_ms.engine_construct").read(h) == 1000.0
    assert ".weights 400.0, .programs 600.0" in capsys.readouterr().out
    assert reader("setup_ms.first_launches").read(h) == 1500.0
    said = capsys.readouterr().out
    assert "step 3 1500.0 ms (jit(step): trace 400.0 + lower 300.0 + " \
        "backend 100.0 [hit], 2 records)" in said
    # every record's own time, once: 10+20 + 50 + 400+300 + 100+100
    assert reader("setup_ms.trace_lower").read(h) == 980.0
    assert "jit(reference)" in capsys.readouterr().out
    assert reader("setup_ms.compile").read(h) == 300.0 + 100.0 + 800.0
    assert reader("setup_cache_miss_programs").read(h) == 1
    # import 1000 + construct 1000 + the step and the reference that
    # overlaps it 4000..6200, of 6200
    pct = reader("setup_named_pct").read(h)
    assert pct == pytest.approx(100.0 * 4200 / 6200)
    said = capsys.readouterr().out
    assert "the remainder 2.000 s" in said and "0.100 s before" in said
    # a training cell's readers find no span of theirs here
    for name in TRAIN:
        assert reader(name).read(h) is None


@pytest.mark.parametrize("kind,mine,others", [("serve", SERVE, TRAIN),
                                              ("train", TRAIN, SERVE)])
def test_every_reader_on_a_recorded_ledger(kind, mine, others, capsys):
    h = of(kind)
    led = h.counters["setup_ledger"]
    got = {name: reader(name).read(h) for name in NEW}
    said = capsys.readouterr().out
    for name in mine + BOTH:
        assert got[name] is not None and got[name] >= 0, name
    for name in others:
        assert got[name] is None, name
    whole = "serving.engine.construct" if kind == "serve" \
        else "trainer.build"
    (sp,) = sl.spans_named(led, whole)
    assert got[mine[0]] == (sp["end_ns"] - sp["start_ns"]) / 1e6
    kids = [k for k in led.spans if k["parent"] == whole]
    assert sum(k["end_ns"] - k["start_ns"] for k in kids) \
        >= 0.95 * (sp["end_ns"] - sp["start_ns"])
    # the serving ledger is a second run's in its checkout (the cache
    # answered everything), the training one a first run's
    assert got["setup_cache_miss_programs"] == led.totals["misses"]
    assert (led.totals["misses"] == 0) == (kind == "serve")
    assert led.totals["hits"] > 0
    assert got["setup_ms.compile"] == led.totals["compile_ns"] / 1e6
    assert 0 < got["setup_named_pct"] <= 100
    assert "the remainder" in said and "not kept one by one" in said
    if kind == "serve":
        assert got["setup_ms.first_launches"] > 0
        assert "jit(step)" in said
    else:
        assert 0 < got["setup_ms.trainer_build.model"] \
            <= got["setup_ms.trainer_build"]
        assert ".model" in said and ".plan" in said


def test_a_program_without_the_ledger_reads_nothing(monkeypatch):
    from paddle_tpu.observability import tracing
    parent = types.SimpleNamespace(steps=lambda: [])    # no `setup`
    monkeypatch.setattr(tracing, "recorder", lambda: parent)
    h = types.SimpleNamespace(counters={}, t_start=0.0)
    for name in NEW:
        assert reader(name).read(h) is None
    # ... nor one run with the flag off
    off = types.SimpleNamespace(
        setup=lambda: {"spans": [], "programs": [], "totals": {}})
    monkeypatch.setattr(tracing, "recorder", lambda: off)
    h = types.SimpleNamespace(counters={}, t_start=0.0)
    for name in NEW:
        assert reader(name).read(h) is None


def test_the_entries_and_their_files():
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    setup = next(m for m in BENCHMARK["end_to_end"]
                 if m["name"] == "setup_s")
    assert "workloads" not in setup         # every cell reports it
    entries = {m["name"]: m for m in BENCHMARK["per_layer"]
               if m["moves"] == "setup_s"}
    assert sorted(entries) == sorted(NEW)
    for name, m in entries.items():
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name
        assert set(m.get("workloads", cells)) <= cells
        assert m["source"] == ("program_counter" if "programs" in name
                               else "program_span")
    serve = {w["name"] for w in BENCHMARK["workloads"]
             if "serve" in w["traffic"] or "-serve-" in w["name"]}
    for name in SERVE:
        assert set(entries[name]["workloads"]) == serve
    for name in TRAIN:
        assert set(entries[name]["workloads"]) == cells - serve
    for name in BOTH:
        assert "workloads" not in entries[name]


@pytest.mark.parametrize("cell,mine", [
    ("mistral7b-serve-decode-steady", SERVE),
    ("mellum2-train-ep4share-8k", TRAIN)])
def test_a_rehearsal_prints_the_new_names(cell, mine):
    proc = run_cell(cell, "--trace", "1", "--rehearse")
    line = last_json(proc)
    check_line(line, cell, trace=True)
    got = line["metrics"]
    for name in mine + BOTH:
        assert name in got, name
        if name == "setup_cache_miss_programs":     # a count: its value
            assert isinstance(got[name]["value"], int)
        else:
            assert got[name]["value"] is None
    assert "set-up named:" in proc.stdout
