"""The readers of the program's own step records and request stamps
(``lib/program_spans.py`` and the ``layer_metrics`` files over it) on
hand-made records, and one serving cell's ``--rehearse --trace 1`` line:
counters with values, every span-timed metric null."""

import importlib.util
import json
import os
import types

import pytest

from benchmarks.lib import program_spans as ps
from benchmarks.tests.test_rehearsal import (BENCHMARK, check_line,
                                             last_json, run_cell)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1_000_000      # ns

NEW = ["engine_phase_ms." + p for p in ps.PHASES] + [
    "engine_step_max_ms", "queue_wait_p95_ms.engine",
    "prefill_wait_p95_ms.engine", "prefill_run_p95_ms.engine",
    "engine_rows_per_step.decode", "engine_rows_per_step.prefill",
    "ragged_live_page_share", "kv_pool_used_pct",
    "engine_compiles_in_window"]


def reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "lm_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def record(seq, start_ms, phases_ms, **counts):
    """One step record: phases back to back from ``start_ms``."""
    t = start_ms * MS
    phases = []
    for name, ms in zip(ps.PHASES, phases_ms):
        phases.append((ps.SPAN_PREFIX + name, t, t + int(ms * MS)))
        t += int(ms * MS)
    rec = {"seq": seq, "name": "serving.engine.step", "replica": None,
           "start_ns": start_ms * MS, "end_ns": t, "phases": phases,
           "compiles": 0, "decode_rows": 0, "prefill_rows": 0, "live": 0,
           "waiting": 0, "admitted": 0, "finished": 0, "preempted": 0,
           "cow_pages": 0, "pages_live": 0, "pages_visited": 0,
           "pool_pages_used": 0, "pool_pages_total": 10}
    rec.update(counts)
    return rec


def seen(t, prefill=0, decoded=0, traced=False):
    return {"t": t, "prefill": prefill, "decoded": decoded, "seqs": [],
            "live_kv": 0, "live": 0, "traced": traced}


def harness(steps, n_in, **counters):
    return types.SimpleNamespace(
        counters=dict(steps=steps, steps_in_window=n_in, **counters),
        reduced=None)


@pytest.fixture
def hand_made(monkeypatch):
    """Two warm-up records, then a window of four steps (the third under
    the profiler) and one drain step after it."""
    records = [
        record(1, 0, [9, 9, 9, 9, 9, 9], prefill_rows=7),
        record(2, 100, [9, 9, 9, 9, 9, 9], decode_rows=1),
        record(3, 1000, [0.1, 2.0, 1.0, 80.0, 0.5, 0.4], prefill_rows=256,
               decode_rows=2, pages_live=10, pages_visited=100,
               pool_pages_used=2),
        record(4, 1100, [0.3, 4.0, 1.0, 90.0, 0.5, 0.4], decode_rows=4,
               pages_live=30, pages_visited=100, pool_pages_used=4),
        record(5, 1200, [0.2, 3.0, 5.0, 95.0, 1.5, 0.4], decode_rows=4,
               pages_live=20, pages_visited=100, pool_pages_used=6,
               compiles=2),
        record(6, 1300, [0.2, 3.0, 1.0, 900.0, 0.5, 0.7], decode_rows=6,
               pages_live=20, pages_visited=100, pool_pages_used=8),
        record(7, 5000, [50, 50, 50, 50, 50, 50], decode_rows=1,
               pages_live=1, pages_visited=100, compiles=5),
    ]
    steps = [seen(0.1, prefill=256, decoded=2), seen(0.2, decoded=4),
             seen(0.3, decoded=4, traced=True), seen(0.4, decoded=6),
             seen(9.9, decoded=1)]
    monkeypatch.setattr(ps, "_records", lambda: records)
    return harness(steps, 4)


def test_window_is_taken_from_the_end(hand_made):
    w = ps.window(hand_made)
    assert [r["seq"] for _, r in w.steps] == [3, 4, 5, 6, 7]
    assert [r["seq"] for _, r in ps.in_window(w)] == [3, 4, 5, 6]
    assert w.cut_ns == 100 * MS + 54 * MS       # the warm-up's last end


def test_phase_means_leave_out_traced_steps_and_the_drain(hand_made):
    got = {p: reader("engine_phase_ms." + p).read(hand_made)
           for p in ps.PHASES}
    assert got["admit"] == pytest.approx((0.1 + 0.3 + 0.2) / 3)
    assert got["build"] == pytest.approx(3.0)
    assert got["launch"] == pytest.approx(1.0)      # the traced 5.0 is out
    assert got["sync"] == pytest.approx((80 + 90 + 900) / 3)
    assert got["account"] == pytest.approx(0.5)


def test_longest_step_and_counts(hand_made, capsys):
    assert reader("engine_step_max_ms").read(hand_made) == \
        pytest.approx(0.2 + 3 + 1 + 900 + 0.5 + 0.7)
    said = capsys.readouterr().out
    assert "'seq': 6" in said and "'sync': 900.0" in said
    assert reader("engine_rows_per_step.decode").read(hand_made) == 4.0
    assert reader("engine_rows_per_step.prefill").read(hand_made) == 64.0
    assert reader("ragged_live_page_share").read(hand_made) == \
        pytest.approx(100.0 * 80 / 400)
    assert reader("kv_pool_used_pct").read(hand_made) == \
        pytest.approx(50.0)
    # the drain step's 5 compiles are outside the window
    assert reader("engine_compiles_in_window").read(hand_made) == 2


def test_empty_ring_and_mismatch_give_none(monkeypatch, capsys):
    steps = [seen(0.1, prefill=3)]
    monkeypatch.setattr(ps, "_records", lambda: [])
    for name in NEW:
        assert reader(name).read(harness(steps, 1)) is None, name
    # a program without step records at all (the parent of ISSUE 24)
    monkeypatch.setattr(ps, "_records", lambda: None)
    assert reader("kv_pool_used_pct").read(harness(steps, 1)) is None
    # fewer records than harness steps: the ring wrapped
    monkeypatch.setattr(ps, "_records",
                        lambda: [record(1, 0, [1] * 6, prefill_rows=3)])
    assert ps.window(harness(steps * 2, 2)) is None
    # records that are not the harness's steps
    monkeypatch.setattr(ps, "_records",
                        lambda: [record(1, 0, [1] * 6, prefill_rows=4)])
    assert ps.window(harness(steps, 1)) is None
    assert "does not match" in capsys.readouterr().out
    # no harness steps (a training cell)
    assert ps.window(harness([], 0)) is None


def test_request_phase_percentiles(monkeypatch, hand_made):
    from paddle_tpu.observability import tracing

    def trace(rid, enqueue, admit, chunk, token):
        t = tracing.RequestTrace(rid)
        for name, ms in (("enqueue", enqueue), ("admit", admit),
                         ("prefill_chunk", chunk), ("token", token)):
            if ms is not None:
                t._events.append(tracing.TraceEvent(name, ms * 1000))
        return t

    done = [trace("warm", 50, 60, 70, 80)]      # before the window's cut
    done += [trace(i, 1000 + i, 1010 + i, 1010 + 3 * i, 1100 + 3 * i)
             for i in range(1, 21)]
    live = [trace("queued", 1500, None, None, None),
            trace("waiting", 1500, 1600, None, None)]
    fake = types.SimpleNamespace(finished=lambda kind=None: done,
                                 live=lambda: live)
    monkeypatch.setattr(tracing, "recorder", lambda: fake)
    w = ps.window(hand_made)
    assert len(ps.window_requests(w)) == 22
    # queue wait: twenty of 10 ms and one of 100 ms
    assert reader("queue_wait_p95_ms.engine").read(hand_made) == \
        pytest.approx(10.0)
    # prefill wait 2 i ms, i = 1..20: the 95th percentile lies at 19.05
    assert reader("prefill_wait_p95_ms.engine").read(hand_made) == \
        pytest.approx(2 * 19.05)
    assert reader("prefill_run_p95_ms.engine").read(hand_made) == \
        pytest.approx(90.0)


def test_every_new_metric_is_declared_with_a_reader():
    declared = {m["name"]: m for m in BENCHMARK["per_layer"]}
    assert [m["name"] for m in BENCHMARK["per_layer"]][-len(NEW):] == NEW
    for name in NEW:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
        assert declared[name]["source"] in ("program_span",
                                            "program_counter")
        assert "mistral7b-train-zero2-mp2-4chip" not in \
            declared[name]["workloads"]


def test_rehearsed_trace_line_has_counters_and_null_spans():
    cell = "mistral7b-serve-chat-0.8knee"
    proc = run_cell(cell, "--trace", "1", "--rehearse")
    line = last_json(proc)
    check_line(line, cell, 1)
    got = line["metrics"]
    declared = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in NEW:
        assert name in got, name
        if declared[name]["source"] == "program_span":
            assert got[name]["value"] is None
        else:
            assert isinstance(got[name]["value"], (int, float))
    assert got["engine_compiles_in_window"]["value"] == 0
    assert 0 < got["ragged_live_page_share"]["value"] <= 100
    assert 0 < got["kv_pool_used_pct"]["value"] <= 100
    assert "request books: queue + prefill wait + prefill run differ " \
        "from the program's TTFT by at most 0.000000 ms" in proc.stdout
    print(json.dumps(got, indent=1))
