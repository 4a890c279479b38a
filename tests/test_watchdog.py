"""Collective watchdog & flight recorder (ISSUE 3): ring-buffer
recording at every collective entry, hang detection within
FLAGS_collective_timeout with a JSON post-mortem dump, cross-rank desync
diagnosis through the rendezvous store, merge/first-divergence tooling,
the trainer's emergency-checkpoint path on CollectiveTimeout, and the
watchdog-off overhead gate."""

import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu import resilience as res
from paddle_tpu.distributed import collective as coll
from paddle_tpu.distributed import watchdog as wd
from paddle_tpu.flags import flags_guard
from paddle_tpu.io import Dataset
from paddle_tpu.trainer.trainer import Trainer, TrainingArguments


@pytest.fixture(autouse=True)
def _clean_watchdog():
    res.clear_fault_spec()
    wd.reset()
    yield
    res.clear_fault_spec()
    wd.stop_monitor()
    wd.detach_store()
    wd.set_recording(False)
    wd.reset()


def _metric(name: str) -> float:
    snap = wd.metrics().get(name)
    if not snap:
        return 0.0
    return sum(s["value"] for s in snap["series"])


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def test_recorder_ring_seq_and_eviction():
    r = wd.FlightRecorder(capacity=3)
    for i in range(5):
        rec = r.start("all_reduce", [[4, 4]], ["float32"], 64, "dp")
        r.finish(rec, "ok")
    recs = r.records()
    assert len(recs) == 3                       # fixed-size ring evicted
    assert [x.seq for x in recs] == [3, 4, 5]   # monotonic seq survives
    assert r.last_completed().seq == 5
    assert all(x.status == "ok" and x.end is not None for x in recs)


def test_recording_off_by_default():
    assert not wd.enabled()                     # FLAGS_collective_timeout=0
    assert wd.start_record("all_reduce") is None
    coll.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
    assert wd.recorder().records() == []


def test_collective_calls_recorded_with_shapes():
    wd.set_recording(True)
    t = paddle.to_tensor(np.ones((2, 3), np.float32))
    coll.all_reduce(t)
    coll.barrier()
    recs = wd.recorder().records()
    assert [r.op for r in recs] == ["all_reduce", "barrier"]
    ar = recs[0]
    assert ar.shapes == [[2, 3]] and ar.dtypes == ["float32"]
    assert ar.bytes == 2 * 3 * 4
    assert ar.status == "ok" and ar.seq == 1
    assert _metric("watchdog.collectives_recorded") >= 2


def test_injected_error_recorded_as_error():
    wd.set_recording(True)
    res.set_fault_spec("seed=9;collective_error@collective=all_reduce")
    with pytest.raises(res.InjectedFault):
        coll.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
    rec = wd.recorder().records()[-1]
    assert rec.op == "all_reduce" and rec.status == "error"


def test_dump_format(tmp_path):
    wd.set_recording(True)
    coll.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
    p = wd.dump_to(str(tmp_path / "flightdump.0.json"))
    d = json.load(open(p))
    assert d["version"] == 1 and d["rank"] == 0
    assert d["last_completed_seq"] == 1
    (rec,) = d["records"]
    assert rec["op"] == "all_reduce" and rec["status"] == "ok"
    assert rec["seq"] == 1 and rec["duration_s"] >= 0
    assert set(rec) >= {"seq", "op", "shapes", "dtypes", "bytes", "axis",
                        "start", "end", "duration_s", "status"}


# ---------------------------------------------------------------------------
# hang detection (tentpole acceptance)
# ---------------------------------------------------------------------------
def test_hang_detected_within_timeout(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_LOG_DIR", str(tmp_path))
    res.set_fault_spec(
        "seed=1;collective_hang@collective=all_reduce:ms=30000")
    before = _metric("watchdog.timeouts")
    with flags_guard(collective_timeout=0.3):
        t0 = time.monotonic()
        with pytest.raises(wd.CollectiveTimeout) as ei:
            coll.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
        elapsed = time.monotonic() - t0
    # detected within the deadline (not the 30s hang), with the diagnosis
    assert 0.25 <= elapsed < 5.0
    e = ei.value
    assert e.op == "all_reduce" and e.seq == 1
    assert e.elapsed_s >= 0.3
    assert _metric("watchdog.timeouts") >= before + 1
    # the dump landed in the worker log dir and names the hung op
    assert e.dump_path == str(tmp_path / "flightdump.0.json")
    d = json.load(open(e.dump_path))
    assert d["timed_out_seq"] == 1
    assert d["records"][0]["op"] == "all_reduce"
    assert d["records"][0]["status"] == "timeout"


def test_timeout_error_names_a_dump_held_back(tmp_path, monkeypatch):
    """`cancelled` is published after the dump: a wait site that polls it
    while another thread (the monitor) is still writing the dump raises
    an error that names the dump, not one built before it existed."""
    monkeypatch.setenv("PADDLE_LOG_DIR", str(tmp_path))
    wd.set_recording(True)
    rec = wd.start_record("all_reduce")
    real = wd.recorder().dump_to

    def held_back(*a, **k):
        time.sleep(0.2)
        return real(*a, **k)
    monkeypatch.setattr(wd.recorder(), "dump_to", held_back)
    t = threading.Thread(target=wd.handle_timeout, args=(rec,))
    t.start()
    try:
        with pytest.raises(wd.CollectiveTimeout) as ei:
            wd.simulate_hang("all_reduce", 5.0)
    finally:
        t.join()
        wd.end_record(rec, "timeout")
    assert ei.value.dump_path == str(tmp_path / "flightdump.0.json")
    assert os.path.exists(ei.value.dump_path)


def test_unguarded_hang_is_bounded_by_ms():
    # watchdog off: the injected hang still returns after ms, not forever
    res.set_fault_spec("seed=1;collective_hang@collective=all_reduce:ms=50")
    t0 = time.monotonic()
    coll.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
    assert 0.04 <= time.monotonic() - t0 < 5.0


def test_barrier_timeout_on_dead_peer(tmp_path, monkeypatch):
    """Satellite bugfix: barrier() must raise CollectiveTimeout instead of
    hanging forever when a peer never completes (block_until_ready
    blocks)."""
    import jax

    class DeadPeerArray:
        def block_until_ready(self):
            time.sleep(10.0)

    monkeypatch.setenv("PADDLE_LOG_DIR", str(tmp_path))
    monkeypatch.setattr(jax, "live_arrays", lambda: [DeadPeerArray()])
    with flags_guard(collective_timeout=0.2):
        t0 = time.monotonic()
        with pytest.raises(wd.CollectiveTimeout, match="barrier"):
            coll.barrier()
        assert time.monotonic() - t0 < 5.0
    rec = wd.recorder().records()[-1]
    assert rec.op == "barrier" and rec.status == "timeout"


# ---------------------------------------------------------------------------
# cross-rank desync
# ---------------------------------------------------------------------------
def test_publish_progress_and_desync_report():
    from paddle_tpu.native import TCPStore
    s = TCPStore(is_master=True, world_size=2)
    try:
        wd.attach_store(s, rank=0, world_size=2, slot=0)
        wd.set_recording(True)
        coll.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
        coll.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
        wd.publish_progress()
        # a peer stuck one op behind publishes its own progress
        s.set("flight/1",
              f"{time.time()}|rank=1,seq=1,op=all_reduce,"
              f"inflight=all_gather,inflight_seq=2,status=inflight")
        rep = wd.desync_report(s, world_size=2)
        assert rep["desynced"]
        assert rep["lagging_rank"] == 1
        assert rep["lagging_op"] == "all_gather"
        assert rep["min_seq"] == 1 and rep["max_seq"] == 2
        # the heartbeat payload channel stays parseable by the launcher
        from paddle_tpu.distributed.launch import ElasticManager
        m = ElasticManager(s, node_rank=0, ttl=5.0)
        assert 0 in m.alive_nodes(1)
    finally:
        s.close()


def test_desync_report_names_silent_rank():
    from paddle_tpu.native import TCPStore
    s = TCPStore(is_master=True, world_size=2)
    try:
        s.set("flight/0", f"{time.time()}|rank=0,seq=5,op=all_reduce,"
                          f"inflight=,inflight_seq=0,status=idle")
        rep = wd.desync_report(s, world_size=2)
        # rank 1 never published: it is the laggard by definition
        assert rep["missing"] == [1]
        assert rep["lagging_rank"] == 1 and rep["desynced"]
    finally:
        s.close()


def test_hang_dump_names_lagging_rank(tmp_path, monkeypatch):
    """Acceptance: the flight dump written on timeout carries the
    cross-rank desync report naming the lagging rank."""
    from paddle_tpu.native import TCPStore
    monkeypatch.setenv("PADDLE_LOG_DIR", str(tmp_path))
    s = TCPStore(is_master=True, world_size=2)
    try:
        wd.attach_store(s, rank=0, world_size=2, slot=0)
        # the peer (rank 1) never completed anything: it is the laggard
        # whose absence makes OUR collective hang
        s.set("flight/1", f"{time.time()}|rank=1,seq=0,op=,"
                          f"inflight=all_reduce,inflight_seq=1,"
                          f"status=inflight")
        # hang the 2nd all_reduce (2 candidate sites per call -> n=3):
        # we completed seq 1, the peer completed nothing
        res.set_fault_spec("seed=1;collective_hang@n=3:ms=30000")
        with flags_guard(collective_timeout=0.25):
            coll.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
            with pytest.raises(wd.CollectiveTimeout) as ei:
                coll.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
        assert ei.value.lagging_rank == 1
        d = json.load(open(ei.value.dump_path))
        assert d["desync"]["lagging_rank"] == 1
        assert d["desync"]["desynced"]
    finally:
        s.close()


# ---------------------------------------------------------------------------
# post-mortem merge + CLI
# ---------------------------------------------------------------------------
def _dump(rank, records, last=None):
    return {"version": 1, "rank": rank,
            "last_completed_seq": last if last is not None else max(
                (r["seq"] for r in records if r["status"] == "ok"),
                default=0),
            "records": records}


def _rec(seq, op, status="ok", shapes=((4,),)):
    return {"seq": seq, "op": op, "shapes": [list(s) for s in shapes],
            "dtypes": ["float32"], "bytes": 16, "axis": "dp",
            "start": 0.0, "end": 0.1, "duration_s": 0.1, "status": status}


def test_merge_dumps_names_lagging_rank_and_timeout():
    d0 = _dump(0, [_rec(1, "all_reduce"), _rec(2, "all_gather"),
                   _rec(3, "all_reduce", status="timeout")], last=2)
    d1 = _dump(1, [_rec(1, "all_reduce")], last=1)
    m = wd.merge_dumps([d0, d1])
    assert m["world"] == 2 and m["ranks"] == [0, 1]
    assert m["last_completed_seq"] == {0: 2, 1: 1}
    assert m["lagging_rank"] == 1
    fd = m["first_divergence"]
    assert fd["seq"] == 2 and fd["reason"] == "missing_rank"
    assert fd["missing"] == [1]
    # merged records interleave by (seq, rank)
    assert [(r["seq"], r["rank"]) for r in m["records"]] == [
        (1, 0), (1, 1), (2, 0), (3, 0)]


def test_first_divergence_detects_op_mismatch():
    d0 = _dump(0, [_rec(1, "all_reduce"), _rec(2, "all_gather")])
    d1 = _dump(1, [_rec(1, "all_reduce"), _rec(2, "broadcast")])
    fd = wd.first_divergence([d0, d1])
    assert fd["seq"] == 2 and fd["reason"] == "op_mismatch"
    assert fd["ops"] == {0: "all_gather", 1: "broadcast"}


def test_first_divergence_none_when_consistent():
    d0 = _dump(0, [_rec(1, "all_reduce"), _rec(2, "barrier")])
    d1 = _dump(1, [_rec(1, "all_reduce"), _rec(2, "barrier")])
    assert wd.first_divergence([d0, d1]) is None


def _cli():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "flight_recorder.py")
    spec = importlib.util.spec_from_file_location("flight_recorder_cli",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_flight_recorder_cli_merge_and_diff(tmp_path, capsys):
    cli = _cli()
    logs = tmp_path / "log"
    logs.mkdir()
    (logs / "flightdump.0.json").write_text(json.dumps(
        _dump(0, [_rec(1, "all_reduce"), _rec(2, "all_gather")], last=2)))
    (logs / "flightdump.1.json").write_text(json.dumps(
        _dump(1, [_rec(1, "all_reduce"),
                  _rec(2, "all_gather", status="timeout")], last=1)))
    out = tmp_path / "report.json"
    rc = cli.main(["merge", str(logs), "-o", str(out)])
    assert rc == 1                              # divergence found
    rep = json.loads(out.read_text())
    assert rep["lagging_rank"] == 1
    assert rep["first_divergence"]["seq"] == 2
    assert rep["first_divergence"]["reason"] == "not_ok"
    rc = cli.main(["diff", str(logs)])
    assert rc == 1
    shown = capsys.readouterr().out
    assert "lagging_rank" in shown and '"seq": 2' in shown
    # consistent dumps -> exit 0
    (logs / "flightdump.1.json").write_text(json.dumps(
        _dump(1, [_rec(1, "all_reduce"), _rec(2, "all_gather")], last=2)))
    assert cli.main(["diff", str(logs)]) == 0


def test_write_watchdog_report(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    try:
        import bench_util
    finally:
        sys.path.pop(0)
    wd.set_recording(True)
    coll.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
    p = str(tmp_path / "wd_report.json")
    rep = bench_util.write_watchdog_report(p, extra={"run": "unit"})
    assert os.path.exists(p)
    assert rep["run"] == "unit"
    assert rep["totals"]["watchdog.collectives_recorded"] >= 1
    assert rep["flight"]["records"][0]["op"] == "all_reduce"


# ---------------------------------------------------------------------------
# trainer integration (acceptance: chaos hang -> emergency ckpt -> resume)
# ---------------------------------------------------------------------------
class ToyDataset(Dataset):
    def __init__(self, n=64, seed=0):
        rng = np.random.RandomState(seed)
        self.x = rng.randn(n, 8).astype(np.float32)
        w = rng.randn(8, 2).astype(np.float32)
        self.y = self.x @ w

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


class CollNet(nn.Layer):
    """A net whose forward issues a collective every micro-batch (the
    grad-sync stand-in the hang drill targets)."""

    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(8, 2)

    def forward(self, x, y=None):
        out = self.fc(x)
        coll.all_reduce(paddle.to_tensor(np.ones((1,), np.float32)))
        if y is not None:
            return ((out - y) ** 2).mean(), out
        return out


def _args(tmp_path, **kw):
    base = dict(output_dir=str(tmp_path), per_device_train_batch_size=8,
                learning_rate=5e-2, logging_steps=2, max_steps=10,
                warmup_steps=2, seed=7)
    base.update(kw)
    return TrainingArguments(**base)


def test_chaos_hang_emergency_checkpoint_and_resume(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_LOG_DIR", str(tmp_path / "log"))
    # fault-free reference
    t_ref = Trainer(model=CollNet(), args=_args(tmp_path / "ref"),
                    train_dataset=ToyDataset())
    assert t_ref.train()["global_step"] == 10

    # hang the 5th all_reduce (each call = 2 candidate sites -> n=9),
    # 30s unguarded; the watchdog deadline is 0.3s
    res.set_fault_spec("seed=3;collective_hang@n=9:ms=30000")
    out = tmp_path / "chaos"
    args = _args(out)
    t = Trainer(model=CollNet(), args=args, train_dataset=ToyDataset())
    before = _metric("watchdog.timeouts")
    with flags_guard(collective_timeout=0.3):
        t0 = time.monotonic()
        with pytest.raises(wd.CollectiveTimeout) as ei:
            t.train()
        assert time.monotonic() - t0 < 30.0     # detected, not the hang
    assert ei.value.op == "all_reduce"
    assert _metric("watchdog.timeouts") >= before + 1
    # flight dump names the hung op
    d = json.load(open(ei.value.dump_path))
    assert d["timed_out_seq"] == ei.value.seq
    timed_out = [r for r in d["records"] if r["status"] == "timeout"]
    assert timed_out and timed_out[0]["op"] == "all_reduce"
    # the trainer took the emergency-checkpoint path: step 5's forward
    # hung, so the last applied step (4) was checkpointed
    assert t.state["global_step"] == 4
    emergency = out / "checkpoint-4"
    assert emergency.is_dir()
    entry = next(e for e in t.state["log_history"]
                 if "collective_timeout" in e)
    assert "all_reduce" in entry["collective_timeout"]
    assert entry["emergency_checkpoint"] == str(emergency)

    # clear the fault, resume -> same final step count as fault-free
    res.clear_fault_spec()
    t2 = Trainer(model=CollNet(), args=args, train_dataset=ToyDataset())
    state2 = t2.train(resume_from_checkpoint=str(emergency))
    assert state2["global_step"] == 10


# ---------------------------------------------------------------------------
# overhead gate: watchdog off must not tax the collective hot path
# ---------------------------------------------------------------------------
class TestOverhead:
    def test_disabled_overhead_under_5pct(self, monkeypatch):
        """With the watchdog off, a collective's two hooks return before
        they reach the recorder: no record is built and no lock taken.
        (Until ISSUE 30 this compared two wall-clock loops at 5 %, which
        failed under six workers; the name stays, the judgement is a
        count, as in test_observability.py since ISSUE 24.)"""
        reached = {"start": 0, "finish": 0, "lock": 0, "record": 0}

        def counted(name, fn):
            def call(*a, **kw):
                reached[name] += 1
                return fn(*a, **kw)
            return call

        class CountingLock:
            def __init__(self):
                self._lock = threading.Lock()

            def __enter__(self):
                reached["lock"] += 1
                return self._lock.__enter__()

            def __exit__(self, *exc):
                return self._lock.__exit__(*exc)

        monkeypatch.setattr(wd.FlightRecorder, "start",
                            counted("start", wd.FlightRecorder.start))
        monkeypatch.setattr(wd.FlightRecorder, "finish",
                            counted("finish", wd.FlightRecorder.finish))
        monkeypatch.setattr(wd.FlightRecord, "__init__",
                            counted("record", wd.FlightRecord.__init__))
        wd.recorder()._lock = CountingLock()

        def hooks(n=50):
            for _ in range(n):
                wd.end_record(wd.start_record("all_reduce"))

        assert not wd.enabled()
        hooks()
        assert reached == {"start": 0, "finish": 0, "lock": 0, "record": 0}
        assert wd.recorder().records() == []
        reached["lock"] = 0             # records() itself took it
        # the same loop with recording on reaches all four: the counters
        # read what the hooks do
        wd.set_recording(True)
        try:
            hooks()
        finally:
            wd.set_recording(False)
        assert reached == {"start": 50, "finish": 50, "lock": 100,
                           "record": 50}
