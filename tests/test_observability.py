"""Observability layer (ISSUE 1): metric semantics, exporter round-trips,
span/step-log correlation, the disabled-path overhead gate, and the
acceptance check that >=4 subsystems actually report into the default
registry (ops dispatch, collectives, trainer, serving)."""

import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import (Registry, StepLogger, parse_prometheus,
                                      sample_values, span, to_prometheus)


@pytest.fixture(autouse=True)
def _metrics_on():
    """Every test here assumes metrics are recording; restore on exit."""
    prev = obs.enabled()
    obs.set_enabled(True)
    yield
    obs.set_enabled(prev)


# ---------------------------------------------------------------------------
# metric semantics
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter(self):
        r = Registry()
        c = r.counter("c_total", "help text")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge(self):
        r = Registry()
        g = r.gauge("g", "a gauge")
        g.set(4.0)
        g.inc()
        g.dec(2.0)
        assert g.value == 3.0

    def test_histogram_buckets(self):
        r = Registry()
        h = r.histogram("h_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        assert h.count == 5
        assert h.sum == pytest.approx(56.05)
        flat = sample_values(r)
        # cumulative exposition: le=0.1 -> 1, le=1 -> 3, le=10 -> 4, +Inf -> 5
        assert flat['h_seconds_bucket{le="0.1"}'] == 1
        assert flat['h_seconds_bucket{le="1"}'] == 3
        assert flat['h_seconds_bucket{le="10"}'] == 4
        assert flat['h_seconds_bucket{le="+Inf"}'] == 5

    def test_histogram_timer(self):
        r = Registry()
        h = r.histogram("t_seconds")
        with h.time():
            time.sleep(0.002)
        assert h.count == 1
        assert h.sum >= 0.002

    def test_labels_vend_children(self):
        r = Registry()
        c = r.counter("ops_total", labels=("op",))
        c.labels(op="add").inc()
        c.labels(op="add").inc()
        c.labels(op="mul").inc()
        assert c.labels(op="add").value == 2
        assert c.labels(op="mul").value == 1
        with pytest.raises(ValueError):
            c.labels(notalabel="x")

    def test_get_or_create_and_mismatch(self):
        r = Registry()
        a = r.counter("same", "h")
        assert r.counter("same") is a
        with pytest.raises(ValueError):
            r.gauge("same")
        with pytest.raises(ValueError):
            r.counter("same", labels=("x",))

    def test_disabled_mutations_are_dropped(self):
        r = Registry()
        c = r.counter("off_total")
        h = r.histogram("off_seconds")
        obs.set_enabled(False)
        c.inc()
        h.observe(1.0)
        obs.set_enabled(True)
        assert c.value == 0 and h.count == 0

    def test_thread_safety(self):
        r = Registry()
        c = r.counter("mt_total", labels=("t",))
        u = r.counter("mt_unlabeled_total")

        def work(i):
            for _ in range(1000):
                c.labels(t=str(i % 2)).inc()
                u.inc()
        threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        assert u.value == 4000
        assert (c.labels(t="0").value + c.labels(t="1").value) == 4000

    def test_reset(self):
        r = Registry()
        c = r.counter("r_total", labels=("k",))
        c.labels(k="a").inc(5)
        r.reset()
        assert c.labels(k="a").value == 0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _populated_registry():
    r = Registry()
    r.counter("req_total", 'requests with "quotes" and \\ and\nnewline',
              labels=("path", "code")).labels(path="/v1", code="200").inc(7)
    g = r.gauge("temp", "a gauge")
    g.set(36.6)
    h = r.histogram("lat_seconds", "latency", labels=("route",),
                    buckets=(0.01, 0.1, 1.0))
    h.labels(route="a").observe(0.005)
    h.labels(route="a").observe(0.5)
    h.labels(route="b").observe(99.0)
    return r


class TestExporters:
    def test_prometheus_round_trip(self):
        r = _populated_registry()
        text = to_prometheus(r)
        assert "# TYPE req_total counter" in text
        assert "# TYPE lat_seconds histogram" in text
        assert parse_prometheus(text) == sample_values(r)

    def test_json_snapshot_round_trip(self):
        r = _populated_registry()
        snap = r.snapshot()
        # survives actual JSON serialization, not just dict equality
        snap2 = json.loads(json.dumps(snap))
        rebuilt = Registry.from_snapshot(snap2)
        assert rebuilt.snapshot() == snap
        assert sample_values(rebuilt) == sample_values(r)

    def test_prometheus_escaping(self):
        r = Registry()
        r.counter("e_total", labels=("v",)).labels(v='a"b\\c\nd').inc()
        flat = parse_prometheus(to_prometheus(r))
        assert flat == sample_values(r)


# ---------------------------------------------------------------------------
# spans + step log (chrome-trace correlation)
# ---------------------------------------------------------------------------

class TestStepLog:
    def test_span_ids_join_trace_and_jsonl(self, tmp_path):
        from paddle_tpu import native
        native.prof_clear()
        native.prof_enable(True)
        log_path = str(tmp_path / "steps.jsonl")
        with StepLogger(log_path) as sl:
            with span("train_step") as sp:
                sum(range(100))
            sl.log(step=1, span_id=sp.span_id, loss=0.5)
        native.prof_enable(False)
        trace = str(tmp_path / "trace.json")
        native.prof_export(trace)
        events = json.load(open(trace))["traceEvents"]
        names = [e["name"] for e in events]
        assert f"train_step[span={sp.span_id}]" in names
        rows = [json.loads(l) for l in open(log_path)]
        assert rows[0]["step"] == 1
        assert rows[0]["span_id"] == sp.span_id
        assert rows[0]["loss"] == 0.5
        assert isinstance(rows[0]["metrics"], dict)
        native.prof_clear()

    def test_step_log_snapshots_metrics(self, tmp_path):
        r = Registry()
        c = r.counter("steps_total")
        p = str(tmp_path / "s.jsonl")
        with StepLogger(p, reg=r) as sl:
            c.inc()
            sl.log(step=1)
            c.inc()
            sl.log(step=2)
        rows = [json.loads(l) for l in open(p)]
        assert rows[0]["metrics"]["steps_total"] == 1
        assert rows[1]["metrics"]["steps_total"] == 2


# ---------------------------------------------------------------------------
# subsystem population (acceptance: >=4 subsystems report in)
# ---------------------------------------------------------------------------

class TestSubsystems:
    def test_dispatch_and_collectives_and_serving_and_trainer(self, tmp_path):
        reg = obs.registry()

        # 1. ops dispatch: one eager add
        before = sample_values(reg).get('pt_ops_dispatch_total{op="add"}', 0)
        t = paddle.to_tensor(np.ones((2, 2), np.float32))
        _ = t + t
        flat = sample_values(reg)
        assert flat['pt_ops_dispatch_total{op="add"}'] == before + 1

        # 2. collectives: all_reduce (meshless degrades to identity but the
        #    call-level instrumentation still fires)
        from paddle_tpu.distributed import collective
        b4_calls = flat.get(
            'pt_collective_calls_total{collective="all_reduce"}', 0)
        collective.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
        flat = sample_values(reg)
        assert flat['pt_collective_calls_total{collective="all_reduce"}'] \
            == b4_calls + 1
        assert flat['pt_collective_bytes_total{collective="all_reduce"}'] > 0
        assert flat['pt_collective_seconds_count'
                    '{collective="all_reduce"}'] >= 1

        # 3. serving: paged decode attention samples KV-page utilization and
        #    counts the routed kernel
        from paddle_tpu.ops.paged_attention import paged_attention
        q = np.random.RandomState(0).randn(2, 2, 8).astype(np.float32)
        kp = np.random.RandomState(1).randn(1, 4, 4, 8).astype(np.float32)
        vp = np.random.RandomState(2).randn(1, 4, 4, 8).astype(np.float32)
        lens = np.array([3, 6], np.int32)
        tab = np.array([[0, 1], [2, 3]], np.int32)
        import jax.numpy as jnp
        paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                        jnp.asarray(lens), jnp.asarray(tab))
        flat = sample_values(reg)
        util = flat["pt_serving_kv_page_utilization"]
        assert util == pytest.approx(4.5 / 8.0)
        assert sum(v for k, v in flat.items()
                   if k.startswith("pt_kernel_launch_total")) >= 1

        # 4. trainer: a 2-step run populates the step breakdown + gauges
        from paddle_tpu import nn
        from paddle_tpu.io import Dataset
        from paddle_tpu.trainer.trainer import Trainer, TrainingArguments

        class DS(Dataset):
            def __len__(self):
                return 16

            def __getitem__(self, i):
                x = np.full((4,), i, np.float32)
                return x, x.sum(keepdims=True)

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(4, 1)

            def forward(self, x, y=None):
                out = self.fc(x)
                if y is not None:
                    return ((out - y) ** 2).mean()
                return out

        b4_steps = flat.get("pt_train_steps_total", 0)
        tr = Trainer(model=Net(),
                     args=TrainingArguments(
                         output_dir=str(tmp_path), max_steps=2,
                         per_device_train_batch_size=4, logging_steps=1,
                         flops_per_sample=1e6, hardware_peak_flops=1e12),
                     train_dataset=DS())
        tr.train()
        flat = sample_values(reg)
        assert flat["pt_train_steps_total"] == b4_steps + 2
        assert flat["pt_train_forward_seconds_count"] >= 2
        assert flat["pt_train_backward_seconds_count"] >= 2
        assert flat["pt_train_optimizer_seconds_count"] >= 2
        assert flat["pt_train_data_seconds_count"] >= 2
        assert flat["pt_train_grad_norm_count"] >= 2
        assert flat["pt_train_samples_per_second"] > 0
        assert flat["pt_train_tokens_per_second"] > 0
        assert flat["pt_train_mfu"] > 0

        # the four subsystems are all visible in one Prometheus scrape
        text = to_prometheus(reg)
        for family in ("pt_ops_dispatch_total", "pt_collective_calls_total",
                       "pt_serving_kv_page_utilization",
                       "pt_train_steps_total"):
            assert f"# TYPE {family}" in text

    def test_jit_cache_hit_miss_counters(self):
        reg = obs.registry()
        from paddle_tpu import jit

        @jit.to_static
        def f(x):
            return x * 2 + 1

        x = paddle.to_tensor(np.ones((3,), np.float32))
        f(x)
        f(x)
        f(x)
        flat = sample_values(reg)
        calls = flat['pt_jit_call_total{kind="to_static"}']
        traces = flat['pt_jit_trace_total{kind="to_static"}']
        assert calls >= 3
        # same shape/dtype -> exactly one trace for the three calls
        assert traces >= 1
        assert calls - traces >= 2  # cache hits


# ---------------------------------------------------------------------------
# overhead gate: disabled metrics must not tax the hot loop
# ---------------------------------------------------------------------------

class _CountingLock:
    """Stands in for a `threading.Lock`; counts what takes it."""

    def __init__(self):
        self._lock = threading.Lock()
        self.taken = 0

    def acquire(self, *a, **kw):
        self.taken += 1
        return self._lock.acquire(*a, **kw)

    def release(self):
        self._lock.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False


def _what_it_does(body, n=200):
    """Run `body(i)` n times and report what it DID inside paddle_tpu:
    Python functions entered and C functions called per iteration
    (`sys.setprofile`), and the blocks still allocated afterwards from
    paddle_tpu's own files (`tracemalloc`).  All three repeat exactly
    under any load, which a ratio of two wall-clock loops does not (the
    gate this replaces failed in the driver's run under six workers)."""
    import os
    import sys
    import tracemalloc
    pkg = os.path.dirname(os.path.abspath(obs.__file__))
    pkg = os.path.dirname(pkg) + os.sep
    seen = {"py": 0, "c": 0}

    def profile(frame, event, arg):
        if frame.f_code.co_filename.startswith(pkg):
            if event == "call":
                seen["py"] += 1
            elif event == "c_call":
                seen["c"] += 1

    body(-1)        # lazy imports and first-call caches are not the path
    prev = sys.getprofile()
    sys.setprofile(profile)
    try:
        for i in range(n):
            body(i)
    finally:
        sys.setprofile(prev)
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for i in range(n):
            body(i)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    only = [tracemalloc.Filter(True, pkg + "*")]
    kept = sum(d.count_diff for d in after.filter_traces(only).compare_to(
        before.filter_traces(only), "filename") if d.count_diff > 0)
    return {"py_calls": seen["py"] / n, "c_calls": seen["c"] / n,
            "blocks_kept": kept}


class TestOverhead:
    """What a DISABLED entry point does: how many Python functions it
    enters, whether it calls into C, takes a lock or keeps an object.
    (Until ISSUE 24 these compared two wall-clock loops at 5 %; the
    names stay, the judgement is now a count.)"""

    def _off(self):
        from paddle_tpu.observability import tracing as tr
        obs.set_enabled(False)
        tr.set_enabled(False)

    def _on(self):
        from paddle_tpu.observability import tracing as tr
        obs.set_enabled(True)
        tr.set_enabled(True)

    def test_disabled_overhead_under_5pct(self):
        from paddle_tpu.observability import tracing as tr
        r = Registry()
        c = r.counter("ov_total")
        h = r.histogram("ov_seconds")
        rec = tr.TraceRecorder(capacity=8)
        locks = [_CountingLock() for _ in range(3)]
        c._lock, h._lock, rec._lock = locks

        def body(i):
            c.inc()
            h.observe(1.0)
            rec.stamp(i, "token", index=i)

        self._off()
        try:
            did = _what_it_does(body)
        finally:
            self._on()
        # each entry point is ONE Python function that tests the flag
        # and returns: no C call, no lock, nothing kept
        assert did == {"py_calls": 3, "c_calls": 0, "blocks_kept": 0}
        assert [lk.taken for lk in locks] == [0, 0, 0]
        assert c.value == 0 and h.count == 0   # the flag really gated
        assert not rec.live() and not rec.finished()

    def test_disabled_counter_tracks_under_5pct(self):
        # ISSUE 11: the per-step attribution stamps the engine adds —
        # counter-track points and gauge sampling — must also vanish
        # under the off flags; ISSUE 24: so must the step records and
        # the in-memory sink of `observability.span`
        from paddle_tpu.observability import tracing as tr
        r = Registry()
        g = r.gauge("ov_gauge")
        g.set(1.0)
        rec = tr.TraceRecorder(capacity=8)
        rec._lock = lock = _CountingLock()

        def body(i):
            rec.counter("ov.track", float(i))
            rec.sample_gauges(("ov_gauge",), reg=r)
            rec.open_step(i, "ov.step")
            rec.close_step({"decode_rows": i})

        self._off()
        try:
            did = _what_it_does(body)
        finally:
            self._on()
        assert rec.counters() == {} and rec.steps() == []
        assert not rec._listening       # no jax.monitoring listener
        # close_step has to look (a step may have opened before the
        # flag went off): one lock, nothing else
        assert did == {"py_calls": 4, "c_calls": 0, "blocks_kept": 0}
        assert lock.taken == 2 * 200 + 1 + 2    # 2 loops, warm-up, reads

        # the span primitive with the flag off: its profiler annotation
        # and the native host event have switches of their own (no trace
        # is on), and the recorder is never reached
        default = tr.recorder()
        n_spans = len(default.spans())
        real, default._lock = default._lock, _CountingLock()
        self._off()
        try:
            def spans(i):
                with span("ov.span", step=i):
                    pass
            did = _what_it_does(spans)
            taken = default._lock.taken
        finally:
            default._lock = real
            self._on()
        assert taken == 0 and len(default.spans()) == n_spans
        assert did["c_calls"] <= 8      # the two sinks' own enter / exit
        assert did["blocks_kept"] <= 2  # of 200 spans: none keeps one
        # span(), _Span.__init__/__enter__/__exit__ and RecordEvent's
        # three: a fixed handful, whatever the load
        assert did["py_calls"] <= 8

    def test_disabled_fleet_paths_under_5pct(self):
        # ISSUE 16: the fleet plane's hot-path hooks — the router's SLO
        # observes and the per-stamp replica-context/handoff-context
        # machinery — must also vanish under the off flags
        from paddle_tpu.observability import fleet as fleet_mod
        from paddle_tpu.observability import tracing as tr
        rec = tr.TraceRecorder(capacity=8)
        rec._lock = lock = _CountingLock()

        def body(i):
            fleet_mod.observe_ttft(0.1)
            fleet_mod.observe_handoff(0.01)
            rec.set_replica_context("pf0")
            rec.adopt(i, rec.export_context(i))

        before = obs.snapshot()["serving.fleet.ttft_seconds"]
        self._off()
        try:
            did = _what_it_does(body)
        finally:
            self._on()
        after = obs.snapshot()["serving.fleet.ttft_seconds"]
        assert after["series"][0]["count"] \
            == before["series"][0]["count"]  # observes really gated
        assert not rec.live() and not rec.finished()
        assert did == {"py_calls": 5, "c_calls": 0, "blocks_kept": 0}
        assert lock.taken == 2              # the two reads above

    def test_a_serving_run_never_reads_its_own_program(self, monkeypatch):
        # ISSUE 37: the step scopes are metadata of the one tracing;
        # construction and `step()` compile nothing twice, lower nothing
        # again and build no table — only a reader that asks does
        import numpy as np
        from test_engine_programs import _tiny
        from paddle_tpu.observability import attribution as at
        from paddle_tpu.serving import ServingEngine
        from paddle_tpu.serving import engine as engine_mod
        called = []
        for name in ("op_scopes", "compile_named"):
            monkeypatch.setattr(
                at, name, lambda *a, _n=name, **k: called.append(_n))
        monkeypatch.setattr(engine_mod, "compile_named",
                            lambda *a, **k: called.append("compile_named"))
        monkeypatch.setattr(
            ServingEngine, "compiled_programs",
            lambda self: called.append("compiled_programs"))
        fresh = []
        real = ServingEngine._step_programs
        monkeypatch.setattr(
            ServingEngine, "_step_programs",
            lambda self: fresh.append(1) or real(self))
        eng = ServingEngine(_tiny("llama"), max_slots=2, page_size=8,
                            max_context=64, prefill_chunk=8)
        for n in (5, 9):
            eng.add_request(np.arange(n, dtype=np.int32), max_new_tokens=4)
        assert len(eng.run_to_completion()) == 2
        assert called == [] and fresh == [1]    # the constructor's one
        assert all(n == 1 for n in eng.program_cache_sizes().values())


class TestReplicaPrefixMetrics:
    """ISSUE 15 satellite: the fleet router's locality signal is visible
    per replica — hit tokens, pinned pages, and evictions carry a
    replica label in the registry."""

    @staticmethod
    def _series(name, replica):
        from paddle_tpu import serving as srv
        fam = srv.metrics().get(name) or {"series": []}
        return sum(s["value"] for s in fam["series"]
                   if s["labels"].get("replica") == replica)

    def test_per_replica_hit_pin_evict_counters(self):
        from paddle_tpu.serving import PageBlockAllocator, PrefixCache
        a = PageBlockAllocator(num_pages=9, page_size=4, pages_per_seq=4)
        pc = PrefixCache(a, replica="pf_obs_test")
        prompt = np.arange(100, 112, dtype=np.int32)
        a.allocate("s", 12)
        a.extend("s", 12)

        hit0 = self._series("serving.prefix_cache.replica_hit_tokens",
                            "pf_obs_test")
        ev0 = self._series("serving.prefix_cache.replica_evicted_pages",
                           "pf_obs_test")
        pc.insert(prompt, a.seq_pages("s"))
        assert self._series(
            "serving.prefix_cache.replica_pinned_pages",
            "pf_obs_test") == 3
        # a lookup on the cached prompt counts matched tokens (capped
        # one token short of the prompt: 2 of the 3 pages)
        m = pc.lookup(prompt)
        assert self._series(
            "serving.prefix_cache.replica_hit_tokens",
            "pf_obs_test") == hit0 + 8
        m.release()
        a.free("s")
        pc.flush()
        assert self._series(
            "serving.prefix_cache.replica_evicted_pages",
            "pf_obs_test") == ev0 + 3
        assert self._series(
            "serving.prefix_cache.replica_pinned_pages",
            "pf_obs_test") == 0

    def test_set_replica_renames_late(self):
        # the FleetRouter names engines it was handed anonymously:
        # set_replica adopts the label for subsequent traffic
        from paddle_tpu.serving import PageBlockAllocator, PrefixCache
        a = PageBlockAllocator(num_pages=9, page_size=4, pages_per_seq=4)
        pc = PrefixCache(a)
        pc.set_replica("late_name_test")
        a.allocate("s", 8)
        a.extend("s", 8)
        pc.insert(np.arange(8, dtype=np.int32), a.seq_pages("s"))
        assert self._series(
            "serving.prefix_cache.replica_pinned_pages",
            "late_name_test") == 2

    def test_handoff_and_router_families_registered(self):
        # the handoff/router metric families exist in the default
        # registry with their label schema (values are exercised by the
        # serving tests; this pins the observable surface)
        snap = obs.registry().snapshot()
        assert snap["serving.handoff.requests"]["labels"] == ["direction"]
        assert "serving.handoff.pages" in snap
        assert "serving.handoff.bytes" in snap
        assert sorted(snap["serving.router.placements"]["labels"]) \
            == ["replica", "signal"]
        assert snap["serving.router.drains"]["labels"] == ["replica"]
        assert "serving.router.requeued" in snap
        assert "serving.router.replicas_up" in snap


# ---------------------------------------------------------------------------
# the set-up ledger (ISSUE 68): program records from jax.monitoring's
# events, on a fresh recorder fed by hand — a stage is reported when it
# ENDS, with its length, and the recorder stamps it on its own clock
# ---------------------------------------------------------------------------

_EV = "/jax/core/compile/"
_TRACE, _LOWER, _BACKEND = (_EV + "jaxpr_trace_duration",
                            _EV + "jaxpr_to_mlir_module_duration",
                            _EV + "backend_compile_duration")
_CACHE = "/jax/compilation_cache/"
MS = 1_000_000


class _Feed:
    """A recorder, a clock the test sets, and the events as jax sends
    them: `stage(kind, name, end in ms, length in ms)`."""

    def __init__(self, monkeypatch, capacity=8):
        from paddle_tpu.observability import tracing as tr
        self.rec = tr.TraceRecorder(capacity=capacity)
        self.now = 0
        monkeypatch.setattr(tr, "_now_ns", lambda: self.now)

    def stage(self, kind, name, end_ms, ms):
        self.now = int(end_ms * MS)
        self.rec._on_duration(kind, ms / 1e3, fun_name=name)

    def program(self, fn, at_ms, cache=None, trace=3, lower=2, backend=1,
                read=0.5):
        """trace -> lower -> what the cache said -> backend, from
        `at_ms` on; returns the end."""
        t = at_ms + trace
        self.stage(_TRACE, fn, t, trace)
        t += lower
        self.stage(_LOWER, f"jit({fn})", t, lower)
        if cache is not None:
            self.rec._on_event(_CACHE + "compile_requests_use_cache")
            self.rec._on_event(_CACHE + "cache_" + cache)
        if cache == "hits":
            self.rec._on_duration(_CACHE + "cache_retrieval_time_sec",
                                  read / 1e3)
        t += backend
        self.stage(_BACKEND, f"jit({fn})", t, backend)
        return t


class TestSetupLedger:
    @pytest.mark.parametrize("said,cache,read", [
        ("hits", "hit", 0.5), ("misses", "miss", 0), (None, "off", 0)])
    def test_a_program_is_one_record(self, monkeypatch, said, cache, read):
        f = _Feed(monkeypatch)
        end = f.program("step", 10, cache=said, backend=40)
        (r,) = f.rec.programs()
        assert r == {"name": "jit(step)", "start_ns": 10 * MS,
                     "end_ns": end * MS, "trace_ns": 3 * MS,
                     "lower_ns": 2 * MS, "compile_ns": 40 * MS,
                     "cache": cache, "cache_read_ns": int(read * MS),
                     "span": None, "step": None}
        want = dict.fromkeys(("hits", "misses"), 0)
        if said:
            want[said] = 1
        assert f.rec.program_totals() == {None: dict(
            want, programs=1, trace_ns=3 * MS, lower_ns=2 * MS,
            compile_ns=40 * MS, cache_read_ns=int(read * MS))}

    def test_a_nested_programs_time_counts_once(self, monkeypatch):
        # the step program's trace, 0..10 ms, holds a kernel's jit
        # traced 2..5 and an eager constant's whole compile 6..8
        f = _Feed(monkeypatch)
        f.stage(_TRACE, "kernel", 5, 3)
        f.program("const", 6, trace=0.5, lower=0.5, backend=1)
        f.stage(_TRACE, "step", 10, 10)
        f.stage(_LOWER, "jit(step)", 12, 2)
        f.stage(_BACKEND, "jit(step)", 15, 3)
        by = {r["name"]: r for r in f.rec.programs()}
        assert sorted(by) == ["jit(const)", "jit(step)", "kernel"]
        assert by["kernel"]["trace_ns"] == 3 * MS
        assert by["kernel"]["cache"] is None     # never reached a backend
        assert by["jit(step)"]["trace_ns"] == (10 - 3 - 2) * MS
        assert (by["jit(step)"]["start_ns"], by["jit(step)"]["end_ns"]) \
            == (0, 15 * MS)
        t = f.rec.program_totals()[None]
        assert t["programs"] == 3
        assert t["trace_ns"] + t["lower_ns"] + t["compile_ns"] == 15 * MS

    def test_a_compile_finds_its_lowering_by_name(self, monkeypatch):
        # `lowered = f.lower(...)`, other work, `lowered.compile()`
        f = _Feed(monkeypatch)
        f.stage(_TRACE, "step", 3, 3)
        f.stage(_LOWER, "jit(step)", 5, 2)
        f.program("other", 6)
        f.stage(_BACKEND, "jit(step)", 20, 4)
        by = {r["name"]: r for r in f.rec.programs()}
        assert by["jit(step)"]["compile_ns"] == 4 * MS
        assert by["jit(step)"]["lower_ns"] == 2 * MS
        assert len(by) == 2

    def test_span_and_step_are_the_open_ones(self, monkeypatch):
        f = _Feed(monkeypatch)
        with span("t.construct"):
            with span("t.construct.programs"):
                f.program("feed", 0, cache="misses")
            f.program("pool", 10)
        f.rec.open_step(7, "t.step")
        with span("t.step", step=7):
            with span("t.launch"):
                f.program("step", 20, cache="hits")
        f.rec._span_done("t.launch", 20 * MS, 27 * MS, "t.step", 7)
        f.rec._span_done("t.step", 19 * MS, 28 * MS, None, 7)
        f.rec.close_step({"decode_rows": 1})
        got = {r["name"]: (r["span"], r["step"]) for r in f.rec.programs()}
        assert got == {"jit(feed)": ("t.construct.programs", None),
                       "jit(pool)": ("t.construct", None),
                       "jit(step)": ("t.launch", 7)}
        assert sorted(f.rec.program_totals()) == [
            "t.construct", "t.construct.programs", "t.launch"]
        # the step counts what reached the backend while it was open,
        # and a step in which a program landed is copied to the ledger
        (st,) = f.rec.steps()
        assert st["compiles"] == 1 and "programs" not in st
        copied = [sp for sp in f.rec.setup()["spans"] if sp["step"] == 7]
        assert copied == [{
            "name": "t.step", "start_ns": 19 * MS, "end_ns": 28 * MS,
            "parent": None, "step": 7,
            "phases": [("t.launch", 20 * MS, 27 * MS)]}]
        # a step that compiled nothing is not
        f.rec.open_step(8, "t.step")
        f.rec.close_step({"decode_rows": 1})
        assert f.rec.steps()[-1]["compiles"] == 0
        assert len([sp for sp in f.rec.setup()["spans"]
                    if sp["step"] is not None]) == 1

    def test_totals_survive_ten_thousand_records(self, monkeypatch):
        from paddle_tpu.observability import tracing as tr
        monkeypatch.setattr(tr, "PROGRAMS_KEPT", 32)
        f = _Feed(monkeypatch)
        t = 0
        for i in range(10_000):     # record i is i us of backend
            t = f.program(f"op{i}", t, trace=0.001, lower=0.001,
                          backend=(i + 1) / 1e3)
        kept = f.rec.programs()
        assert len(kept) == 32      # ... the longest
        assert {r["name"] for r in kept} == {
            f"jit(op{i})" for i in range(10_000 - 32, 10_000)}
        tot = f.rec.program_totals()[None]
        assert tot["programs"] == 10_000
        assert tot["compile_ns"] == sum(range(1, 10_001)) * 1000
        assert tot["trace_ns"] == tot["lower_ns"] == 10_000 * 1000

    def test_set_up_survives_the_step_traffic(self, monkeypatch):
        # a window makes thousands of step spans through the span ring
        f = _Feed(monkeypatch, capacity=64)
        f.rec._span_done("t.construct.pools", 1 * MS, 4 * MS,
                         "t.construct", None)
        f.rec._span_done("t.construct", 0, 5 * MS, None, None)
        f.program("feed", 1)
        for i in range(10_000):
            f.rec._span_done("t.step", (10 + i) * MS, (11 + i) * MS,
                             None, i)
        assert len(f.rec.spans()) == 64
        assert not [s for s in f.rec.spans() if s[0] == "t.construct"]
        got = f.rec.setup()
        assert [sp["name"] for sp in got["spans"]
                if sp["name"].startswith("t.")] == [
            "t.construct", "t.construct.pools"]
        assert [r["name"] for r in got["programs"]] == ["jit(feed)"]
        assert got["totals"][None]["programs"] == 1

    def test_the_flag_off_keeps_nothing(self, monkeypatch):
        from paddle_tpu.observability import tracing as tr
        f = _Feed(monkeypatch)
        tr.set_enabled(False)
        try:
            f.program("step", 0, cache="hits")
            with span("t.construct"):
                pass
        finally:
            tr.set_enabled(True)
        got = f.rec.setup()
        assert got["programs"] == [] and got["totals"] == {}
        # (the package's own two clock reads are not the recorder's)
        assert [sp["name"] for sp in got["spans"]] == ["paddle_tpu.import"]
        a, b = paddle._IMPORT_NS
        assert 0 < a < b

    def test_a_real_jit_under_a_span_is_named(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.observability import tracing as tr

        def _only_traced_here(x):
            return x * 3 + 1

        rec = tr.recorder()
        assert rec._listening       # on since the first import that asked
        with span("t.construct"):
            jax.jit(_only_traced_here)(jnp.ones(3)).block_until_ready()
        mine = [r for r in rec.programs()
                if r["name"] == "jit(_only_traced_here)"]
        assert len(mine) == 1
        r = mine[0]
        assert r["span"] == "t.construct" and r["step"] is None
        assert r["trace_ns"] > 0 and r["lower_ns"] > 0 \
            and r["compile_ns"] > 0
        assert r["cache"] in ("hit", "miss", "off")
        assert r["start_ns"] < r["end_ns"] <= time.perf_counter_ns()
        assert rec.program_totals()["t.construct"]["programs"] >= 1
