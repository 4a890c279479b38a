"""observability.tracing: percentile-from-cumulative-buckets math (exact
on synthetic distributions), span-event ordering/monotonicity under a
seeded join/leave serving trace, chrome-trace round-trip via
load_profiler_result with host-span correlation, terminal events for
refused/overloaded/timeout requests, the ring buffer + background
exporter, and trainer step-phase spans."""

import json
import threading

import numpy as np
import pytest

from paddle_tpu import serving as srv
from paddle_tpu.observability import Histogram, Registry
from paddle_tpu.observability import tracing as tr
from paddle_tpu.ops.pallas_ragged import ragged_tile_tokens
from paddle_tpu.profiler import load_profiler_result


@pytest.fixture(autouse=True)
def _clean_recorder():
    tr.recorder().clear()
    yield
    tr.recorder().clear()
    tr.set_enabled(True)


# ---------------------------------------------------------------- percentiles

class TestPercentile:
    def test_exact_on_bucket_bounds(self):
        # 100 observations at 1.0 and 100 at 2.0 on bounds (1,2,4):
        # p50 interpolates to exactly 1.0, p100 to exactly 2.0
        h = Histogram(buckets=(1.0, 2.0, 4.0))
        for _ in range(100):
            h.observe(1.0)
        for _ in range(100):
            h.observe(2.0)
        assert tr.percentile(h, 50) == pytest.approx(1.0)
        assert tr.percentile(h, 100) == pytest.approx(2.0)
        # p75: target=150 lands mid-bucket (1,2] -> 1 + (150-100)/100
        assert tr.percentile(h, 75) == pytest.approx(1.5)

    def test_uniform_interpolation(self):
        # uniform mass in one bucket: quantiles scale linearly
        h = Histogram(buckets=(0.0, 10.0))
        for _ in range(10):
            h.observe(5.0)
        assert tr.percentile(h, 50) == pytest.approx(5.0)
        assert tr.percentile(h, 90) == pytest.approx(9.0)
        assert tr.percentile(h, 10) == pytest.approx(1.0)

    def test_empty_is_none(self):
        h = Histogram(buckets=(1.0,))
        assert tr.percentile(h, 50) is None
        assert tr.percentiles(h) == {"p50": None, "p90": None, "p99": None}

    def test_inf_bucket_clamps_to_last_finite(self):
        h = Histogram(buckets=(1.0, 2.0))
        h.observe(100.0)   # lands in +Inf bucket
        assert tr.percentile(h, 99) == pytest.approx(2.0)

    def test_invalid_q_raises(self):
        h = Histogram(buckets=(1.0,))
        with pytest.raises(ValueError):
            tr.percentile(h, 101)

    def test_snapshot_series_form(self):
        # the snapshot dict shape ({counts, count}) + explicit buckets
        h = Histogram(buckets=(1.0, 2.0))
        for _ in range(4):
            h.observe(1.0)
        series = {"counts": list(h._counts), "count": h.count}
        assert tr.percentile(series, 100, buckets=h.buckets) == \
            pytest.approx(1.0)
        with pytest.raises(ValueError):
            tr.percentile(series, 50)   # buckets required

    def test_slo_summary_shape(self):
        reg = Registry()
        h = reg.histogram("serving.engine.ttft_seconds", buckets=(1.0, 2.0))
        h.observe(1.0)
        s = tr.slo_summary(["serving.engine.ttft_seconds"], reg=reg)
        row = s["serving.engine.ttft_seconds"]
        assert row["count"] == 1
        assert row["mean"] == pytest.approx(1.0)
        assert set(row) == {"count", "mean", "p50", "p90", "p99"}


class TestSloEdgeCases:
    """ISSUE 11 satellite: slo_summary on degenerate histograms —
    empty, single-bucket, and everything-in-+Inf."""

    def test_empty_histogram_reports_count_zero_none_quantiles(self):
        reg = Registry()
        reg.histogram("slo.empty", buckets=(1.0, 2.0))
        s = tr.slo_summary(("slo.empty",), reg=reg)
        assert s["slo.empty"] == {"count": 0, "mean": None, "p50": None,
                                  "p90": None, "p99": None}

    def test_single_bucket_interpolates_from_zero(self):
        h = Histogram(buckets=(2.0,))
        for _ in range(4):
            h.observe(1.0)
        # one finite bucket: quantiles interpolate linearly from the
        # implicit 0 lower edge to the single bound
        assert tr.percentile(h, 25) == pytest.approx(0.5)
        assert tr.percentile(h, 50) == pytest.approx(1.0)
        assert tr.percentile(h, 100) == pytest.approx(2.0)

    def test_all_observations_in_inf_bucket_clamp(self):
        reg = Registry()
        h = reg.histogram("slo.inf", buckets=(0.1, 1.0))
        for _ in range(3):
            h.observe(9.9)
        s = tr.slo_summary(("slo.inf",), reg=reg)["slo.inf"]
        assert s["count"] == 3
        assert s["mean"] == pytest.approx(9.9)
        # every quantile clamps to the largest finite bound (the
        # Prometheus histogram_quantile convention) — the mean is the
        # only signal the buckets were mis-sized
        assert s["p50"] == s["p90"] == s["p99"] == pytest.approx(1.0)


# ------------------------------------------------------------------ recorder

class TestRecorder:
    def test_event_ordering_monotonic(self):
        rec = tr.TraceRecorder(capacity=4)
        rec.begin("r1")
        for name in ("enqueue", "admit", "token", "token"):
            rec.stamp("r1", name)
        rec.finish("r1", "finish")
        t = rec.trace("r1")
        ts = [e.t_us for e in t.timeline()]
        assert ts == sorted(ts)
        assert [e.name for e in t.timeline()] == \
            ["enqueue", "admit", "token", "token", "finish"]
        assert t.outcome == "finish"

    def test_derived_latencies(self):
        rec = tr.TraceRecorder(capacity=4)
        rec.begin("r")
        rec.stamp("r", "enqueue")
        rec.stamp("r", "admit")
        rec.stamp("r", "token")
        rec.stamp("r", "token")
        rec.stamp("r", "token")
        rec.finish("r", "finish")
        t = rec.trace("r")
        assert t.queue_wait_s() >= 0
        assert t.ttft_s() >= t.queue_wait_s()
        # 3 tokens -> tpot = (last-first)/2
        gap = (t.last("token").t_us - t.first("token").t_us) / 1e6
        assert t.tpot_s() == pytest.approx(gap / 2)
        assert t.e2e_s() >= t.ttft_s()

    def test_unknown_id_stamp_ignored(self):
        rec = tr.TraceRecorder(capacity=4)
        rec.stamp("ghost", "token")
        rec.finish("ghost")
        assert rec.trace("ghost") is None

    def test_ring_eviction_oldest_first(self):
        rec = tr.TraceRecorder(capacity=3)
        for i in range(5):
            rec.begin(i)
            rec.stamp(i, "enqueue")
            rec.finish(i, "finish")
        done = rec.finished()
        assert [t.request_id for t in done] == [2, 3, 4]

    def test_disabled_records_nothing(self):
        rec = tr.TraceRecorder(capacity=4)
        tr.set_enabled(False)
        try:
            assert rec.begin("r") is None
            rec.stamp("r", "enqueue")
            rec.finish("r")
        finally:
            tr.set_enabled(True)
        assert not rec.live() and not rec.finished()

    def test_trace_prefers_live_then_latest_done(self):
        rec = tr.TraceRecorder(capacity=4)
        rec.begin("r")
        rec.stamp("r", "enqueue")
        rec.finish("r", "finish")
        rec.begin("r")           # same id re-submitted
        rec.stamp("r", "enqueue")
        assert rec.trace("r").outcome is None       # the live one
        rec.finish("r", "finish")
        assert rec.trace("r").outcome == "finish"

    def test_background_exporter_jsonl(self, tmp_path):
        rec = tr.TraceRecorder(capacity=16)
        path = str(tmp_path / "traces.jsonl")
        rec.start_exporter(path, interval_s=0.01)
        try:
            for i in range(4):
                rec.begin(i)
                rec.stamp(i, "enqueue")
                rec.stamp(i, "token")
                rec.finish(i, "finish")
        finally:
            rec.stop_exporter()
        lines = [json.loads(ln) for ln in open(path) if ln.strip()]
        assert len(lines) == 4
        assert {r["request_id"] for r in lines} == {0, 1, 2, 3}
        assert all(r["outcome"] == "finish" for r in lines)
        assert all(e["t_us"] for r in lines for e in r["events"])

    def test_exporter_thread_shares_recorder_lock(self):
        # the flush thread must only touch state under the recorder lock
        # (the PT006 discipline): hammer finish() from the main thread
        # while the exporter drains, then verify nothing was lost
        rec = tr.TraceRecorder(capacity=512)
        stop = threading.Event()

        def producer():
            for i in range(200):
                rec.begin(("p", i))
                rec.stamp(("p", i), "enqueue")
                rec.finish(("p", i), "finish")
            stop.set()

        import tempfile
        with tempfile.TemporaryDirectory() as d:
            rec.start_exporter(d + "/t.jsonl", interval_s=0.001)
            th = threading.Thread(target=producer)
            th.start()
            th.join(timeout=10)
            rec.stop_exporter()
            assert stop.is_set()
            lines = [json.loads(ln) for ln in open(d + "/t.jsonl")
                     if ln.strip()]
        assert len(lines) == 200


# ------------------------------------------------- serving-engine integration

def _tiny_engine(**kw):
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    cfg = llama_tiny_config(num_hidden_layers=1)
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("prefill_chunk", 4)
    return srv.ServingEngine(LlamaForCausalLM(cfg), **kw), cfg


@pytest.mark.slow
class TestEngineTracing:
    def test_seeded_join_leave_trace_timeline(self):
        eng, cfg = _tiny_engine()
        rng = np.random.RandomState(0)
        for i in range(3):
            eng.add_request(rng.randint(0, cfg.vocab_size, 5).astype(
                np.int32), max_new_tokens=3, request_id=i)
        eng.run_to_completion()
        done = {t.request_id: t for t in tr.recorder().finished("request")}
        assert set(done) == {0, 1, 2}
        for t in done.values():
            names = [e.name for e in t.timeline()]
            # monotonic timestamps, canonical order, terminal last
            ts = [e.t_us for e in t.timeline()]
            assert ts == sorted(ts)
            assert names[0] == "enqueue" and names[-1] == "finish"
            assert names.index("admit") < names.index("prefill_chunk") \
                < names.index("token")
            assert t.count("token") == 3
            assert t.outcome == "finish"
            # every request produced the full SLO set
            assert t.queue_wait_s() is not None
            assert t.ttft_s() is not None
            assert t.tpot_s() is not None
            assert t.e2e_s() is not None
        # SLO percentiles come out of serving.slo()
        s = srv.slo()
        assert s["serving.engine.ttft_seconds"]["count"] >= 3
        assert s["serving.engine.ttft_seconds"]["p99"] is not None

    def test_chrome_export_round_trip_and_host_correlation(self, tmp_path):
        eng, cfg = _tiny_engine()
        eng.add_request(np.arange(5, dtype=np.int32) % cfg.vocab_size,
                        max_new_tokens=2, request_id="rt")
        eng.run_to_completion()
        path = str(tmp_path / "trace.json")
        n = tr.recorder().export_chrome_trace(path)
        events = load_profiler_result(path)
        assert len(events) == n > 0
        req_trace = tr.recorder().trace("rt")
        # the request's lifetime span carries its span id in the
        # observability.span naming convention
        spans = [e for e in events
                 if e["name"].endswith(f"[span={req_trace.span_id}]")]
        assert len(spans) == 1 and spans[0]["ph"] == "X"
        assert spans[0]["args"]["outcome"] == "finish"
        # phase rows nest inside the lifetime span
        phases = {e["name"] for e in events if e.get("cat") == "phase"}
        assert {"queue", "prefill", "decode"} <= phases
        # token stamps carry the engine step that produced them: the
        # one key that joins them to the step row and to the profiler's
        # `serving.engine.step` events (argument `step`)
        toks = [e for e in events if e["name"] == "token"]
        assert toks and all("step" in e["args"] for e in toks)
        rows = {e["args"]["seq"] for e in events
                if e["name"] == "serving.engine.step"}
        assert {e["args"]["step"] for e in toks} <= rows

    def test_refused_request_appears_in_timeline(self):
        from paddle_tpu import resilience as res
        from paddle_tpu.inference import Config
        cfg = Config()
        cfg.set_admission(max_inflight=1, queue_timeout_s=0.0)
        eng, mcfg = _tiny_engine(config=cfg, max_slots=1)
        eng.add_request(np.arange(4, dtype=np.int32) % mcfg.vocab_size,
                        max_new_tokens=2, request_id="a")
        with pytest.raises(res.Overloaded):
            eng.add_request(np.arange(4, dtype=np.int32) % mcfg.vocab_size,
                            max_new_tokens=2, request_id="b")
        t = tr.recorder().trace("b")
        assert t is not None and t.outcome == "refused"
        assert [e.name for e in t.timeline()] == ["enqueue", "refused"]
        eng.run_to_completion()
        assert tr.recorder().trace("a").outcome == "finish"

    def test_queue_timeout_stamps_overloaded(self):
        from paddle_tpu import resilience as res
        from paddle_tpu.inference import Config
        cfg = Config()
        cfg.set_admission(max_inflight=1, queue_timeout_s=1e-4)
        eng, mcfg = _tiny_engine(config=cfg, max_slots=1)
        eng.add_request(np.arange(4, dtype=np.int32) % mcfg.vocab_size,
                        max_new_tokens=4, request_id="x")
        eng.add_request(np.arange(4, dtype=np.int32) % mcfg.vocab_size,
                        max_new_tokens=4, request_id="y")
        import time
        time.sleep(0.01)
        results = eng.run_to_completion()
        assert isinstance(results["y"], res.Overloaded)
        t = tr.recorder().trace("y")
        assert t.outcome == "overloaded"
        assert t.first("token") is None   # never decoded
        assert "waited_s" in t.last("overloaded").meta

    def test_deadline_timeout_stamps_terminal(self):
        from paddle_tpu import resilience as res
        eng, mcfg = _tiny_engine()
        eng.add_request(np.arange(6, dtype=np.int32) % mcfg.vocab_size,
                        max_new_tokens=8, deadline_s=1e-6,
                        request_id="d")
        results = eng.run_to_completion()
        assert isinstance(results["d"], res.TimeoutResult)
        t = tr.recorder().trace("d")
        assert t.outcome == "timeout"

    def test_tracing_off_engine_still_exact(self):
        tr.set_enabled(False)
        try:
            eng, mcfg = _tiny_engine()
            eng.add_request(np.arange(5, dtype=np.int32) % mcfg.vocab_size,
                            max_new_tokens=3, request_id=0)
            results = eng.run_to_completion()
            assert results[0].shape == (3,)
            assert tr.recorder().trace(0) is None
        finally:
            tr.set_enabled(True)


PHASES = ["serving.engine." + p for p in
          ("admit", "build", "launch", "sync", "sample", "account")]


def _inside_and_disjoint(step, phases):
    """`phases` [(start, end)] in order: inside `step`, none overlapping."""
    edge = step[0]
    for a, b in phases:
        assert edge <= a <= b
        edge = b
    assert edge <= step[1]


class TestStepTimeline:
    """ISSUE 24: one step record per `ServingEngine.step()`, its phase
    spans on the profiler's clock, request stamps keyed by the step."""

    def _script(self, eng, cfg, ragged_seed=0):
        """Seeded join/leave: three requests at once into two slots (one
        waits), a long prompt ahead of a short one. Returns what every
        `step()` returned and the allocator's page count after it."""
        rng = np.random.RandomState(ragged_seed)
        for rid, n in (("long", 14), ("short", 3), ("late", 6)):
            eng.add_request(rng.randint(0, cfg.vocab_size, n).astype(
                np.int32), max_new_tokens=3, request_id=rid)
        outs = []
        while eng.has_work():
            out = eng.step()
            outs.append((out, eng.allocator.stats()["pages_used"]))
        return outs

    def test_one_record_per_step_with_counts(self):
        eng, cfg = _tiny_engine(prefix_sharing=False,
                                enable_prefix_cache=False)
        outs = self._script(eng, cfg)
        steps = tr.recorder().steps()
        assert [s["seq"] for s in steps] == list(range(1, len(outs) + 1))
        rows = eng.max_slots + eng.prefill_chunk
        tiles = -(-rows // ragged_tile_tokens(rows, eng._q_rep,
                                              eng._q_dtype))
        queued_on = 0   # pages in use when the call before returned
        for s, (out, pages_used) in zip(steps, outs):
            assert s["name"] == "serving.engine.step"
            _inside_and_disjoint((s["start_ns"], s["end_ns"]),
                                 [(a, b) for _, a, b in s["phases"]])
            names = [n for n, _, _ in s["phases"]]
            assert names == PHASES
            assert s["decode_rows"] == out["decoded"]
            assert s["prefill_rows"] == out["prefill_tokens"]
            assert s["admitted"] == out["admitted"]
            assert s["finished"] == out["finished"]
            assert s["pool_pages_used"] == pages_used
            assert s["pool_pages_total"] == eng.num_pages - 1
            # the record describes the launch this call RETIRED,
            # queued by the call before (ISSUE 34), like the counts
            # `step()` returns. Nothing shared: its live pages are
            # the allocator's own count when it was queued (a
            # request that ended in that call had no row in it)
            assert s["pages_live"] == queued_on
            queued_on = pages_used
            # the kernel fetches a sequence's pages once for each
            # query tile that holds rows of it: decode slots sit in
            # one tile each, the chunk may span several
            assert s["pages_live"] <= s["pages_visited"] \
                <= tiles * s["pages_live"]
            if not out["prefill_tokens"]:
                assert s["pages_visited"] == s["pages_live"]
            # a visit brings the page for a block of KV heads (here
            # all of them: `hbm_accounting` says the kernel's choice)
            kv = eng._kv_geom[0]
            hb = int(eng.hbm_accounting()["attn_head_block"])
            assert hb == kv > 1
            assert s["attn_block_visits"] \
                == s["pages_visited"] * kv // hb
            # a decode row's visits run on the window of rows that
            # holds its own; a chunk's on the tile's
            assert eng.hbm_accounting()["attn_narrow_rows"] == 8
            assert s["attn_narrow_updates"] <= s["pages_visited"]
            if not out["prefill_tokens"]:
                # a launch without a chunk is ONE tile of the two decode
                # rows, no wider than the window: nothing is narrower
                assert s["rows_computed"] in (0, eng.max_slots)
                assert s["attn_narrow_updates"] == 0
            else:
                assert s["rows_computed"] == rows
            assert s["preempted"] == s["cow_pages"] == 0
        # two slots, three requests: one waited, then everyone left
        assert steps[0]["live"] == 2 and steps[0]["waiting"] == 1
        assert sum(s["admitted"] for s in steps) == 3
        assert sum(s["finished"] for s in steps) == 3
        # steps() hands out copies
        steps[0]["phases"].clear()
        assert tr.recorder().steps()[0]["phases"]

    @pytest.mark.parametrize("kw", [
        {}, {"spec_decode": 2}, {"page_size": 8, "prefill_chunk": 12}],
        ids=["rows_of_one", "spec_rows", "chunk_of_12_in_pages_of_8"])
    def test_append_runs_is_the_devices_table(self, kw, monkeypatch):
        """The step record's `append_runs` is taken on the host by the
        rule the device makes its run table by: every launch's count
        equals the live runs of the table the jitted step derives from
        the same row tables, and the record of the call that retires
        the launch carries it."""
        import jax.numpy as jnp
        from paddle_tpu.ops.fused import append_run_table
        eng, cfg = _tiny_engine(prefix_sharing=False, **kw)
        B, R, C = eng.max_slots, 1 + eng.spec_k, eng.prefill_chunk
        tile = eng._append_tile
        bound = B * R + -(-C // tile) + 1
        seq_start = jnp.asarray(np.append(np.arange(B) * R, B * R),
                                jnp.int32)
        counted, rows = [], []
        build = eng._build_unified

        def spy(*a):
            out = build(*a)
            _, _, num_tokens, _, _, tok_page, tok_off = out[0]
            table = np.asarray(append_run_table(
                seq_start, jnp.asarray(num_tokens), jnp.asarray(tok_page),
                jnp.asarray(tok_off), tile=tile,
                max_runs=bound)).reshape(5, bound)
            assert out[-1]["append_runs"] == (table[1] > 0).sum()
            assert table[1].sum() == num_tokens.sum()
            counted.append(out[-1]["append_runs"])
            rows.append(int(num_tokens.sum()))
            return out

        monkeypatch.setattr(eng, "_build_unified", spy)
        self._script(eng, cfg)
        steps = tr.recorder().steps()
        # every launch retires into one record (the call after the one
        # that built it, or a retire forced in between: the count adds)
        assert sum(s["append_runs"] for s in steps) == sum(counted)
        # a run holds a row at least and a tile of rows at most
        assert all(c <= n <= c * tile for c, n in zip(counted, rows))
        assert any(c < n for c, n in zip(counted, rows))

    def test_request_phases_sum_to_ttft(self):
        eng, cfg = _tiny_engine(prefix_sharing=False)
        self._script(eng, cfg)
        done = {t.request_id: t for t in tr.recorder().finished("request")}
        assert set(done) == {"long", "short", "late"}
        for t in done.values():
            parts = [t.queue_wait_s(), t.prefill_wait_s(),
                     t.prefill_run_s()]
            assert None not in parts
            # exact on the stamps' own integer microseconds
            marks = [t.first(n).t_us for n in
                     ("enqueue", "admit", "prefill_chunk", "token")]
            assert marks == sorted(marks)
            assert sum(b - a for a, b in zip(marks, marks[1:])) == \
                marks[-1] - marks[0]
            assert sum(parts) == pytest.approx(t.ttft_s(), abs=1e-9)
            # every chunk is stamped, each with the step that ran it
            chunks = [e for e in t.timeline() if e.name == "prefill_chunk"]
            assert sum(e.meta["tokens"] for e in chunks) == \
                t.meta["prompt_len"]
            assert all("step" in e.meta for e in t.timeline()
                       if e.name != "enqueue")
            assert "prefill_wait_s" in t.to_dict()
        # admitted in the same step as the 14-token prompt (4 chunks of
        # 4), "short" holds a slot and waits behind them
        long_chunks = [e.meta["step"] for e in done["long"].timeline()
                       if e.name == "prefill_chunk"]
        first_short = done["short"].first("prefill_chunk")
        assert first_short.meta["step"] == long_chunks[-1] + 1
        assert done["short"].first("admit").meta["step"] == long_chunks[0]
        assert done["short"].prefill_wait_s() > 0
        assert done["short"].prefill_wait_s() > \
            done["long"].prefill_wait_s()

    def test_compiles_counted_in_the_step_that_compiled(self):
        eng, cfg = _tiny_engine()
        rng = np.random.RandomState(0)
        eng.add_request(rng.randint(0, cfg.vocab_size, 5).astype(np.int32),
                        max_new_tokens=12, request_id="c")
        for _ in range(4):
            eng.step()
        warm = tr.recorder().steps()
        # each program's first launch: the first of the prompt's two
        # chunks, then the first launch of decode rows alone
        assert [s["compiles"] >= 1 for s in warm] == \
            [True, False, True, False]
        assert eng.reconfigure(prefill_chunk=8)
        eng.step()
        eng.step()
        after = tr.recorder().steps()[len(warm):]
        assert after[0]["compiles"] >= 1 and after[1]["compiles"] == 0

    def test_flags_off_records_nothing_tokens_unchanged(self):
        from paddle_tpu import observability as obs

        def tokens():
            import paddle_tpu as paddle
            paddle.seed(11)             # the same weights both times
            eng, cfg = _tiny_engine()
            rng = np.random.RandomState(3)
            for i in range(3):
                eng.add_request(rng.randint(0, cfg.vocab_size, 6).astype(
                    np.int32), max_new_tokens=4, request_id=i)
            res = eng.run_to_completion()
            return [res[i].tolist() for i in range(3)]

        want = tokens()
        rec = tr.recorder()
        assert rec.steps() and rec.spans()
        rec.clear()
        obs.set_enabled(False)
        tr.set_enabled(False)
        try:
            got = tokens()
        finally:
            obs.set_enabled(True)
            tr.set_enabled(True)
        assert got == want
        assert rec.steps() == [] and rec.spans() == []
        assert rec.finished() == [] and rec.live() == []
        assert rec.counters() == {}

    def test_profiler_trace_holds_the_step_timeline(self, tmp_path):
        """A `jax.profiler` trace on the CPU: the step and its six
        phases are host events under their plain names, nested and
        disjoint, and agree with the in-memory records."""
        import glob
        import jax
        from jax.profiler import ProfileData
        eng, cfg = _tiny_engine()
        rng = np.random.RandomState(0)
        eng.add_request(rng.randint(0, cfg.vocab_size, 6).astype(np.int32),
                        max_new_tokens=6, request_id="p")
        eng.step()                      # compile outside the trace
        n0 = len(tr.recorder().steps())
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for _ in range(3):
                eng.step()
        finally:
            jax.profiler.stop_trace()
        records = tr.recorder().steps()[n0:]
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        names = {"serving.engine.step", *PHASES}
        events = []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        events.append((e.start_ns, e.start_ns
                                       + e.duration_ns, e.name,
                                       dict(e.stats)))
        events.sort()
        parents = [e for e in events if e[2] == "serving.engine.step"]
        assert [int(e[3]["step"]) for e in parents] == \
            [r["seq"] for r in records]
        for (a, b, _, _), rec in zip(parents, records):
            inner = [e for e in events if a <= e[0] and e[1] <= b
                     and e[2] != "serving.engine.step"]
            assert [e[2] for e in inner] == PHASES == \
                [n for n, _, _ in rec["phases"]]
            _inside_and_disjoint((a, b), [(e[0], e[1]) for e in inner])
        assert len(events) == len(parents) * (1 + len(PHASES))


class TestSpanPrimitive:
    def test_span_records_parent_and_step(self):
        from paddle_tpu.observability import span
        rec = tr.recorder()
        with span("outer", step=7, note="x"):
            with span("inner"):
                pass
        with span("alone"):
            pass
        got = rec.spans()
        assert [(n, p, s) for n, _, _, p, s in got] == [
            ("inner", "outer", 7), ("outer", None, 7),
            ("alone", None, None)]
        (_, a, b, _, _), (_, c, d, _, _) = got[0], got[1]
        assert c <= a <= b <= d
        assert rec.steps() == []        # no step was opened

    def test_spans_of_another_thread_do_not_nest(self):
        from paddle_tpu.observability import span
        seen = []

        def other():
            with span("other.thread"):
                pass
            seen.append(True)

        with span("main.thread", step=1):
            th = threading.Thread(target=other)
            th.start()
            th.join(timeout=10)
        assert seen and not th.is_alive()
        by_name = {n: (p, s) for n, _, _, p, s in tr.recorder().spans()}
        assert by_name["other.thread"] == (None, None)

    def test_step_ring_is_bounded_and_separate(self):
        rec = tr.TraceRecorder(capacity=1)
        kept = tr.STEPS_PER_SLOT        # step records a request-ring slot
        for seq in range(1, kept + 3):
            rec.open_step(seq, "s")
            rec.begin(seq)
            rec.stamp(seq, "token")
            rec.close_step({"decode_rows": seq})
        last = kept + 2
        assert [s["seq"] for s in rec.steps()] == list(range(3, last + 1))
        assert rec.steps()[-1]["decode_rows"] == last
        # stamps inside an open step carry it; outside they do not
        assert rec.trace(last).first("token").meta == {"step": last}
        rec.stamp(last, "late")
        assert rec.trace(last).first("late").meta is None
        assert len(rec.live()) == last  # the request table is its own

    def test_chrome_export_has_the_step_row(self, tmp_path):
        rec = tr.TraceRecorder(capacity=4)
        rec.open_step(9, "eng.step")
        rec._span_done("eng.a", 2_000, 5_000, "eng.step", 9)
        rec._span_done("eng.b", 6_000, 9_000, "eng.step", 9)
        rec._span_done("eng.deeper", 6_500, 7_000, "eng.b", 9)
        rec._span_done("eng.step", 1_000, 10_000, None, 9)
        rec.close_step({"decode_rows": 3})
        (st,) = rec.steps()
        assert (st["start_ns"], st["end_ns"]) == (1_000, 10_000)
        assert st["phases"] == [("eng.a", 2_000, 5_000),
                                ("eng.b", 6_000, 9_000)]   # direct children
        path = str(tmp_path / "steps.json")
        assert rec.export_chrome_trace(path) == 3
        events = load_profiler_result(path)
        assert [(e["name"], e["ts"], e["dur"], e["tid"]) for e in events] \
            == [("eng.step", 1, 9, 0), ("eng.a", 2, 3, 0),
                ("eng.b", 6, 3, 0)]
        assert events[0]["args"]["decode_rows"] == 3
        assert events[0]["args"]["seq"] == events[1]["args"]["step"] == 9

    def test_trainer_loop_spans(self, tmp_path):
        """`run_pretrain.run` opens data-wait, step and save spans
        through the one primitive, keyed by the optimizer step."""
        import random
        import signal
        from paddle_tpu.trainer import run_pretrain
        rng = random.Random(0)
        corpus = tmp_path / "corpus.txt"
        corpus.write_text(" ".join(rng.choice(["a", "bb", "ccc", "dd"])
                                   for _ in range(3000)))
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(json.dumps({
            "model": {"preset": "tiny", "num_hidden_layers": 1},
            "data": {"corpus": str(corpus), "vocab_size": 270},
            "seq_len": 16, "global_batch": 8, "max_steps": 3,
            "parallel": {"dp": 8},      # conftest's eight CPU devices
            "save_interval": 2, "remat": "none",
            "output_dir": str(tmp_path / "out")}))
        prev = signal.getsignal(signal.SIGTERM)
        try:
            assert run_pretrain.run(
                run_pretrain._load_config(str(cfg_path))) == 0
        finally:
            signal.signal(signal.SIGTERM, prev)
        # (the loop's: `trainer.build` and its sections belong to no step)
        got = [(n, p, s) for n, _, _, p, s in tr.recorder().spans()
               if n.startswith("trainer.") and s is not None]
        assert got == [
            ("trainer.data_wait", None, 1), ("trainer.step", None, 1),
            ("trainer.data_wait", None, 2), ("trainer.step", None, 2),
            ("trainer.save", None, 2),
            ("trainer.data_wait", None, 3), ("trainer.step", None, 3),
            ("trainer.save", None, 3)]


class TestServingStampRoundTrip:
    """PR-10 stamps (prefix_hit, preempted/resumed, draft/verify_accept)
    recorded on a RequestTrace survive the chrome-trace export."""

    def test_recorder_level_roundtrip(self, tmp_path):
        rec = tr.recorder()
        rec.begin("r", prompt_len=12, max_new_tokens=4, priority=2,
                  tenant="acme")
        rec.stamp("r", "enqueue")
        rec.stamp("r", "admit", slot=0)
        rec.stamp("r", "prefix_hit", tokens=8, pages=2)
        rec.stamp("r", "token")
        rec.stamp("r", "preempted", decoded=1)
        rec.stamp("r", "resumed", slot=1, decoded=1)
        rec.stamp("r", "draft", tokens=3)
        rec.stamp("r", "verify_accept", drafted=3, accepted=2)
        rec.stamp("r", "token")
        rec.finish("r", "finish")
        t = rec.trace("r")
        names = [e.name for e in t.timeline()]
        for name in ("prefix_hit", "preempted", "resumed", "draft",
                     "verify_accept"):
            assert name in names
        assert names.index("preempted") < names.index("resumed")
        assert t.first("prefix_hit").meta["tokens"] == 8
        assert t.first("verify_accept").meta == {"drafted": 3,
                                                 "accepted": 2}
        path = str(tmp_path / "trace.json")
        n = rec.export_chrome_trace(path)
        events = load_profiler_result(path)
        assert len(events) == n
        by_name = {e["name"]: e for e in events
                   if e["name"] in ("prefix_hit", "preempted", "resumed",
                                    "draft", "verify_accept")}
        assert set(by_name) == {"prefix_hit", "preempted", "resumed",
                                "draft", "verify_accept"}
        assert by_name["prefix_hit"]["args"]["tokens"] == 8
        assert by_name["verify_accept"]["args"]["accepted"] == 2


class TestCounterTracks:
    """ISSUE 11 satellite: gauge samples become ph:"C" counter events in
    the chrome export (the PR-6 exporter dropped gauges entirely)."""

    def test_counter_roundtrip(self, tmp_path):
        rec = tr.recorder()
        rec.counter("pool.util", 0.25, t_us=100)
        rec.counter("pool.util", 0.75, t_us=200)
        rec.counter("hbm.bytes", 4096, t_us=150)
        assert rec.counters()["pool.util"] == [(100, 0.25), (200, 0.75)]
        path = str(tmp_path / "t.json")
        n = rec.export_chrome_trace(path)
        events = load_profiler_result(path)
        assert len(events) == n == 3
        cs = [e for e in events if e["ph"] == "C"]
        assert {(e["name"], e["ts"], e["args"]["value"]) for e in cs} \
            == {("pool.util", 100, 0.25), ("pool.util", 200, 0.75),
                ("hbm.bytes", 150, 4096.0)}
        assert all(e["cat"] == "counter" for e in cs)

    def test_sample_gauges_reads_registry(self):
        reg = Registry()
        reg.gauge("g.a", "a").set(3.5)
        reg.gauge("g.b", "b").set(7)
        reg.counter("g.c", "not a gauge").inc()
        rec = tr.recorder()
        # missing names and non-gauges are skipped, not errors
        assert rec.sample_gauges(("g.a", "g.b", "g.c", "g.nope"),
                                 reg=reg) == 2
        got = rec.counters()
        assert [v for _, v in got["g.a"]] == [3.5]
        assert [v for _, v in got["g.b"]] == [7.0]
        assert "g.c" not in got and "g.nope" not in got

    def test_counter_disabled_is_noop(self):
        tr.set_enabled(False)
        try:
            rec = tr.recorder()
            rec.counter("x", 1.0)
            assert rec.sample_gauges(("x",)) == 0
            assert rec.counters() == {}
        finally:
            tr.set_enabled(True)

    def test_counter_track_bounded_by_capacity(self):
        rec = tr.TraceRecorder(capacity=4)
        for i in range(10):
            rec.counter("x", float(i), t_us=i)
        assert [v for _, v in rec.counters()["x"]] == [6.0, 7.0, 8.0, 9.0]

    def test_clear_drops_counters(self):
        rec = tr.recorder()
        rec.counter("x", 1.0)
        rec.clear()
        assert rec.counters() == {}


@pytest.mark.slow
class TestEngineCounterTracks:
    def test_engine_step_exports_hbm_counter_tracks(self, tmp_path):
        eng, cfg = _tiny_engine()
        eng.add_request(np.arange(5, dtype=np.int32) % cfg.vocab_size,
                        max_new_tokens=4, request_id="c")
        eng.run_to_completion()
        acct = eng.hbm_accounting()
        assert acct["weights_bytes"] > 0
        assert acct["page_pool_bytes"] > 0
        assert acct["ledger_tokens"] > 0
        # the live ledger and the analytical budget agree well inside
        # the observatory's 25% acceptance band on this seeded trace
        ratio = (acct["bytes_per_token_measured"]
                 / acct["bytes_per_token_model"])
        assert 0.75 < ratio < 1.25
        path = str(tmp_path / "t.json")
        tr.recorder().export_chrome_trace(path)
        events = load_profiler_result(path)
        series = {}
        for e in events:
            if e["ph"] == "C":
                series.setdefault(e["name"], []).append(
                    e["args"]["value"])
        for name in ("serving.engine.pages_used",
                     "serving.engine.page_utilization",
                     "serving.engine.page_fragmentation",
                     "serving.engine.hbm_weights_bytes",
                     "serving.engine.hbm_page_pool_bytes",
                     "serving.engine.bytes_per_token_measured"):
            assert name in series, name
        # one sample per engine step, constant residency throughout
        assert set(series["serving.engine.hbm_weights_bytes"]) \
            == {acct["weights_bytes"]}
        assert set(series["serving.engine.hbm_page_pool_bytes"]) \
            == {acct["page_pool_bytes"]}
        # utilization rises from empty, then drains at finish down to
        # the pages the prefix cache retains for future prompt hits
        util = series["serving.engine.page_utilization"]
        assert max(util) > 0 and util[-1] < max(util)


@pytest.mark.slow
class TestEngineServingStamps:
    def test_prefix_hit_and_spec_stamps(self, tmp_path):
        eng, cfg = _tiny_engine(spec_decode=3, prefix_sharing=False)
        rng = np.random.RandomState(7)
        prompt = rng.randint(0, cfg.vocab_size, 12).astype(np.int32)
        eng.add_request(prompt, max_new_tokens=3, request_id="warm")
        eng.run_to_completion()
        eng.add_request(prompt.copy(), max_new_tokens=3, request_id="hit",
                        tenant="acme")
        eng.run_to_completion()
        t = tr.recorder().trace("hit")
        hit = t.first("prefix_hit")
        assert hit is not None and hit.meta["tokens"] >= 8
        assert t.meta.get("tenant") == "acme"
        # spec decode on a repetitive prompt stamps draft/verify_accept
        rep = np.asarray([5, 9, 5, 9, 5, 9, 5, 9], np.int32)
        eng.add_request(rep, max_new_tokens=6, request_id="spec")
        eng.run_to_completion()
        ts = tr.recorder().trace("spec")
        if ts.first("draft") is not None:       # model-dependent drafts
            assert ts.first("draft").meta["tokens"] >= 1
        # chrome export round-trips every stamped event
        path = str(tmp_path / "t.json")
        n = tr.recorder().export_chrome_trace(path)
        events = load_profiler_result(path)
        assert len(events) == n > 0
        assert any(e["name"] == "prefix_hit" for e in events)

    def test_preempt_resume_stamps(self):
        from paddle_tpu.serving.scheduler import DECODE
        eng, cfg = _tiny_engine(max_slots=1)
        rng = np.random.RandomState(9)
        p1 = rng.randint(0, cfg.vocab_size, 5).astype(np.int32)
        p2 = rng.randint(0, cfg.vocab_size, 5).astype(np.int32)
        r1 = eng.add_request(p1, max_new_tokens=8, request_id="low",
                             priority=0)
        while r1.state != DECODE or len(r1.tokens) < 1:
            eng.step()
        eng.add_request(p2, max_new_tokens=2, request_id="high",
                        priority=3)
        eng.run_to_completion()
        t = tr.recorder().trace("low")
        names = [e.name for e in t.timeline()]
        assert "preempted" in names and "resumed" in names
        assert names.index("preempted") < names.index("resumed")
        assert t.first("preempted").meta["decoded"] >= 1
        # no re-prefill on resume: every prefill_chunk stamp precedes
        # the preemption
        pre = names.index("preempted")
        assert all(i < pre for i, nm in enumerate(names)
                   if nm == "prefill_chunk")
        assert tr.recorder().trace("high").meta.get("priority") == 3


# ---------------------------------------------------------- trainer phases

@pytest.mark.slow
class TestTrainerTracing:
    def test_step_phase_spans(self):
        from paddle_tpu import nn
        from paddle_tpu.trainer.trainer import Trainer, TrainingArguments

        class DS:
            def __len__(self):
                return 4

            def __getitem__(self, i):
                x = np.random.RandomState(i).randn(4).astype("float32")
                return x, x.sum(keepdims=True).astype("float32")

        t = Trainer(model=nn.Linear(4, 1),
                    args=TrainingArguments(
                        max_steps=2, per_device_train_batch_size=2,
                        logging_steps=1),
                    train_dataset=DS(), criterion=nn.MSELoss())
        t.train()
        done = tr.recorder().finished("train")
        assert len(done) == 2
        for st in done:
            names = [e.name for e in st.timeline()]
            assert names == ["data", "fwd", "bwd", "opt", "finish"]
            assert all(e.meta and e.meta.get("dur_us", 0) >= 0
                       for e in st.timeline()[:-1])
            assert st.outcome == "finish"
        assert done[0].meta["step"] == 1
        # train-step traces must NOT pollute the serving SLO histograms
        # (kind guard): export still renders them as chrome rows
        import tempfile
        with tempfile.TemporaryDirectory() as d:
            n = tr.recorder().export_chrome_trace(d + "/t.json")
            evs = load_profiler_result(d + "/t.json")
        assert any(e["name"].startswith("train:train-step-")
                   for e in evs)
        # phase events carry explicit durations -> exported as X spans
        assert any(e["ph"] == "X" and e["name"] == "fwd" for e in evs)
