"""paddle_tpu.observability.tracing — per-request span timelines and SLO
histograms (ISSUE 6 tentpole).

The metrics registry (``observability``) answers "how much / how fast in
aggregate"; this module answers "what happened to request 17": a
`TraceRecorder` keyed by request id collects monotonic `TraceEvent`
stamps from the serving path (enqueue → admit → prefill chunks → one
`token` event per decode step → finish/timeout/overloaded/refused, plus
copy-on-write page events) and from the trainer (data/fwd/bwd/opt phase
events per optimizer step), so one timeline covers both workloads.

Terminal events derive the serving SLOs a serving tier is operated by
and observe them into registry histograms:

  - ``serving.engine.queue_wait_seconds``  (enqueue → admit)
  - ``serving.engine.ttft_seconds``        (enqueue → first token)
  - ``serving.engine.tpot_seconds``        (inter-token, steady decode)
  - ``serving.engine.e2e_seconds``         (enqueue → completion)

`percentile()` / `percentiles()` compute p50/p90/p99 from the cumulative
bucket counts (linear interpolation within the landing bucket — exact
whenever observations sit on bucket bounds), and `slo_summary()` renders
the standard serving table. `TraceRecorder.export_chrome_trace` writes
the timelines as chrome-trace JSON whose lifetime spans share the
``name[span=<pid>-<seq>]`` convention of the legacy host-profiler events
`observability.span` emits.

The recorder also keeps what `observability.span` records — every host
span as ``(name, start_ns, end_ns, parent, step)`` — and one STEP RECORD
per `ServingEngine.step()` (`open_step` / `close_step`): the step's
phases and the counts taken where the work happens (`STEP_COUNTS`), in
a ring of their own. The step is the one join key: each stamp made
inside a step carries ``step=<seq>``, the step's spans carry it in
memory, and its profiler event (`jax.profiler.TraceAnnotation`, on the
device trace's clock) carries it as an argument.

And it keeps the SET-UP LEDGER (`TraceRecorder.setup`): one PROGRAM
RECORD for every program jax traces, lowers and compiles or reads from
the compile cache — assembled from the `jax.monitoring` events of the
recorder's one pair of listeners, each with the innermost open span of
the thread that compiled and the open step — and the spans that belong
to no step (`serving.engine.construct`, `trainer.build` and their
children), in a store of their own that step traffic cannot evict.

Overhead contract (same as the metrics layer): every entry point checks
the cached ``FLAGS_request_tracing`` flag object FIRST, so with tracing
off a stamp costs one function call + one attribute test. Gated
alongside the metrics gate in tests/test_observability.py::TestOverhead
(calls made, locks taken, objects kept).

Thread discipline (paddlelint PT006): all recorder state — the live
table, the finished-trace ring, the exporter file handle — is touched
only under ``self._lock``; the optional background flush thread
(`start_exporter`) shares exactly that state and that lock.
"""

from __future__ import annotations

import heapq
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple, Union

from .. import flags as _flags
from . import (DEFAULT_BUCKETS, Histogram, _new_span_id, _open_spans,
               registry)

__all__ = ["TraceEvent", "RequestTrace", "TraceRecorder", "recorder",
           "enabled", "set_enabled", "percentile", "percentiles",
           "slo_summary", "SLO_METRICS", "STEP_COUNTS", "STEPS_PER_SLOT",
           "STEP_COUNTS_BY_KIND", "STEP_COUNTS_MOE", "STEP_COUNTS_LATENT",
           "STEP_COUNTS_EVA", "STEP_COUNTS_LOOP", "STEP_COUNTS_SSM",
           "STEP_COUNTS_MHC", "STEP_COUNTS_SHARED",
           "STEP_COUNTS_DIFFUSION", "STEP_COUNTS_PREFIX",
           "STEP_COUNTS_TAIL", "PROGRAMS_KEPT", "SETUP_SPANS_KEPT"]

_FLAG = _flags._registry["FLAGS_request_tracing"]


def enabled() -> bool:
    """Whether trace stamps are recorded (FLAGS_request_tracing)."""
    return _FLAG.value


def set_enabled(on: bool) -> None:
    _flags.set_flags({"FLAGS_request_tracing": bool(on)})


def _now_us() -> int:
    # same clock family as the host profiler's pure-python fallback
    # (perf_counter_ns // 1000), so exported timelines share an epoch
    return time.perf_counter_ns() // 1000


# the spans' clock, where a `jax.monitoring` event is stamped as it
# arrives (jax says how long a stage took, not when it ended)
_now_ns = time.perf_counter_ns


# the four serving SLO histograms; registered here so importing the
# tracing module is what creates them (engine/scheduler only stamp)
SLO_METRICS: Tuple[str, ...] = (
    "serving.engine.queue_wait_seconds",
    "serving.engine.ttft_seconds",
    "serving.engine.tpot_seconds",
    "serving.engine.e2e_seconds",
)
_H_QWAIT = registry().histogram(
    "serving.engine.queue_wait_seconds",
    "enqueue -> admit wait per request", buckets=DEFAULT_BUCKETS)
_H_TTFT = registry().histogram(
    "serving.engine.ttft_seconds",
    "enqueue -> first generated token per request",
    buckets=DEFAULT_BUCKETS)
_H_TPOT = registry().histogram(
    "serving.engine.tpot_seconds",
    "steady-state inter-token latency per request "
    "((last - first token) / (tokens - 1))", buckets=DEFAULT_BUCKETS)
_H_E2E = registry().histogram(
    "serving.engine.e2e_seconds",
    "enqueue -> completion per finished request", buckets=DEFAULT_BUCKETS)


#: the counts of one step record, taken by the engine where the work
#: happens (docs/OBSERVABILITY.md says what each one is). The last,
#: `rows_computed`: the flat rows the retired launch computed, beside
#: the rows it owned (`decode_rows` + `prefill_rows`) — the step's full
#: row count where it carried a prompt's chunk, the decode rows' alone
#: where no prompt was being dispatched
STEP_COUNTS: Tuple[str, ...] = (
    "decode_rows", "prefill_rows", "live", "waiting", "admitted",
    "finished", "preempted", "cow_pages", "pools_in_place", "pages_live",
    "pages_visited", "pool_pages_used", "pool_pages_total",
    "launch_ahead", "rows_dropped", "append_runs", "attn_block_visits",
    "attn_narrow_updates", "rows_computed")
#: more counts where a model keeps two kinds of cache (full layers and
#: sliding-window layers; the plain `pages_*` / `pool_pages_*` are then
#: the sum of both kinds) ...
STEP_COUNTS_BY_KIND: Tuple[str, ...] = (
    "pages_live.full", "pages_live.window", "pages_visited.full",
    "pages_visited.window", "window_pages_freed", "pool_pages_used.full",
    "pool_pages_used.window", "pool_pages_total.full",
    "pool_pages_total.window")
#: ... and where its routed layers hold a share of their experts: taken
#: on the device, returned with the step's logits
STEP_COUNTS_MOE: Tuple[str, ...] = (
    "moe_pairs_routed", "moe_pairs_held", "moe_expert_rows_max",
    "moe_expert_rows_mean", "moe_experts_hit")
#: ... and where the cache holds latent attention's rows: the prefill
#: chunk's KV length after the step (0 without a chunk: what the chunk's
#: query tiles each walk), the bytes a token a layer as STORED (the
#: row's lanes, padding included), and the (query tile, page) softmax
#: updates the kernel computes in one layer: `pages_visited` counts a
#: visit once for the block of tiles it serves, this every tile served
STEP_COUNTS_LATENT: Tuple[str, ...] = ("chunk_kv_len", "latent_row_bytes",
                                       "attn_tile_chains")
#: ... and where every layer is chunk-summary (EVA) attention, whose
#: cache is two lists of rows from one pool. Of the launch (the first
#: four and the last add up): the pooled and the exact rows its
#: queries' sequences read in ONE layer, the pooled rows it wrote, the
#: windows its new tokens closed; the bytes of one row of either list
#: in one layer. Of the call: the pages its closes returned, and the
#: pool's pages by list (the total is the one pool's, the same under
#: both names). Of the launch again: the cache-tile runs its pooled
#: rows' append makes in one layer (`ops.fused.append_slot_run_table`)
STEP_COUNTS_EVA: Tuple[str, ...] = (
    "summary_rows_live", "window_rows_live", "summaries_written",
    "windows_closed", "cache_row_bytes", "window_pages_freed",
    "pool_pages_used.summary", "pool_pages_used.exact",
    "pool_pages_total.summary", "pool_pages_total.exact",
    "pool_append_runs")
#: ... and where the layer list runs several times a token (a looped
#: decoder): the passes of a launch, the layer applications they make,
#: the bytes ALL of a token's cache rows take (passes x layers x K + V),
#: and — taken on the device, returned with the step's logits — the
#: mean exit distribution p(u) of the rows a request owned, a tuple of
#: `ut_steps` floats that sums to 1: what adaptive exit would save
STEP_COUNTS_LOOP: Tuple[str, ...] = (
    "ut_steps", "layer_applications", "cache_row_bytes", "ut_exit_mass")
#: ... and where some layers are state-space mixers, whose memory of a
#: sequence is a FIXED-SIZE state in a slot of a pool, not pages: the
#: slots a launch's rows name (its decode rows' and its chunk's), the
#: bytes a slot holds in ONE such layer as stored (the recurrent state
#: and the convolution's tail), the bytes of recurrent state the launch
#: moved over all such layers (each named slot's once in and once out;
#: a slot that starts its sequence is not read), the chunk's rows, the
#: slots the launch started from zero state, and the pool's slots that
#: hold a request against all of them. A KDA (delta-rule) block's
#: state lives in the same pool under the same counts, and so does the
#: state of a block whose one norm feeds a state-space AND an attention
#: mixer (Falcon-H1: "such layers" are then ALL layers, each with pages
#: too); `ssm_state_bytes` is the slot as the pool's layout stores it
#: (`ops.pallas_ssm.state_layout`)
STEP_COUNTS_SSM: Tuple[str, ...] = (
    "ssm_slots_live", "ssm_state_bytes", "ssm_state_bytes_moved",
    "ssm_scan_rows", "ssm_state_resets", "state_pool_slots_used",
    "state_pool_slots_total")
#: ... and where the engine holds a prefix cache (`serving.prefix_cache`:
#: every family that keeps it, dense ones by default). Of the step's
#: admissions (`_reserve_pages`): the prompt tokens and the pages adopted
#: from the trie, and the trie pages evicted to make room
STEP_COUNTS_PREFIX: Tuple[str, ...] = (
    "prefix_tokens_adopted", "prefix_pages_adopted", "prefix_pages_evicted")
#: ... and where the state blocks' memory is a FINITE HISTORY and nothing
#: else (a short convolution's last K - 1 rows: LFM2), which can be cut
#: at a page's last row, so the family keeps the prefix cache — the
#: `STEP_COUNTS_SSM` counts read 0 bytes of state held and moved. Of the
#: launch the record retires: the pages whose last row its chunk wrote
#: (each one's tails go to the snapshot plane of EVERY such block),
#: whether the chunk continued an adopted prefix from a snapshot (these
#: two add up over a record's launches), and the bytes a slot holds in
#: ONE such block (its tail; a page's snapshot is as many)
STEP_COUNTS_TAIL: Tuple[str, ...] = (
    "tail_snapshots_written", "tail_restores", "tail_bytes")
#: ... and where the residual is wider than one stream a token
#: (hyper-connections: `serving.engine._HyperResidual`). Of the launch
#: the record retires: the rows mixed (the flat buffer's, idle rows
#: too; it adds up over a record's launches; the rows a sequence owned
#: are `decode_rows` + `prefill_rows`), the sublayers a row is mixed
#: around (two a layer); and — taken on the device, returned with the
#: step's logits beside the routed layers' counts — the largest
#: |column sum - 1| of any residual matrix of an owned row: whether the
#: Sinkhorn iterations converged on this traffic. (The bytes of a row
#: of the stream are a constant of the engine: `hbm_accounting()`.)
STEP_COUNTS_MHC: Tuple[str, ...] = (
    "mhc_rows", "mhc_sublayers", "mhc_colsum_err_max")
#: ... and where blocks that own no pages read ANOTHER block's pool
#: inside the launch (a cross-decoder over one layer's keys and values):
#: the launches of a step that fetch the full kind's pages, the owner's
#: and every borrower's; `pages_visited.full` is what ONE of them visits
STEP_COUNTS_SHARED: Tuple[str, ...] = ("shared_pool_readers",)
#: ... and where the model generates by diffusion over blocks: of the
#: launch the record retires, the slots whose rows were a DENOISE pass
#: of their block (the transfer rule reads their logits) and a COMMIT
#: pass (it writes the block's final K/V) — a slot a launch counts ONE
#: pass —, of the denoise ones those whose launch ALSO carried the
#: commit of the block before, riding behind the block rows (FUSED: a
#: commit that cost no launch), the decode rows that were
#: still masked going in (the rows whose logits the rule reads), the
#: tokens its commits emitted, the cache tokens its REQUESTS held, open
#: blocks and the chunk included, once a request however many entries
#: its rows take — what ONE layer's attention has to
#: read (the first six add up over a record's launches), and the slots
#: that held an open block
STEP_COUNTS_DIFFUSION: Tuple[str, ...] = (
    "diffusion_passes_denoise", "diffusion_passes_commit",
    "diffusion_passes_fused",
    "diffusion_rows_masked", "diffusion_tokens_committed",
    "diffusion_kv_tokens", "diffusion_blocks_open")
#: step records kept for each slot of the request ring. A request lives
#: through tens to hundreds of steps, and whoever reads a whole measured
#: window from the records (`benchmarks/lib/program_spans.py`: 50-56 s
#: of steps plus the warm-up) needs every one: 2048 records stopped
#: holding the chat cell's window once a step took under 27 ms (PR 29)
STEPS_PER_SLOT = 4
#: the `jax.monitoring` duration events a program record is assembled
#: from, by the record's field each one fills (jax reports a stage when
#: it ENDS, with its length; the backend's event surrounds the compile
#: cache's, so it fires on a hit too) ...
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace_ns",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_ns",
           "/jax/core/compile/backend_compile_duration": "compile_ns"}
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: ... and the plain events that say what the compile cache answered
_CACHE_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "miss",
                 "/jax/compilation_cache/cache_misses": "miss",
                 "/jax/compilation_cache/cache_hits": "hit"}
#: program records kept one by one, the LONGEST (an eager float32 model
#: compiles thousands of one-primitive programs; `program_totals` counts
#: every one whatever is kept), and the spans of no step kept likewise
PROGRAMS_KEPT = 4096
SETUP_SPANS_KEPT = 256
#: what one thread holds at most of stages nobody has claimed and of
#: records still open (a step program's trace holds a jit a kernel a
#: layer inside it; past this the oldest is let go and its time stays
#: in the stage that surrounds it)
_OPEN_KEPT = 4096
#: the sums `program_totals` keeps for each span name
_TOTAL_KEYS = ("programs", "hits", "misses", "trace_ns", "lower_ns",
               "compile_ns", "cache_read_ns")


def _keep_longest(heap: list, capacity: int, length: int, seq: int,
                  entry: Dict[str, Any]) -> None:
    """Push `entry` on a heap of (length, seq, entry) that keeps the
    `capacity` longest (`seq` is unique: entries are never compared)."""
    item = (length, seq, entry)
    if len(heap) < capacity:
        heapq.heappush(heap, item)
    elif item > heap[0]:
        heapq.heapreplace(heap, item)


class _ThreadPrograms:
    """What one thread's `jax.monitoring` events have said so far of the
    programs it is still assembling. A stage is reported at its END, so
    what ran inside it (a kernel's jit traced under the step program's
    trace, an eager constant's whole compile) has been reported before
    it: `stages` holds the finished stage intervals nobody has claimed
    yet, and a new stage takes those that start inside it off its own
    time. `open` holds the records that may still get a stage (traced
    and not lowered, lowered and not compiled), oldest first."""

    __slots__ = ("stages", "open", "cache", "cache_read_ns")

    def __init__(self):
        self.stages: deque = deque(maxlen=_OPEN_KEPT)  # (start, length)
        self.open: List[Dict[str, Any]] = []
        self.cache = "off"
        self.cache_read_ns = 0

    def inside(self, start_ns: int, end_ns: int) -> int:
        """Nanoseconds of [start_ns, end_ns] that stages reported before
        it took; they are this interval's from here on."""
        took, stages = 0, self.stages
        while stages and stages[-1][0] >= start_ns:
            took += stages.pop()[1]
        stages.append((start_ns, end_ns - start_ns))
        return took


class TraceEvent:
    """One monotonic stamp: name, microsecond timestamp, optional meta
    (token index, chunk size, engine step, explicit dur_us)."""

    __slots__ = ("name", "t_us", "meta")

    def __init__(self, name: str, t_us: int,
                 meta: Optional[Dict[str, Any]] = None):
        self.name = name
        self.t_us = int(t_us)
        self.meta = meta

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {"name": self.name, "t_us": self.t_us}
        if self.meta:
            d.update(self.meta)
        return d

    def __repr__(self):
        return f"TraceEvent({self.name!r}, t_us={self.t_us})"


class RequestTrace:
    """The event timeline of one request (or one train step).

    Events are appended by the owning `TraceRecorder` under its lock;
    readers get copies via `timeline()`. Derived latencies return None
    until the required events exist.
    """

    __slots__ = ("request_id", "kind", "span_id", "outcome", "meta",
                 "_events")

    def __init__(self, request_id, kind: str = "request",
                 meta: Optional[Dict[str, Any]] = None):
        self.request_id = request_id
        self.kind = kind
        # same namespace + format as observability.span host spans
        self.span_id = _new_span_id()
        self.outcome: Optional[str] = None
        self.meta = dict(meta) if meta else {}
        self._events: List[TraceEvent] = []

    # -- queries -----------------------------------------------------------
    def timeline(self) -> List[TraceEvent]:
        return list(self._events)

    def first(self, name: str) -> Optional[TraceEvent]:
        for e in self._events:
            if e.name == name:
                return e
        return None

    def last(self, name: str) -> Optional[TraceEvent]:
        for e in reversed(self._events):
            if e.name == name:
                return e
        return None

    def count(self, name: str) -> int:
        return sum(e.name == name for e in self._events)

    # -- derived SLOs ------------------------------------------------------
    def _gap_s(self, a: Optional[TraceEvent],
               b: Optional[TraceEvent]) -> Optional[float]:
        if a is None or b is None:
            return None
        return (b.t_us - a.t_us) / 1e6

    def queue_wait_s(self) -> Optional[float]:
        return self._gap_s(self.first("enqueue"), self.first("admit"))

    def prefill_wait_s(self) -> Optional[float]:
        """First admit -> first prefill chunk: the wait, holding a slot,
        behind the chunks of the prompts admitted earlier."""
        return self._gap_s(self.first("admit"),
                           self.first("prefill_chunk"))

    def prefill_run_s(self) -> Optional[float]:
        """First prefill chunk -> first token. With `queue_wait_s` and
        `prefill_wait_s` it sums to `ttft_s` by construction."""
        return self._gap_s(self.first("prefill_chunk"),
                           self.first("token"))

    def ttft_s(self) -> Optional[float]:
        return self._gap_s(self.first("enqueue"), self.first("token"))

    def tpot_s(self) -> Optional[float]:
        n = self.count("token")
        if n < 2:
            return None
        gap = self._gap_s(self.first("token"), self.last("token"))
        return gap / (n - 1) if gap is not None else None

    def e2e_s(self) -> Optional[float]:
        if not self._events:
            return None
        return self._gap_s(self.first("enqueue"), self._events[-1])

    def to_dict(self) -> Dict[str, Any]:
        return {"request_id": self.request_id, "kind": self.kind,
                "span_id": self.span_id, "outcome": self.outcome,
                "meta": self.meta,
                "queue_wait_s": self.queue_wait_s(),
                "prefill_wait_s": self.prefill_wait_s(),
                "prefill_run_s": self.prefill_run_s(),
                "ttft_s": self.ttft_s(), "tpot_s": self.tpot_s(),
                "e2e_s": self.e2e_s(),
                "events": [e.to_dict() for e in self._events]}

    def __repr__(self):
        return (f"RequestTrace(id={self.request_id!r}, kind={self.kind}, "
                f"events={len(self._events)}, outcome={self.outcome})")


_TERMINAL_OBSERVES_E2E = ("finish",)


class TraceRecorder:
    """Process-wide request/step timeline recorder.

    All mutation goes through `begin` / `stamp` / `finish`, each gated on
    FLAGS_request_tracing first. Finished traces move to a bounded ring
    (FLAGS_trace_ring_size, oldest evicted) so a long-lived serving
    process cannot grow without bound; host spans have a ring of the
    same capacity and step records one of `STEPS_PER_SLOT` times it.
    The set-up ledger (program records, the spans of no step, the steps
    in which a program was traced) is a store of its own, bounded by
    keeping the longest, with exact totals beside it.
    An optional background exporter thread drains finished traces to
    JSONL; it shares the same lock as every other accessor (paddlelint
    PT006 discipline).
    """

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            capacity = int(_flags.flag("FLAGS_trace_ring_size"))
        self._lock = threading.Lock()
        self._live: Dict[Any, RequestTrace] = {}
        self._done: deque = deque(maxlen=int(capacity))
        self._capacity = int(capacity)
        self._counters: Dict[str, deque] = {}
        self._spans: deque = deque(maxlen=int(capacity))
        self._steps: deque = deque(maxlen=STEPS_PER_SLOT * int(capacity))
        self._open_step: Optional[Dict[str, Any]] = None
        self._listening = False
        # the set-up ledger: heaps of (length, tie-break, entry) — the
        # shortest goes first once one is full
        self._threads: Dict[int, _ThreadPrograms] = {}
        self._programs: List[Tuple[int, int, Dict[str, Any]]] = []
        self._program_totals: Dict[Optional[str], Dict[str, int]] = {}
        self._setup_spans: List[Tuple[int, int, Dict[str, Any]]] = []
        self._setup_steps: List[Dict[str, Any]] = []
        self._kept_seq = itertools.count()
        self._replica: Optional[str] = None
        self._export_f = None
        self._export_thread: Optional[threading.Thread] = None
        self._export_stop: Optional[threading.Event] = None
        self._pending_export: deque = deque()

    # ------------------------------------------------------------ recording
    def begin(self, request_id, kind: str = "request",
              **meta) -> Optional[RequestTrace]:
        """Open a trace for `request_id` (replacing any live one) and
        stamp nothing; returns None with tracing off."""
        if not _FLAG.value:
            return None
        tr = RequestTrace(request_id, kind=kind, meta=meta or None)
        with self._lock:
            self._live[request_id] = tr
        return tr

    def stamp(self, request_id, name: str, **meta) -> None:
        """Append one monotonic event to the live trace of `request_id`;
        silently ignored when tracing is off or the id is unknown (a
        request admitted before tracing was switched on)."""
        if not _FLAG.value:
            return
        t = _now_us()
        with self._lock:
            tr = self._live.get(request_id)
            if tr is None:
                return
            st = self._open_step
            if st is not None and "step" not in meta:
                meta["step"] = st["seq"]
            rp = self._replica
            if rp is not None and "replica" not in meta:
                meta["replica"] = rp
            tr._events.append(TraceEvent(name, t, meta or None))

    def finish(self, request_id, outcome: str = "finish", **meta) -> None:
        """Stamp the terminal event, derive the SLOs into the registry
        histograms, and move the trace to the finished ring. Overloaded /
        Timeout / refused requests go through here too — they appear in
        the timeline instead of vanishing."""
        if not _FLAG.value:
            return
        self.stamp(request_id, outcome, **meta)
        with self._lock:
            tr = self._live.pop(request_id, None)
            if tr is None:
                return
            tr.outcome = outcome
            self._done.append(tr)
            if self._export_f is not None:
                self._pending_export.append(tr)
        if tr.kind != "request":
            return
        qw, ttft, tpot = (tr.queue_wait_s(), tr.ttft_s(), tr.tpot_s())
        if qw is not None:
            _H_QWAIT.observe(qw)
        if ttft is not None:
            _H_TTFT.observe(ttft)
        if tpot is not None:
            _H_TPOT.observe(tpot)
        if outcome in _TERMINAL_OBSERVES_E2E:
            e2e = tr.e2e_s()
            if e2e is not None:
                _H_E2E.observe(e2e)

    # ------------------------------------------------- spans and steps
    def _span_done(self, name: str, start_ns: int, end_ns: int,
                   parent: Optional[str], step: Optional[int]) -> None:
        """`observability.span`'s in-memory sink (the span checked the
        flag when it opened). A span of no step is kept in the set-up
        ledger too, the longest, where the ring cannot evict it. A span of
        the open step also lands in that step's record: the step's own
        span gives its start and end, its direct children are its
        phases."""
        with self._lock:
            self._spans.append((name, start_ns, end_ns, parent, step))
            if step is None:    # a span of no step is a set-up span
                _keep_longest(
                    self._setup_spans, SETUP_SPANS_KEPT, end_ns - start_ns,
                    next(self._kept_seq),
                    {"name": name, "start_ns": start_ns, "end_ns": end_ns,
                     "parent": parent, "step": None})
                return
            st = self._open_step
            if st is not None and step == st["seq"]:
                if parent == st["name"]:
                    st["phases"].append((name, start_ns, end_ns))
                elif name == st["name"]:
                    st["start_ns"], st["end_ns"] = start_ns, end_ns

    # --------------------------------------------------- program records
    def _listen(self) -> None:
        """Register the recorder's ONE pair of `jax.monitoring`
        listeners (durations, plain events), once: when the first span
        opens or `recorder()` is first asked, so before any program of
        set-up is traced. Loads no profiler and starts no backend; with
        the flag off each listener returns at its first line."""
        if self._listening:
            return
        with self._lock:
            if self._listening:
                return
            self._listening = True
        import jax.monitoring as monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _thread_programs(self) -> _ThreadPrograms:
        # (under the lock; listeners run in the thread that compiles)
        ident = threading.get_ident()
        th = self._threads.get(ident)
        if th is None:
            th = self._threads[ident] = _ThreadPrograms()
        return th

    def _on_event(self, event: str, **kw) -> None:
        """What the compile cache answered the program whose backend
        stage is open in this thread (its duration event comes last)."""
        answer = _CACHE_EVENTS.get(event)
        if answer is None or not _FLAG.value:
            return
        with self._lock:
            th = self._thread_programs()
            if th.cache != "hit":
                th.cache = answer

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        """One stage of one program ended in this thread: its time less
        what ran inside it goes to the record it continues, or opens
        one. A lowering continues the trace that ended before it began,
        a backend stage the lowering of its name; what started inside
        the stage (a nested program) can get no more and is closed."""
        if not _FLAG.value:
            return
        field = _STAGES.get(event)
        if field is None and event != _CACHE_READ_EVENT:
            return
        end_ns = _now_ns()
        length = round(secs * 1e9)
        start_ns = end_ns - length
        name = str(kw.get("fun_name", "?"))
        stack = getattr(_open_spans, "stack", None)
        span = stack[-1].name if stack else None
        with self._lock:
            th = self._thread_programs()
            if field is None:       # the cache's read: inside the backend's
                th.cache_read_ns = length
                return
            # (at least 1 ns: a stage that happened reads true)
            own = max(length - th.inside(start_ns, end_ns), 1)
            while th.open and th.open[-1]["start_ns"] >= start_ns:
                self._close_program(th.open.pop())
            rec = None
            if field == "lower_ns":
                top = th.open[-1] if th.open else None
                if top is not None and not top["lower_ns"] \
                        and name.endswith(f"({top['name']})"):
                    rec = top
            elif field == "compile_ns":
                rec = next((r for r in reversed(th.open)
                            if r["name"] == name and r["lower_ns"]), None)
            st = self._open_step
            if rec is None:
                rec = {"name": name, "start_ns": start_ns, "end_ns": end_ns,
                       "trace_ns": 0, "lower_ns": 0, "compile_ns": 0,
                       "cache": None, "cache_read_ns": 0, "span": span,
                       "step": st["seq"] if st is not None else None}
                self._totals_of(span)["programs"] += 1
                if field != "compile_ns":
                    # (what is traced inside a stage waits here until
                    # the stage is reported; a trace nobody lowers, for
                    # good)
                    th.open.append(rec)
                    if len(th.open) > _OPEN_KEPT:
                        self._close_program(th.open.pop(0))
            elif field == "compile_ns":
                th.open.remove(rec)
            totals = self._totals_of(rec["span"])
            rec["name"], rec["end_ns"] = name, end_ns
            rec[field] += own
            totals[field] += own
            if st is not None:
                st["programs"] += 1
            if field == "compile_ns":   # the program reached the backend
                rec["cache"], th.cache = th.cache, "off"
                rec["cache_read_ns"], th.cache_read_ns = th.cache_read_ns, 0
                if rec["cache"] != "off":
                    totals["hits" if rec["cache"] == "hit"
                           else "misses"] += 1
                    totals["cache_read_ns"] += rec["cache_read_ns"]
                if st is not None:
                    st["compiles"] += 1
                self._close_program(rec)

    def _totals_of(self, span: Optional[str]) -> Dict[str, int]:
        totals = self._program_totals.get(span)
        if totals is None:
            totals = self._program_totals[span] = dict.fromkeys(
                _TOTAL_KEYS, 0)
        return totals

    def _close_program(self, rec: Dict[str, Any]) -> None:
        """A record that gets no more stages: kept if it is among the
        longest (its totals were counted stage by stage)."""
        _keep_longest(
            self._programs, PROGRAMS_KEPT,
            rec["trace_ns"] + rec["lower_ns"] + rec["compile_ns"],
            next(self._kept_seq), rec)

    def open_step(self, seq: int, name: str) -> None:
        """Open the record of engine step `seq`, whose span is `name`.
        Until `close_step`, stamps carry ``step=seq``, the step's spans
        land in the record, and so does the count of the programs that
        reach the backend (`compiles`)."""
        if not _FLAG.value:
            return
        with self._lock:
            self._open_step = {
                "seq": int(seq), "name": name, "replica": self._replica,
                "start_ns": None, "end_ns": None, "phases": [],
                "compiles": 0, "programs": 0}

    def close_step(self, counts: Mapping[str, int]) -> None:
        """Close the open step record with the engine's `counts`
        (`STEP_COUNTS`) and move it to the step ring. A step in which a
        program was traced, lowered or compiled (a program's first
        launch) is copied into the set-up ledger too: the ring may have
        turned over by the time anyone asks."""
        with self._lock:
            st, self._open_step = self._open_step, None
            if st is None:
                return
            if st.pop("programs") and \
                    len(self._setup_steps) < SETUP_SPANS_KEPT:
                self._setup_steps.append({
                    "name": st["name"], "start_ns": st["start_ns"],
                    "end_ns": st["end_ns"], "parent": None,
                    "step": st["seq"], "phases": list(st["phases"])})
            st.update(counts)
            self._steps.append(st)

    def set_replica_context(self, name: Optional[str]) -> None:
        """Record which fleet replica is currently stamping; subsequent
        stamps carry ``replica=<name>`` in their meta so the fleet
        stitcher (`observability.fleet`) can split one cross-replica
        timeline into per-replica chrome-trace lanes. The serving engine
        sets this at the top of every method that stamps (and clears it
        with None for solo engines)."""
        if not _FLAG.value:
            return
        with self._lock:
            self._replica = name

    # ---------------------------------------------- cross-replica handoff
    def export_context(self, request_id) -> Optional[Dict[str, Any]]:
        """Portable trace context for a request leaving this process
        with a `KVPageHandoff`: request id, span lineage, accumulated
        events. `adopt()` on the importing replica's recorder continues
        the SAME logical timeline. Returns None with tracing off or for
        an unknown id."""
        if not _FLAG.value:
            return None
        with self._lock:
            tr = self._live.get(request_id)
            if tr is None:
                return None
            return {
                "request_id": tr.request_id, "kind": tr.kind,
                "span_id": tr.span_id, "meta": dict(tr.meta),
                "events": [{"name": e.name, "t_us": e.t_us,
                            "meta": dict(e.meta) if e.meta else None}
                           for e in tr._events],
            }

    def adopt(self, request_id, ctx: Optional[Dict[str, Any]]) -> None:
        """Continue a timeline exported by another replica's recorder
        (`export_context` travelling on the handoff). In-process fleets
        share ONE recorder, so a request that is still live here keeps
        its existing trace untouched; on a real fleet the importing
        process rebuilds the carried events — same span id, same
        lineage — and the scheduler's resume path appends to it."""
        if not _FLAG.value or not ctx:
            return
        with self._lock:
            if request_id in self._live:
                return
            tr = RequestTrace(request_id, kind=ctx.get("kind", "request"),
                              meta=ctx.get("meta") or None)
            if ctx.get("span_id"):
                tr.span_id = ctx["span_id"]
            for e in ctx.get("events", ()):
                tr._events.append(TraceEvent(e["name"], e["t_us"],
                                             e.get("meta") or None))
            self._live[request_id] = tr

    def counter(self, name: str, value, t_us: Optional[int] = None) -> None:
        """Record one sample on a named counter track — a (t, value)
        point rendered as a chrome-trace ``ph:"C"`` counter series on
        the same timeline as the request spans (the live HBM accounting
        view ISSUE 11 adds: weights / page pool / draft state /
        utilization). Bounded per series by the ring capacity."""
        if not _FLAG.value:
            return
        t = _now_us() if t_us is None else int(t_us)
        with self._lock:
            series = self._counters.get(name)
            if series is None:
                series = self._counters[name] = deque(
                    maxlen=self._capacity)
            series.append((t, float(value)))

    def sample_gauges(self, names: Sequence[str], reg=None) -> int:
        """Sample current registry gauge values onto counter tracks (one
        `counter()` point per gauge that exists). The engine calls this
        at the end of every step, so the exporter's counter tracks move
        in lockstep with the span timeline. Returns the sampled count."""
        if not _FLAG.value:
            return 0
        reg = reg or registry()
        n = 0
        for name in names:
            m = reg._metrics.get(name)
            if m is None or m.kind != "gauge":
                continue
            self.counter(name, m.value)
            n += 1
        return n

    # -------------------------------------------------------------- queries
    def counters(self) -> Dict[str, List[Tuple[int, float]]]:
        """Snapshot of every counter track: {name: [(t_us, value), ...]}."""
        with self._lock:
            return {k: list(v) for k, v in self._counters.items()}

    def spans(self) -> List[Tuple[str, int, int, Optional[str],
                                  Optional[int]]]:
        """The newest host spans, oldest first:
        ``(name, start_ns, end_ns, parent, step)`` on the
        `time.perf_counter_ns` clock."""
        with self._lock:
            return list(self._spans)

    def steps(self) -> List[Dict[str, Any]]:
        """Copies of the newest step records, oldest first: ``seq``,
        ``name``, ``replica``, ``start_ns`` / ``end_ns``, ``phases``
        (``[(span name, start_ns, end_ns)]``, disjoint, inside the
        step), ``compiles`` (the program records that reached the
        backend while the step was open) and the `STEP_COUNTS`."""
        with self._lock:
            return [dict(st, phases=list(st["phases"]))
                    for st in self._steps]

    def programs(self) -> List[Dict[str, Any]]:
        """Copies of the program records kept (the `PROGRAMS_KEPT`
        longest, and those still being assembled), by their end:
        ``name`` (jax's ``fun_name``), ``start_ns`` / ``end_ns``,
        ``trace_ns`` (less what was traced inside it: a nested
        program's time counts once, in its own record), ``lower_ns``,
        ``compile_ns``, ``cache`` ("hit" / "miss" / "off"; None for a
        program that never reached the backend), ``cache_read_ns``,
        ``span`` (the innermost open span of the thread that compiled)
        and ``step`` (the open step's ``seq``)."""
        with self._lock:
            recs = [dict(r) for _, _, r in self._programs]
            recs += [dict(r) for th in self._threads.values()
                     for r in th.open]
        return sorted(recs, key=lambda r: r["end_ns"])

    def program_totals(self) -> Dict[Optional[str], Dict[str, int]]:
        """{span name (None: under no span): ``programs``, ``hits``,
        ``misses`` and the four time sums} over EVERY program record,
        whatever `programs` still holds."""
        with self._lock:
            return {k: dict(v) for k, v in self._program_totals.items()}

    def setup(self) -> Dict[str, Any]:
        """Where a start went (docs/OBSERVABILITY.md, "Set-up: where a
        start goes"): ``spans`` — the spans of no step
        (``paddle_tpu.import``, ``serving.engine.construct`` /
        ``trainer.build`` and their children) and a copy of every step
        in which a program was traced or compiled, with its ``phases``
        — by their start, ``programs`` (`programs`) and ``totals``
        (`program_totals`)."""
        with self._lock:
            spans = [dict(sp) for _, _, sp in self._setup_spans]
            spans += [dict(st, phases=list(st["phases"]))
                      for st in self._setup_steps]
        pair = getattr(sys.modules.get("paddle_tpu"), "_IMPORT_NS", None)
        if pair is not None:    # (None while the package still imports)
            spans.append({"name": "paddle_tpu.import", "start_ns": pair[0],
                          "end_ns": pair[1], "parent": None, "step": None})
        return {"spans": sorted(spans, key=lambda sp: sp["start_ns"] or 0),
                "programs": self.programs(),
                "totals": self.program_totals()}

    def trace(self, request_id) -> Optional[RequestTrace]:
        """Most recent trace for `request_id`: live first, then the
        newest matching finished one."""
        with self._lock:
            tr = self._live.get(request_id)
            if tr is not None:
                return tr
            for t in reversed(self._done):
                if t.request_id == request_id:
                    return t
        return None

    def live(self) -> List[RequestTrace]:
        with self._lock:
            return list(self._live.values())

    def is_live(self, request_id) -> bool:
        with self._lock:
            return request_id in self._live

    def finished(self, kind: Optional[str] = None) -> List[RequestTrace]:
        with self._lock:
            done = list(self._done)
        return [t for t in done if kind is None or t.kind == kind]

    def clear(self) -> None:
        with self._lock:
            self._live.clear()
            self._done.clear()
            self._counters.clear()
            self._pending_export.clear()
            self._spans.clear()
            self._steps.clear()
            self._open_step = None
            self._replica = None
            self._threads.clear()
            self._programs.clear()
            self._program_totals.clear()
            self._setup_spans.clear()
            self._setup_steps.clear()

    # ------------------------------------------------------- chrome export
    def export_chrome_trace(self, path: str,
                            include_live: bool = True) -> int:
        """Write every trace as chrome-trace JSON: one `tid` row per
        request/step, an enclosing lifetime span named
        ``<kind>:<id>[span=<span_id>]`` (the observability.span naming
        convention, so ids join against host-profiler exports), phase
        spans (queue / prefill / decode or the trainer phases), an
        instant per point event, and one ``ph:"C"`` counter event per
        counter-track sample (gauge series — page-pool utilization,
        HBM accounting — rendered by Perfetto as value-over-time tracks
        on the same clock). The step records are one more row
        (``tid`` 0): a span per step with its counts as args, its
        phases nested inside. Returns the event count; the file
        round-trips through `profiler.load_profiler_result`."""
        with self._lock:
            traces = list(self._done) + \
                (list(self._live.values()) if include_live else [])
            counters = {k: list(v) for k, v in self._counters.items()}
            steps = list(self._steps)
        pid = os.getpid()
        events: List[Dict[str, Any]] = []
        for st in steps:
            if st["start_ns"] is None:
                continue    # the step's span never closed
            counts = {k: v for k, v in st.items()
                      if k not in ("name", "phases", "start_ns", "end_ns")}
            rows = [(st["name"], st["start_ns"], st["end_ns"], counts)]
            rows += [(n, a, b, {"step": st["seq"]})
                     for n, a, b in st["phases"]]
            for name, a, b, args in rows:
                events.append({
                    "name": name, "ph": "X", "pid": pid, "tid": 0,
                    "ts": a // 1000, "dur": max((b - a) // 1000, 1),
                    "cat": "step", "args": args})
        for tid, tr in enumerate(traces, start=1):
            evs = tr.timeline()
            if not evs:
                continue
            t0, t1 = evs[0].t_us, evs[-1].t_us
            args = {"span_id": tr.span_id, "outcome": tr.outcome}
            args.update(tr.meta)
            events.append({
                "name": f"{tr.kind}:{tr.request_id}[span={tr.span_id}]",
                "ph": "X", "pid": pid, "tid": tid, "ts": t0,
                "dur": max(t1 - t0, 1), "cat": tr.kind, "args": args})
            events.extend(self._phase_events(tr, evs, pid, tid))
            for e in evs:
                rec = {"name": e.name, "ph": "i", "pid": pid, "tid": tid,
                       "ts": e.t_us, "s": "t", "cat": "event"}
                if e.meta:
                    rec["args"] = dict(e.meta)
                    dur = e.meta.get("dur_us")
                    if dur:
                        rec.update(ph="X", dur=int(dur),
                                   ts=e.t_us - int(dur), cat="phase")
                events.append(rec)
        for name, series in sorted(counters.items()):
            for t, v in series:
                events.append({"name": name, "ph": "C", "pid": pid,
                               "ts": t, "cat": "counter",
                               "args": {"value": v}})
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"traceEvents": events}, f)
        return len(events)

    @staticmethod
    def _phase_events(tr: RequestTrace, evs: List[TraceEvent], pid: int,
                      tid: int) -> List[Dict[str, Any]]:
        """Queue / prefill / decode phase spans for request traces (the
        trainer stamps its phases with explicit dur_us instead)."""
        if tr.kind != "request":
            return []
        out = []
        enq, adm = tr.first("enqueue"), tr.first("admit")
        tok1, tokn = tr.first("token"), tr.last("token")
        spans = [("queue", enq, adm or (evs[-1] if enq else None)),
                 ("prefill", adm, tok1), ("decode", tok1, tokn)]
        for name, a, b in spans:
            if a is None or b is None or b.t_us < a.t_us:
                continue
            out.append({"name": name, "ph": "X", "pid": pid, "tid": tid,
                        "ts": a.t_us, "dur": max(b.t_us - a.t_us, 1),
                        "cat": "phase",
                        "args": {"span_id": tr.span_id}})
        return out

    # -------------------------------------------------- background export
    def start_exporter(self, path: str,
                       interval_s: float = 1.0) -> None:
        """Start the background flush thread: finished traces are
        appended to `path` as JSONL (one trace per line). Idempotent per
        recorder; `stop_exporter` joins the thread and closes the file."""
        with self._lock:
            if self._export_thread is not None:
                return
            parent = os.path.dirname(os.path.abspath(path))
            os.makedirs(parent, exist_ok=True)
            self._export_f = open(path, "a", encoding="utf-8")
            self._export_stop = threading.Event()
            stop = self._export_stop
            t = threading.Thread(
                target=self._export_loop, args=(stop, float(interval_s)),
                name="trace-exporter", daemon=True)
            self._export_thread = t
        t.start()

    def _export_loop(self, stop: threading.Event,
                     interval_s: float) -> None:
        while not stop.wait(interval_s):
            self._flush_pending()
        self._flush_pending()

    def _flush_pending(self) -> None:
        # drain + write under the one recorder lock: the flush thread
        # touches no state outside it (paddlelint PT006)
        with self._lock:
            if self._export_f is None:
                return
            while self._pending_export:
                tr = self._pending_export.popleft()
                self._export_f.write(json.dumps(tr.to_dict()) + "\n")
            self._export_f.flush()

    def stop_exporter(self) -> None:
        with self._lock:
            t, stop = self._export_thread, self._export_stop
            self._export_thread = self._export_stop = None
        if t is None:
            return
        stop.set()
        t.join(timeout=5.0)
        self._flush_pending()
        with self._lock:
            if self._export_f is not None:
                self._export_f.close()
                self._export_f = None


_default_recorder = TraceRecorder()


def recorder() -> TraceRecorder:
    """The process-wide recorder the serving engine and trainer stamp
    into (module-level singleton, assigned once at import — readers
    never mutate the binding). Asking for it is what turns its
    `jax.monitoring` listeners on (the modules that stamp ask at import:
    before any program of set-up is traced)."""
    _default_recorder._listen()
    return _default_recorder


# ---------------------------------------------------------------------------
# percentiles from cumulative buckets
# ---------------------------------------------------------------------------

def _hist_state(h: Union[Histogram, Mapping[str, Any]],
                buckets: Optional[Sequence[float]] = None):
    """(bounds, per-bucket counts, total) from a Histogram or a snapshot
    series dict ({'counts': [...], 'count': n} + buckets argument)."""
    if isinstance(h, Histogram):
        with h._lock:
            return h.buckets, list(h._counts), h._count
    if buckets is None:
        raise ValueError("snapshot series needs explicit buckets")
    return tuple(buckets), list(h["counts"]), int(h["count"])


def percentile(h: Union[Histogram, Mapping[str, Any]], q: float,
               buckets: Optional[Sequence[float]] = None
               ) -> Optional[float]:
    """q-th percentile (0..100) from cumulative bucket counts.

    Linear interpolation inside the landing bucket (the first bucket's
    lower edge is 0) — exact whenever observations sit on bucket bounds.
    Returns None on an empty histogram; a percentile landing in the +Inf
    bucket clamps to the largest finite bound (the Prometheus
    `histogram_quantile` convention)."""
    if not 0 <= q <= 100:
        raise ValueError(f"percentile q must be in [0, 100], got {q}")
    bounds, counts, total = _hist_state(h, buckets)
    if total == 0:
        return None
    target = q / 100.0 * total
    cum = 0.0
    for i, c in enumerate(counts):
        if cum + c >= target and c > 0:
            if i >= len(bounds):          # +Inf bucket: clamp
                return float(bounds[-1])
            lo = 0.0 if i == 0 else float(bounds[i - 1])
            hi = float(bounds[i])
            return lo + (hi - lo) * (target - cum) / c
        cum += c
    return float(bounds[-1])


def percentiles(h: Union[Histogram, Mapping[str, Any]],
                qs: Sequence[float] = (50, 90, 99),
                buckets: Optional[Sequence[float]] = None
                ) -> Dict[str, Optional[float]]:
    return {f"p{g:g}": percentile(h, g, buckets=buckets) for g in qs}


def slo_summary(names: Sequence[str] = SLO_METRICS, reg=None,
                qs: Sequence[float] = (50, 90, 99)) -> Dict[str, Any]:
    """{metric: {count, mean, p50, p90, p99}} for the serving SLO
    histograms (or any histogram names passed); metrics that never
    observed report count 0 and None quantiles."""
    reg = reg or registry()
    out: Dict[str, Any] = {}
    for name in names:
        h = reg._metrics.get(name) if name in reg._metrics else None
        if h is None or h.kind != "histogram":
            continue
        with h._lock:
            count, total = h._count, h._sum
        row: Dict[str, Any] = {
            "count": count,
            "mean": (total / count) if count else None}
        row.update(percentiles(h, qs))
        out[name] = row
    return out
