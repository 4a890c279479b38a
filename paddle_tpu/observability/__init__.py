"""paddle_tpu.observability — process-wide metrics registry and exporters.

The measurement substrate for every perf/robustness PR (ISSUE 1): a
Prometheus-style metric model (Counter / Gauge / Histogram with fixed
buckets, labeled children, thread-safe) that the hot layers report into:

  - ops dispatch / jit caches   (core/dispatch.py, jit/__init__.py,
                                 generation.py decode-loop cache)
  - Pallas kernel routing       (ops/flash_attention.py, ops/paged_attention.py,
                                 ops/grouped_gemm.py)
  - trainer                     (trainer/trainer.py step breakdown, tokens/s,
                                 MFU, grad-norm)
  - serving                     (inference/Predictor, generation.py,
                                 KV-page utilization)
  - collectives                 (distributed/collective.py calls/bytes/latency)

Three exporters: Prometheus text format (`to_prometheus`), JSON snapshot
(`snapshot` / `Registry.from_snapshot` round-trip), and a JSONL step-log
writer (`StepLogger`) whose records carry span ids minted by `span()` —
the same ids are embedded in the chrome-trace event names the host
profiler exports, so step rows and trace spans correlate.

`span(name, **args)` is the one way the program opens a host span: a
`jax.profiler.TraceAnnotation` under the plain name (on the device
trace's clock whenever a profiler trace is on), an in-memory record in
`tracing.recorder()`, and the legacy native `RecordEvent`
(docs/OBSERVABILITY.md, "Host spans, the step key and the profiler's
clock").

Overhead contract: every mutation checks `FLAGS_metrics` FIRST via a
cached flag-object attribute read, so with the flag off an instrumented
call is one function call + one attribute test (no locks, no dict
lookups). `tests/test_observability.py::TestOverhead` gates what a
disabled entry point does: calls made, locks taken, objects kept.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

from .. import flags as _flags

__all__ = ["Counter", "Gauge", "Histogram", "Registry", "registry",
           "enabled", "set_enabled", "snapshot", "to_prometheus",
           "parse_prometheus", "sample_values", "StepLogger", "span",
           "sections", "DEFAULT_BUCKETS"]

# the flag is defined in paddle_tpu.flags (core flag set); grab the flag
# OBJECT once so the hot-path enabled check is a plain attribute read
_FLAG = _flags._registry["FLAGS_metrics"]


def enabled() -> bool:
    """Whether metric mutations are recorded (FLAGS_metrics)."""
    return _FLAG.value


def set_enabled(on: bool) -> None:
    _flags.set_flags({"FLAGS_metrics": bool(on)})


# seconds-scale latency buckets: 10us .. 60s, roughly log-spaced
DEFAULT_BUCKETS: Tuple[float, ...] = (
    1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3,
    1e-2, 2.5e-2, 5e-2, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


def _label_key(label_names: Tuple[str, ...], kw: Mapping[str, str]) -> tuple:
    try:
        return tuple(str(kw[n]) for n in label_names)
    except KeyError:
        missing = [n for n in label_names if n not in kw]
        raise ValueError(f"missing label(s) {missing}; declared "
                         f"labels are {list(label_names)}") from None


class _Timer:
    """Context manager: observe elapsed seconds into a histogram child.
    When metrics are disabled, enter/exit are two attribute checks."""

    __slots__ = ("_h", "_t0")

    def __init__(self, hist):
        self._h = hist
        self._t0 = 0.0

    def __enter__(self):
        if _FLAG.value:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if _FLAG.value and self._t0:
            self._h.observe(time.perf_counter() - self._t0)
        return False


class _Metric:
    """Base: a named metric with optional declared label names. The parent
    itself holds the unlabeled series; `labels()` vends children."""

    kind = "untyped"

    def __init__(self, name: str, help: str = "",
                 label_names: Sequence[str] = ()):
        self.name = name
        self.help = help
        self.label_names = tuple(label_names)
        self._lock = threading.Lock()
        self._children: Dict[tuple, "_Metric"] = {}

    def labels(self, **kw):
        key = _label_key(self.label_names, kw)
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.get(key)
                if child is None:
                    child = self._make_child()
                    self._children[key] = child
        return child

    def _series(self) -> List[Tuple[tuple, "_Metric"]]:
        """(label_values, series) pairs; unlabeled metrics report self."""
        if self.label_names:
            with self._lock:
                return sorted(self._children.items())
        return [((), self)]

    def _reset_values(self):
        with self._lock:
            self._children.clear()
        self._zero()


class Counter(_Metric):
    kind = "counter"

    def __init__(self, name="", help="", label_names=()):
        super().__init__(name, help, label_names)
        self._value = 0.0

    def _make_child(self):
        return Counter()

    def inc(self, n: float = 1.0) -> None:
        if not _FLAG.value:
            return
        if n < 0:
            raise ValueError("counters only go up")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value

    def _zero(self):
        self._value = 0.0


class Gauge(_Metric):
    kind = "gauge"

    def __init__(self, name="", help="", label_names=()):
        super().__init__(name, help, label_names)
        self._value = 0.0

    def _make_child(self):
        return Gauge()

    def set(self, v: float) -> None:
        if not _FLAG.value:
            return
        with self._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0) -> None:
        if not _FLAG.value:
            return
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    @property
    def value(self) -> float:
        return self._value

    def _zero(self):
        self._value = 0.0


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name="", help="", label_names=(),
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(name, help, label_names)
        b = tuple(sorted(float(x) for x in buckets))
        if not b:
            raise ValueError("histogram needs at least one bucket bound")
        self.buckets = b
        self._counts = [0] * (len(b) + 1)   # last slot = +Inf
        self._sum = 0.0
        self._count = 0

    def _make_child(self):
        return Histogram(buckets=self.buckets)

    def observe(self, v: float) -> None:
        if not _FLAG.value:
            return
        v = float(v)
        i = 0
        for bound in self.buckets:
            if v <= bound:
                break
            i += 1
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    def time(self) -> _Timer:
        return _Timer(self)

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def count(self) -> int:
        return self._count

    def _zero(self):
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._count = 0


class Registry:
    """Get-or-create metric registry. Re-requesting a name returns the
    existing metric; kind/label mismatches raise (one meaning per name)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}

    def _get_or_create(self, cls, name, help, labels, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if not isinstance(m, cls) or m.label_names != tuple(labels):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{m.kind} with labels {m.label_names}")
                return m
            m = cls(name, help, labels, **kw)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "",
                labels: Sequence[str] = ()) -> Counter:
        return self._get_or_create(Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Sequence[str] = ()) -> Gauge:
        return self._get_or_create(Gauge, name, help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._get_or_create(Histogram, name, help, labels,
                                   buckets=buckets)

    def collect(self) -> List[_Metric]:
        with self._lock:
            return [self._metrics[k] for k in sorted(self._metrics)]

    def reset(self) -> None:
        """Zero every value and drop labeled children (metric definitions
        stay registered). For tests."""
        for m in self.collect():
            m._reset_values()

    # -- JSON snapshot exporter ---------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for m in self.collect():
            entry: Dict[str, Any] = {"kind": m.kind, "help": m.help,
                                     "labels": list(m.label_names),
                                     "series": []}
            if m.kind == "histogram":
                entry["buckets"] = list(m.buckets)
            for vals, s in m._series():
                lbl = dict(zip(m.label_names, vals))
                if m.kind == "histogram":
                    with s._lock:
                        entry["series"].append(
                            {"labels": lbl, "counts": list(s._counts),
                             "sum": s._sum, "count": s._count})
                else:
                    entry["series"].append({"labels": lbl, "value": s._value})
            out[m.name] = entry
        return out

    @classmethod
    def from_snapshot(cls, snap: Mapping[str, Any]) -> "Registry":
        """Rebuild a registry holding exactly the snapshot's state (the
        JSON round-trip: reg.snapshot() == Registry.from_snapshot(
        reg.snapshot()).snapshot())."""
        reg = cls()
        for name, e in snap.items():
            labels = tuple(e["labels"])
            if e["kind"] == "counter":
                m = reg.counter(name, e["help"], labels)
            elif e["kind"] == "gauge":
                m = reg.gauge(name, e["help"], labels)
            elif e["kind"] == "histogram":
                m = reg.histogram(name, e["help"], labels,
                                  buckets=e["buckets"])
            else:
                raise ValueError(f"unknown metric kind {e['kind']!r}")
            for s in e["series"]:
                tgt = m.labels(**s["labels"]) if labels else m
                if e["kind"] == "histogram":
                    tgt._counts = list(s["counts"])
                    tgt._sum = float(s["sum"])
                    tgt._count = int(s["count"])
                else:
                    tgt._value = float(s["value"])
        return reg


_default = Registry()


def registry() -> Registry:
    """The process-wide default registry every subsystem reports into."""
    return _default


def snapshot(reg: Optional[Registry] = None) -> Dict[str, Any]:
    return (reg or _default).snapshot()


# ---------------------------------------------------------------------------
# Prometheus text exposition format
# ---------------------------------------------------------------------------

def _esc(v: str) -> str:
    return v.replace("\\", "\\\\").replace("\n", "\\n").replace('"', '\\"')


def _fmt_labels(names: Tuple[str, ...], vals: tuple,
                extra: Sequence[Tuple[str, str]] = ()) -> str:
    pairs = [f'{n}="{_esc(v)}"' for n, v in zip(names, vals)]
    pairs += [f'{n}="{_esc(v)}"' for n, v in extra]
    return "{" + ",".join(pairs) + "}" if pairs else ""


def _fmt_num(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    f = float(v)
    return repr(int(f)) if f == int(f) else repr(f)


def to_prometheus(reg: Optional[Registry] = None) -> str:
    """Render the registry in Prometheus text exposition format."""
    reg = reg or _default
    lines: List[str] = []
    for m in reg.collect():
        if m.help:
            lines.append(f"# HELP {m.name} {_esc(m.help)}")
        lines.append(f"# TYPE {m.name} {m.kind}")
        for vals, s in m._series():
            if m.kind == "histogram":
                with s._lock:
                    counts, total, cnt = list(s._counts), s._sum, s._count
                cum = 0
                for bound, c in zip(m.buckets + (float("inf"),), counts):
                    cum += c
                    lines.append(
                        f"{m.name}_bucket"
                        f"{_fmt_labels(m.label_names, vals, [('le', _fmt_num(bound))])}"
                        f" {cum}")
                lines.append(f"{m.name}_sum"
                             f"{_fmt_labels(m.label_names, vals)} "
                             f"{_fmt_num(total)}")
                lines.append(f"{m.name}_count"
                             f"{_fmt_labels(m.label_names, vals)} {cnt}")
            else:
                lines.append(f"{m.name}{_fmt_labels(m.label_names, vals)} "
                             f"{_fmt_num(s._value)}")
    return "\n".join(lines) + "\n"


def parse_prometheus(text: str) -> Dict[str, float]:
    """Parse text exposition back to {'name{k="v",...}': value} — the same
    flat form `sample_values` produces, so exporters round-trip in tests."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        series, _, val = line.rpartition(" ")
        v = float("inf") if val == "+Inf" else float(val)
        out[series] = v
    return out


def sample_values(reg: Optional[Registry] = None) -> Dict[str, float]:
    """Flat {'name{labels}': value} view of every exposed sample (histogram
    series expand to _bucket/_sum/_count exactly as Prometheus exposes)."""
    reg = reg or _default
    out: Dict[str, float] = {}
    for m in reg.collect():
        for vals, s in m._series():
            if m.kind == "histogram":
                with s._lock:
                    counts, total, cnt = list(s._counts), s._sum, s._count
                cum = 0
                for bound, c in zip(m.buckets + (float("inf"),), counts):
                    cum += c
                    key = (f"{m.name}_bucket"
                           f"{_fmt_labels(m.label_names, vals, [('le', _fmt_num(bound))])}")
                    out[key] = float(cum)
                out[f"{m.name}_sum{_fmt_labels(m.label_names, vals)}"] = \
                    float(total)
                out[f"{m.name}_count{_fmt_labels(m.label_names, vals)}"] = \
                    float(cnt)
            else:
                out[f"{m.name}{_fmt_labels(m.label_names, vals)}"] = \
                    float(s._value)
    return out


# ---------------------------------------------------------------------------
# span ids + JSONL step log (correlates with chrome-trace host events)
# ---------------------------------------------------------------------------

_span_seq = itertools.count(1)
# the pid half of a span id, kept: os.getpid() is a system call on every
# span (5 us on the chip's sandboxed host, my chip run, PR 24)
_pid = os.getpid()


def _pid_after_fork() -> None:
    global _pid
    _pid = os.getpid()


os.register_at_fork(after_in_child=_pid_after_fork)


def _new_span_id() -> str:
    """A process-unique id ``<pid>-<seq>``: host spans and request
    traces draw from one sequence."""
    return f"{_pid}-{next(_span_seq)}"


# per-thread stack of the open spans: the top is the next span's parent
_open_spans = threading.local()
# jax.profiler.TraceAnnotation and native.RecordEvent, imported at the
# first span (importing this package must not load the profiler, let
# alone start a backend)
_TraceAnnotation = _RecordEvent = None


class _Span:
    """One host span, the single way the program opens one.

    Sinks: (1) a `jax.profiler.TraceAnnotation` under the PLAIN name
    with `args` as its arguments — whenever anyone's profiler trace is
    on, the span is a host event in the same ``.xplane.pb`` and on the
    same clock as the device's operations, and costs a fraction of a
    microsecond otherwise; (2) with FLAGS_request_tracing on, one
    ``(name, start_ns, end_ns, parent, step)`` record in the process
    `tracing.recorder()`, in memory; (3) the legacy sink, a native
    host-profiler `RecordEvent` whose name embeds a unique span id —
    `StepLogger.log(..., span_id=sp.span_id)` writes the same id, so
    JSONL rows join `paddle.profiler.Profiler`'s chrome export on it.

    `step` is the argument of that name, or else the enclosing span's:
    the key that joins spans, step records and request stamps."""

    __slots__ = ("name", "span_id", "step", "_ev", "_ann", "_t0",
                 "_parent")

    def __init__(self, name: str, args: Mapping[str, Any]):
        global _TraceAnnotation, _RecordEvent
        if _TraceAnnotation is None:
            from jax.profiler import TraceAnnotation as _TraceAnnotation
            from ..native import RecordEvent as _RecordEvent
            # the first span: the set-up ledger's listeners are on
            # before what the span surrounds is traced
            tracing._default_recorder._listen()
        self.name = name
        self.span_id = _new_span_id()
        self.step = args.get("step")
        self._ev = _RecordEvent(f"{name}[span={self.span_id}]")
        self._ann = _TraceAnnotation(name, **args)
        self._t0 = 0
        self._parent: Optional[str] = None

    def __enter__(self):
        self._ann.__enter__()
        self._ev.__enter__()
        if tracing._FLAG.value:
            stack = getattr(_open_spans, "stack", None)
            if stack is None:
                stack = _open_spans.stack = []
            if stack:
                self._parent = stack[-1].name
                if self.step is None:
                    self.step = stack[-1].step
            stack.append(self)
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self._t0:
            t1 = time.perf_counter_ns()
            _open_spans.stack.pop()
            tracing._default_recorder._span_done(
                self.name, self._t0, t1, self._parent, self.step)
        self._ev.__exit__(*exc)
        self._ann.__exit__(*exc)
        return False


def span(name: str, **args: Any) -> _Span:
    """Open host span `name`; `args` (ids, counts — never pasted into
    the name) become the profiler event's arguments."""
    return _Span(name, args)


class _Sections:
    """The span `name` around a block, and one call to close the child
    span that is open and open the next: disjoint children at the
    borders of ONE long body (a constructor, a builder), whose locals
    carry across them, without indenting it under a `with` a section."""

    __slots__ = ("_parent", "_child")

    def __init__(self, name: str):
        self._parent = _Span(name, {})
        self._child: Optional[_Span] = None

    def __enter__(self):
        self._parent.__enter__()
        return self

    def __call__(self, child: Optional[str] = None) -> None:
        """End the open section; open `<name>.<child>` (None: none)."""
        if self._child is not None:
            self._child.__exit__(None, None, None)
            self._child = None
        if child is not None:
            self._child = _Span(f"{self._parent.name}.{child}",
                                {}).__enter__()

    def __exit__(self, *exc):
        self()
        return self._parent.__exit__(*exc)


def sections(name: str) -> _Sections:
    """``with sections("trainer.build") as section:`` opens host span
    `name`; ``section("model")`` then ends the section before and opens
    the child span ``trainer.build.model`` (`_Sections`)."""
    return _Sections(name)


class StepLogger:
    """Append-only JSONL writer: one record per step with a wall-clock
    timestamp, optional span id, user extras, and the flat sample view of
    the registry at that instant."""

    def __init__(self, path: str, reg: Optional[Registry] = None):
        self.path = path
        self._reg = reg or _default
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        self._f = open(path, "a", encoding="utf-8")
        self._lock = threading.Lock()

    def log(self, step: int, span_id: Optional[str] = None,
            **extra: Any) -> Dict[str, Any]:
        rec = {"ts": time.time(), "step": int(step)}
        if span_id is not None:
            rec["span_id"] = span_id
        if extra:
            rec.update(extra)
        rec["metrics"] = sample_values(self._reg)
        with self._lock:
            self._f.write(json.dumps(rec) + "\n")
            self._f.flush()
        return rec

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# per-request / per-train-step span timelines (imported last: tracing
# builds on the registry, DEFAULT_BUCKETS and the span-id sequence above)
from . import tracing                                    # noqa: E402
from .tracing import (RequestTrace, TraceRecorder,       # noqa: E402,F401
                      percentile, percentiles, slo_summary)

__all__ += ["tracing", "RequestTrace", "TraceRecorder", "percentile",
            "percentiles", "slo_summary"]

# the roofline observatory (ISSUE 11): the analytical per-kernel cost
# registry and the measured-vs-model attribution joins built on it
from . import attribution, costmodel                     # noqa: E402
from .costmodel import CostEstimate                      # noqa: E402,F401

__all__ += ["attribution", "costmodel", "CostEstimate"]


# the fleet observability plane (ISSUE 16): cross-replica trace
# stitching, metric federation, and fleet-scope SLO histograms
from . import fleet                                      # noqa: E402

__all__ += ["fleet"]
