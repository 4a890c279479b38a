"""Collective watchdog & cross-rank flight recorder (ISSUE 3).

The reference pairs its NCCL process groups with async error handling and
a watchdog thread (ProcessGroupNCCL's workCleanupLoop + TORCH/PADDLE
desync debug dumps); without one, a dead or lagging rank turns every
collective into a silent, pod-wide hang. This module is the detection and
diagnosis side of the resilience story (PR 2 shipped injection/recovery):

- **Flight recorder** — every public entry in ``distributed/collective.py``
  logs (monotonic seq, op, shapes/dtypes, payload bytes, mesh axis,
  start/end timestamps, status) into a fixed-size ring buffer
  (``FLAGS_flight_record_size``), dumpable to JSON for post-mortems.
- **Watchdog monitor** — a daemon thread gated by
  ``FLAGS_collective_timeout`` (seconds; 0 = off) that detects an
  in-flight collective past its deadline, dumps the ring buffer to the
  worker's log dir (``PADDLE_LOG_DIR``) and cancels the record so the
  cooperative wait sites raise a diagnostic :class:`CollectiveTimeout`
  (the trainer routes it into its emergency-checkpoint path).
- **Cross-rank desync detection** — each rank publishes its
  last-completed seq into the launcher's TCPStore (``flight/<rank>``
  keys, plus the ``|``-suffixed heartbeat payload channel
  ``ElasticManager.alive_nodes`` already tolerates), so the controller
  can name the lagging rank and the op it is stuck on
  (:func:`desync_report`).
- **Post-mortem merge** — :func:`merge_dumps` / :func:`first_divergence`
  combine per-rank dumps into one report and locate the first seq where
  ranks disagree; ``tools/flight_recorder.py`` is the offline CLI and
  ``CollectiveController.watch()`` writes ``flight_report.json`` on child
  failure.

Overhead contract: with the watchdog off (``FLAGS_collective_timeout``
== 0 and recording not forced), :func:`start_record` is one function
call + one attribute test — gated at <5% in ``tests/test_watchdog.py``,
mirroring the ``FLAGS_metrics`` gate.

Dump file format (version 1), one JSON object per rank::

    {"version": 1, "rank": R, "host": "...", "pid": N, "dumped_at": ts,
     "timeout_s": T, "timed_out_seq": S|null, "last_completed_seq": L,
     "desync": {...}|null,
     "records": [{"seq", "op", "shapes", "dtypes", "bytes", "axis",
                  "start", "end", "duration_s", "status"}, ...]}

``status`` is one of ``inflight`` / ``ok`` / ``error`` / ``timeout``.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Mapping, Optional, Sequence

from .. import flags as _flags
from .. import observability as _obs

__all__ = [
    "CollectiveTimeout", "FlightRecord", "FlightRecorder", "recorder",
    "enabled", "set_recording", "timeout_s", "start_record", "end_record",
    "current_record", "simulate_hang", "handle_timeout", "stop_monitor",
    "attach_store", "detach_store", "publish_progress", "desync_report",
    "merge_dumps", "first_divergence", "metrics", "dump_to",
]

# grab the flag OBJECTS once (same trick as observability): the hot-path
# enabled check is a plain attribute read, no registry lookup
_TIMEOUT_FLAG = _flags._registry["FLAGS_collective_timeout"]
_SIZE_FLAG = _flags._registry["FLAGS_flight_record_size"]
_INTERVAL_FLAG = _flags._registry["FLAGS_watchdog_interval"]

# watchdog.* metrics slice (ISSUE 3): dots match the resilience.* idiom so
# the JSON snapshot consumers key off the prefix
_M_RECORDED = _obs.registry().counter(
    "watchdog.collectives_recorded",
    "collective calls logged by the flight recorder")
_M_TIMEOUTS = _obs.registry().counter(
    "watchdog.timeouts", "in-flight collectives past FLAGS_collective_timeout",
    labels=("collective",))
_M_DUMPS = _obs.registry().counter(
    "watchdog.dumps_written", "flight-recorder ring dumps written to disk")
_G_LAST_SEQ = _obs.registry().gauge(
    "watchdog.last_completed_seq",
    "seq of the newest collective that finished ok on this rank")


def metrics() -> Dict[str, Any]:
    """The watchdog.* slice of the registry snapshot."""
    return {k: v for k, v in _obs.registry().snapshot().items()
            if k.startswith("watchdog.")}


class CollectiveTimeout(RuntimeError):
    """An in-flight collective exceeded ``FLAGS_collective_timeout``.

    Carries the diagnosis so the failure names its culprit instead of
    burning a pod on a silent hang: the hung op and its seq, elapsed
    seconds, the flight-dump path, and (when a store is attached) the
    lagging rank from the cross-rank desync report.
    """

    def __init__(self, msg: str, op: Optional[str] = None,
                 seq: Optional[int] = None,
                 elapsed_s: Optional[float] = None,
                 dump_path: Optional[str] = None,
                 lagging_rank: Optional[int] = None):
        super().__init__(msg)
        self.op = op
        self.seq = seq
        self.elapsed_s = elapsed_s
        self.dump_path = dump_path
        self.lagging_rank = lagging_rank


def enabled() -> bool:
    """Whether the flight recorder is recording (watchdog armed via
    ``FLAGS_collective_timeout`` > 0, or recording forced for tooling)."""
    return _forced_recording or _TIMEOUT_FLAG.value > 0


def timeout_s() -> float:
    return float(_TIMEOUT_FLAG.value)


_forced_recording = False


def set_recording(on: bool) -> None:
    """Force flight recording on/off independent of the watchdog deadline
    (offline tooling / tests want the ring without arming timeouts)."""
    global _forced_recording
    _forced_recording = bool(on)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
class FlightRecord:
    """One collective call in the ring. Mutated in place by finish() and
    the watchdog (cancelled/status/dump_path)."""

    __slots__ = ("seq", "op", "shapes", "dtypes", "bytes", "axis",
                 "start", "end", "mono", "status", "cancelled",
                 "dump_path", "lagging_rank")

    def __init__(self, seq: int, op: str, shapes=(), dtypes=(),
                 bytes: int = 0, axis: Optional[str] = None):
        self.seq = seq
        self.op = op
        self.shapes = [list(s) for s in shapes]
        self.dtypes = [str(d) for d in dtypes]
        self.bytes = int(bytes)
        self.axis = axis
        self.start = time.time()
        self.mono = time.monotonic()
        self.end: Optional[float] = None
        self.status = "inflight"
        self.cancelled = False
        self.dump_path: Optional[str] = None
        self.lagging_rank: Optional[int] = None

    @property
    def elapsed_s(self) -> float:
        return time.monotonic() - self.mono

    def to_dict(self) -> Dict[str, Any]:
        dur = (self.end - self.start) if self.end is not None else None
        return {"seq": self.seq, "op": self.op, "shapes": self.shapes,
                "dtypes": self.dtypes, "bytes": self.bytes,
                "axis": self.axis, "start": self.start, "end": self.end,
                "duration_s": dur, "status": self.status}


class FlightRecorder:
    """Fixed-size, thread-safe ring of FlightRecords with a monotonic seq
    counter. In-flight records are indexed separately so the watchdog scan
    is O(inflight), not O(ring)."""

    def __init__(self, capacity: Optional[int] = None):
        self.capacity = int(capacity if capacity is not None
                            else _SIZE_FLAG.value)
        self._ring: collections.deque = collections.deque(
            maxlen=max(1, self.capacity))
        self._inflight: Dict[int, FlightRecord] = {}
        self._seq = 0
        self._last_completed: Optional[FlightRecord] = None
        self._lock = threading.Lock()

    def start(self, op: str, shapes=(), dtypes=(), bytes: int = 0,
              axis: Optional[str] = None) -> FlightRecord:
        with self._lock:
            self._seq += 1
            rec = FlightRecord(self._seq, op, shapes, dtypes, bytes, axis)
            self._ring.append(rec)
            self._inflight[rec.seq] = rec
        _M_RECORDED.inc()
        return rec

    def finish(self, rec: FlightRecord, status: str = "ok") -> None:
        rec.end = time.time()
        # a watchdog-cancelled record stays "timeout" even if the caller
        # reports ok (the op completed only because the hang drill ended)
        if not (rec.cancelled and status == "ok"):
            rec.status = status
        with self._lock:
            self._inflight.pop(rec.seq, None)
            if status == "ok" and not rec.cancelled:
                if self._last_completed is None \
                        or rec.seq > self._last_completed.seq:
                    self._last_completed = rec
        if status == "ok" and not rec.cancelled:
            _G_LAST_SEQ.set(rec.seq)

    def inflight(self) -> List[FlightRecord]:
        with self._lock:
            return list(self._inflight.values())

    def last_completed(self) -> Optional[FlightRecord]:
        return self._last_completed

    def records(self) -> List[FlightRecord]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._inflight.clear()
            self._last_completed = None

    def dump(self, **extra: Any) -> Dict[str, Any]:
        last = self._last_completed
        out = {
            "version": 1,
            "rank": _rank(),
            "host": os.uname().nodename,
            "pid": os.getpid(),
            "dumped_at": time.time(),
            "timeout_s": timeout_s(),
            "timed_out_seq": None,
            "last_completed_seq": last.seq if last is not None else 0,
            "desync": None,
            "records": [r.to_dict() for r in self.records()],
        }
        out.update(extra)
        return out

    def dump_to(self, path: Optional[str] = None, **extra: Any) -> str:
        """Write the ring as JSON. Default location is the worker's log
        dir (``PADDLE_LOG_DIR``, cwd fallback) as ``flightdump.<rank>.json``
        — the name ``CollectiveController`` collects on child failure."""
        if path is None:
            d = os.environ.get("PADDLE_LOG_DIR", ".")
            path = os.path.join(d, f"flightdump.{_rank()}.json")
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.dump(**extra), f, indent=2)
        _M_DUMPS.inc()
        return path


_recorder: Optional[FlightRecorder] = None
_recorder_lock = threading.Lock()


def recorder() -> FlightRecorder:
    """The per-process flight recorder (created on first use with the
    then-current ``FLAGS_flight_record_size``)."""
    global _recorder
    if _recorder is None:
        with _recorder_lock:
            if _recorder is None:
                _recorder = FlightRecorder()
    return _recorder


def reset(capacity: Optional[int] = None) -> FlightRecorder:
    """Replace the recorder (tests / capacity changes)."""
    global _recorder
    with _recorder_lock:
        _recorder = FlightRecorder(capacity)
    return _recorder


def dump_to(path: Optional[str] = None, **extra: Any) -> str:
    return recorder().dump_to(path, **extra)


# ---------------------------------------------------------------------------
# call-site hooks (collective.py)
# ---------------------------------------------------------------------------
_current = threading.local()


def start_record(op: str, shapes=(), dtypes=(), bytes: int = 0,
                 axis: Optional[str] = None) -> Optional[FlightRecord]:
    """Called at every collective entry. Returns None (one attribute test)
    when neither the watchdog nor forced recording is on."""
    if not enabled():
        return None
    rec = recorder().start(op, shapes, dtypes, bytes, axis)
    _current.rec = rec
    if _TIMEOUT_FLAG.value > 0:
        _ensure_monitor()
    return rec


def end_record(rec: Optional[FlightRecord], status: str = "ok") -> None:
    if rec is None:
        return
    recorder().finish(rec, status)
    if getattr(_current, "rec", None) is rec:
        _current.rec = None


def current_record() -> Optional[FlightRecord]:
    """The calling thread's in-flight record (set by start_record); lets
    deep wait sites — barrier's fence, the injected hang loop — reach the
    record the wrapper opened."""
    return getattr(_current, "rec", None)


# ---------------------------------------------------------------------------
# timeout handling
# ---------------------------------------------------------------------------
_handle_lock = threading.Lock()


def handle_timeout(rec: FlightRecord) -> None:
    """Declare `rec` timed out: mark it, compute the cross-rank desync
    report when a store is attached, dump the ring next to the worker
    log, count the event, and only then cancel the record. Idempotent per
    record (the monitor and a cooperative wait site may race to report
    the same hang): the loser waits on the lock until the winner's dump
    is written, and a wait site that polls `rec.cancelled` without the
    lock never sees it set while `dump_path` / `lagging_rank` are still
    to come."""
    with _handle_lock:
        if rec.cancelled:
            return
        rec.status = "timeout"
        _M_TIMEOUTS.labels(collective=rec.op).inc()
        desync = None
        with contextlib.suppress(Exception):
            publish_progress()          # let peers see where we stopped
            desync = desync_report()
        if desync is not None:
            rec.lagging_rank = desync.get("lagging_rank")
        with contextlib.suppress(Exception):
            rec.dump_path = recorder().dump_to(
                timed_out_seq=rec.seq, desync=desync)
        rec.cancelled = True


def timeout_error(rec: Optional[FlightRecord], op: str,
                  elapsed_s: float) -> CollectiveTimeout:
    """Build the diagnostic exception for a timed-out record."""
    if rec is None:
        return CollectiveTimeout(
            f"collective {op} exceeded FLAGS_collective_timeout="
            f"{timeout_s():g}s after {elapsed_s:.3f}s (flight recorder "
            f"off: no dump)", op=op, elapsed_s=elapsed_s)
    lag = (f", lagging rank {rec.lagging_rank}"
           if rec.lagging_rank is not None else "")
    dump = f"; flight dump: {rec.dump_path}" if rec.dump_path else ""
    return CollectiveTimeout(
        f"collective {rec.op} (seq {rec.seq}) exceeded "
        f"FLAGS_collective_timeout={timeout_s():g}s after "
        f"{elapsed_s:.3f}s{lag}{dump}",
        op=rec.op, seq=rec.seq, elapsed_s=elapsed_s,
        dump_path=rec.dump_path, lagging_rank=rec.lagging_rank)


def simulate_hang(op: str, duration_s: float) -> None:
    """The cooperative hang the `collective_hang` fault kind drives: spin
    in small sleeps until the hang duration elapses (an unguarded hang)
    or the watchdog cancels the in-flight record (the guarded case —
    raise the diagnostic CollectiveTimeout at the call site). Also
    self-checks the deadline so detection does not depend on monitor
    scheduling."""
    rec = current_record()
    end = time.monotonic() + float(duration_s)
    while time.monotonic() < end:
        if rec is not None:
            if rec.cancelled:
                raise timeout_error(rec, op, rec.elapsed_s)
            tmo = timeout_s()
            if tmo > 0 and rec.elapsed_s > tmo:
                handle_timeout(rec)
                continue
        time.sleep(0.002)


# ---------------------------------------------------------------------------
# monitor thread
# ---------------------------------------------------------------------------
_monitor: Optional[threading.Thread] = None
_monitor_stop = threading.Event()
_monitor_lock = threading.Lock()


def _poll_interval() -> float:
    iv = float(_INTERVAL_FLAG.value)
    if iv > 0:
        return iv
    tmo = timeout_s()
    if tmo <= 0:
        return 0.25
    return min(0.25, max(0.01, tmo / 4.0))


def _monitor_loop() -> None:
    while not _monitor_stop.wait(_poll_interval()):
        tmo = timeout_s()
        if tmo <= 0:
            continue
        now = time.monotonic()
        for rec in recorder().inflight():
            if not rec.cancelled and now - rec.mono > tmo:
                handle_timeout(rec)
        with contextlib.suppress(Exception):
            publish_progress()


def _ensure_monitor() -> None:
    global _monitor
    if _monitor is not None and _monitor.is_alive():
        return
    with _monitor_lock:
        if _monitor is not None and _monitor.is_alive():
            return
        _monitor_stop.clear()
        _monitor = threading.Thread(target=_monitor_loop, daemon=True,
                                    name="pt-collective-watchdog")
        _monitor.start()


def stop_monitor() -> None:
    """Stop the monitor thread (tests)."""
    global _monitor
    with _monitor_lock:
        if _monitor is None:
            return
        _monitor_stop.set()
        _monitor.join(timeout=2.0)
        _monitor = None


# ---------------------------------------------------------------------------
# cross-rank progress publishing + desync report
# ---------------------------------------------------------------------------
class _Attached:
    __slots__ = ("store", "rank", "world_size", "slot")

    def __init__(self, store, rank: int, world_size: int, slot: int):
        self.store = store
        self.rank = rank
        self.world_size = world_size
        self.slot = slot


_attached: Optional[_Attached] = None
_attach_lock = threading.Lock()
_auto_attach_failed = False


def _rank() -> int:
    if _attached is not None:
        return _attached.rank
    return int(os.environ.get("PADDLE_TRAINER_ID", "0"))


def attach_store(store, rank: Optional[int] = None,
                 world_size: Optional[int] = None,
                 slot: Optional[int] = None) -> None:
    """Attach the rendezvous TCPStore so this rank's progress is visible
    cross-rank. The launcher env (PADDLE_MASTER/PADDLE_TRAINER_ID/...)
    auto-attaches lazily; tests and controllers call this directly."""
    global _attached
    r = int(os.environ.get("PADDLE_TRAINER_ID", "0")) if rank is None \
        else int(rank)
    ws = world_size
    if ws is None:
        ws = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    if slot is None:
        nnodes = int(os.environ.get("PADDLE_NNODES", "1"))
        nproc = max(1, ws // max(1, nnodes))
        slot = r // nproc
    with _attach_lock:
        _attached = _Attached(store, r, ws, slot)


def detach_store() -> None:
    global _attached, _auto_attach_failed
    with _attach_lock:
        _attached = None
        _auto_attach_failed = False


def _maybe_auto_attach() -> Optional[_Attached]:
    """Client-connect to PADDLE_MASTER once when running under the
    launcher; a failed attempt is remembered so a dead master does not
    stall every publish."""
    global _auto_attach_failed
    if _attached is not None:
        return _attached
    if _auto_attach_failed:
        return None
    master = os.environ.get("PADDLE_MASTER")
    if not master:
        return None
    from ..native import TCPStore
    try:
        host, _, port = master.rpartition(":")
        store = TCPStore(host=host or "127.0.0.1", port=int(port),
                         is_master=False, world_size=1, timeout=5.0)
        attach_store(store)
    except Exception:
        with _attach_lock:
            _auto_attach_failed = True
        return None
    return _attached


def publish_progress() -> None:
    """Publish this rank's last-completed seq/op (and in-flight op, if
    any) to the store: a ``flight/<rank>`` key the controller's desync
    report reads, plus the node's ``heartbeat/<slot>`` key using the
    ``|``-payload channel ``ElasticManager.alive_nodes`` already splits
    off. Best-effort: any store failure is swallowed."""
    att = _maybe_auto_attach()
    if att is None:
        return
    rec = recorder()
    last = rec.last_completed()
    stuck = rec.inflight()
    cur = min(stuck, key=lambda r: r.seq) if stuck else None
    payload = (f"rank={att.rank}"
               f",seq={last.seq if last is not None else 0}"
               f",op={last.op if last is not None else ''}"
               f",inflight={cur.op if cur is not None else ''}"
               f",inflight_seq={cur.seq if cur is not None else 0}"
               f",status={cur.status if cur is not None else 'idle'}")
    with contextlib.suppress(Exception):
        att.store.set(f"flight/{att.rank}", f"{time.time()}|{payload}")
        att.store.set(f"heartbeat/{att.slot}", f"{time.time()}|{payload}")


def _parse_payload(raw: bytes) -> Optional[Dict[str, Any]]:
    try:
        text = raw.decode() if isinstance(raw, bytes) else str(raw)
        ts, _, payload = text.partition("|")
        out: Dict[str, Any] = {"ts": float(ts)}
        for part in payload.split(","):
            k, _, v = part.partition("=")
            if not k:
                continue
            out[k] = int(v) if v.lstrip("-").isdigit() else v
        return out
    except (ValueError, AttributeError):
        return None


def desync_report(store=None, world_size: Optional[int] = None) \
        -> Optional[Dict[str, Any]]:
    """Read every rank's published flight progress and name the laggard:
    the rank with the lowest last-completed seq (ranks that never
    published count as seq -1) plus the op it reports being stuck on.
    Returns None when no store is reachable."""
    att = _maybe_auto_attach()
    if store is None:
        if att is None:
            return None
        store = att.store
    ws = world_size
    if ws is None:
        ws = att.world_size if att is not None else 1
    ranks: Dict[int, Dict[str, Any]] = {}
    for r in range(ws):
        v = store.get(f"flight/{r}")
        if v is None:
            continue
        info = _parse_payload(v)
        if info is not None:
            ranks[r] = info
    missing = [r for r in range(ws) if r not in ranks]
    if not ranks:
        return {"world_size": ws, "ranks": {}, "missing": missing,
                "lagging_rank": missing[0] if missing else None,
                "lagging_op": None, "min_seq": None, "max_seq": None,
                "desynced": bool(missing)}
    seqs = {r: int(info.get("seq", 0)) for r, info in ranks.items()}
    for r in missing:
        seqs[r] = -1
    lag = min(sorted(seqs), key=lambda r: seqs[r])
    lag_info = ranks.get(lag, {})
    lag_op = lag_info.get("inflight") or lag_info.get("op") or None
    return {
        "world_size": ws,
        "ranks": ranks,
        "missing": missing,
        "lagging_rank": lag,
        "lagging_op": lag_op,
        "min_seq": min(seqs.values()),
        "max_seq": max(seqs.values()),
        "desynced": min(seqs.values()) != max(seqs.values()),
    }


# ---------------------------------------------------------------------------
# post-mortem merge (offline: tools/flight_recorder.py; online: controller)
# ---------------------------------------------------------------------------
def _by_rank(dumps) -> Dict[int, List[Mapping[str, Any]]]:
    if isinstance(dumps, Mapping):
        return {int(r): list(d.get("records", d) if isinstance(d, Mapping)
                             else d) for r, d in dumps.items()}
    out: Dict[int, List[Mapping[str, Any]]] = {}
    for i, d in enumerate(dumps):
        out[int(d.get("rank", i))] = list(d.get("records", []))
    return out


def first_divergence(dumps) -> Optional[Dict[str, Any]]:
    """Scan merged per-rank records seq by seq for the first point where
    ranks disagree: an op/shape mismatch (desynced program order — the
    classic cross-rank deadlock), a non-ok status (the hung op itself),
    or a rank missing a seq that later ranks completed past (a laggard).
    ``dumps`` is a list of dump dicts or {rank: records} mapping."""
    per_rank = _by_rank(dumps)
    if not per_rank:
        return None
    max_seq = {r: max((int(rec.get("seq", 0)) for rec in recs), default=0)
               for r, recs in per_rank.items()}
    by_seq: Dict[int, Dict[int, Mapping[str, Any]]] = {}
    for r, recs in per_rank.items():
        for rec in recs:
            by_seq.setdefault(int(rec.get("seq", 0)), {})[r] = rec
    for seq in sorted(by_seq):
        cell = by_seq[seq]
        ops = {r: rec.get("op") for r, rec in cell.items()}
        sigs = {(rec.get("op"),
                 json.dumps(rec.get("shapes"), sort_keys=True))
                for rec in cell.values()}
        if len(sigs) > 1:
            return {"seq": seq, "reason": "op_mismatch", "ops": ops,
                    "statuses": {r: rec.get("status")
                                 for r, rec in cell.items()}}
        bad = {r: rec.get("status") for r, rec in cell.items()
               if rec.get("status") != "ok"}
        if bad:
            return {"seq": seq, "reason": "not_ok", "ops": ops,
                    "statuses": {r: rec.get("status")
                                 for r, rec in cell.items()},
                    "bad_ranks": sorted(bad)}
        behind = [r for r in per_rank if r not in cell and max_seq[r] < seq]
        if behind and len(cell) < len(per_rank):
            return {"seq": seq, "reason": "missing_rank", "ops": ops,
                    "missing": sorted(behind)}
    return None


def merge_dumps(dumps: Sequence[Mapping[str, Any]]) -> Dict[str, Any]:
    """Combine per-rank flight dumps into one post-mortem report: the
    per-rank last-completed seq, the lagging rank, the first divergence,
    and the union of records sorted by (seq, rank)."""
    per_rank = {int(d.get("rank", i)): d for i, d in enumerate(dumps)}
    records: List[Dict[str, Any]] = []
    last_seq: Dict[int, int] = {}
    for r, d in sorted(per_rank.items()):
        last_seq[r] = int(d.get("last_completed_seq", 0))
        for rec in d.get("records", []):
            records.append({**rec, "rank": r})
    records.sort(key=lambda x: (int(x.get("seq", 0)), int(x["rank"])))
    lagging = (min(sorted(last_seq), key=lambda r: last_seq[r])
               if last_seq else None)
    return {
        "version": 1,
        "world": len(per_rank),
        "ranks": sorted(per_rank),
        "last_completed_seq": last_seq,
        "lagging_rank": lagging,
        "first_divergence": first_divergence(list(per_rank.values())),
        "records": records,
    }
