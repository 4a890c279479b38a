"""Shared measurement helpers for the on-chip bench tools.

jax returns before the device finishes; every timing here ends in
``jax.block_until_ready`` so it measures the work, not the enqueue.
"""

from __future__ import annotations

import importlib.util
import sys
import time


def load_text(module: str, name: str, path: str):
    """Another checkout's copy of `paddle_tpu.ops.<module>` (a file at
    `path`) as a module of THIS package, so that its relative imports
    resolve here: a kernel text to time beside this tree's."""
    spec = importlib.util.spec_from_file_location(
        f"paddle_tpu.ops._{module}_text_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def device_events(run, args, calls: int = 3) -> dict:
    """Device seconds a call of ``run(*args)`` by event kind (``stem
    opcode shape``), from a `jax.profiler` trace of ``calls`` calls:
    every device event but loops, conditionals and calls, which span
    their bodies' events. Empty where the backend traces no device."""
    import shutil
    import tempfile
    import jax
    from benchmarks.lib.trace import base_name, find_xplane, load_xplane
    where = tempfile.mkdtemp(prefix="device_events_")
    try:
        jax.profiler.start_trace(where)
        for _ in range(calls):
            jax.block_until_ready(run(*args))
        jax.profiler.stop_trace()
        path = find_xplane(where)
        ops = load_xplane(path).device_ops if path else {}
    finally:
        shutil.rmtree(where, ignore_errors=True)
    by_kind: dict = {}
    for events in ops.values():
        for e in events:
            kind = base_name(e.name)        # "stem opcode shape"
            opcode = (kind.split(" ") + ["?"])[1]
            if opcode not in ("while", "conditional", "call"):
                by_kind[kind] = by_kind.get(kind, 0.0) + e.end - e.start
    n = max(len(ops), 1) * calls
    return {kind: s / n for kind, s in by_kind.items()}


def ready(out):
    """Wait until every array in ``out`` has been computed."""
    import jax
    return jax.block_until_ready(out)


def timeit(fn, *args, reps: int = 20, warmup: bool = True) -> float:
    """Seconds per call, steady-state (one warmup/compile call first;
    pass warmup=False for an already-compiled+warm fn whose single call
    dominates wall-clock, e.g. whole decode loops at reps=1)."""
    if warmup:
        ready(fn(*args))
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    ready(out)
    return (time.time() - t0) / reps


def ab_rounds(kernels, rounds: int = 3, reps: int = 20,
              warmup: bool = True):
    """Same-run interleaved A/B: each round times every kernel once, so
    all contenders see the same chip conditions drift. `kernels`
    is {name: (fn, args_tuple)}. Returns {name: [t_round0, ...]} seconds.
    Single-run cross-process comparisons are not evidence; this is the
    one sanctioned comparison shape."""
    runs = {name: [] for name in kernels}
    for _ in range(rounds):
        for name, (fn, args) in kernels.items():
            runs[name].append(timeit(fn, *args, reps=reps,
                                     warmup=warmup))
    return runs


def band(runs_s, scale: float = 1e6):
    """Collapse a list of per-round seconds into mean/min/max/spread
    fields (default unit: µs). spread_pct = (max-min)/mean."""
    mean = sum(runs_s) / len(runs_s)
    return {
        "mean_us": round(mean * scale, 1),
        "min_us": round(min(runs_s) * scale, 1),
        "max_us": round(max(runs_s) * scale, 1),
        "spread_pct": round((max(runs_s) - min(runs_s)) / mean * 100, 1),
    }


def ratio_band(num_runs, den_runs):
    """Per-round ratio num/den plus its min/max band — a claim 'A is
    X x B' must carry this so readers see whether X exceeds the noise."""
    ratios = [n / d for n, d in zip(num_runs, den_runs)]
    mean = sum(ratios) / len(ratios)
    return {"mean": round(mean, 2), "min": round(min(ratios), 2),
            "max": round(max(ratios), 2)}


def write_metrics_snapshot(path: str, extra: dict | None = None) -> dict:
    """Dump the paddle_tpu.observability registry next to the bench rows.

    A bench row says how fast a run was; the metrics snapshot says what the
    run actually did (which kernel routes fired, jit cache hit/miss, bytes
    through collectives) — together they make a bench reproducible. Returns
    the snapshot dict; writes JSON to `path` (parent dirs created)."""
    import json
    import os

    from paddle_tpu import observability as obs

    snap = {"metrics": obs.registry().snapshot()}
    if extra:
        snap.update(extra)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(snap, f, indent=2, sort_keys=True)
    return snap


def write_resilience_report(path: str, extra: dict | None = None) -> dict:
    """Dump the resilience.* metric slice plus the active fault plan after
    a chaos run (docs/RESILIENCE.md): which faults fired, how many steps
    were skipped/rolled back, checkpoint retries/fallbacks, deadline
    misses. The totals line makes 'did every injected fault get handled'
    a one-field check. Returns the report dict; writes JSON to `path`."""
    import json
    import os

    from paddle_tpu import resilience as res

    snap = res.metrics()
    plan = res.active_plan()
    totals = {}
    for name, m in snap.items():
        totals[name] = sum(s["value"] for s in m["series"])
    report = {
        "fault_spec": plan.spec if plan is not None else "",
        "rules_fired": [
            {"kind": r.kind, "when": dict(r.when), "fired": r.fired}
            for r in plan.rules] if plan is not None else [],
        "totals": totals,
        "metrics": snap,
    }
    if extra:
        report.update(extra)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    return report


def write_serving_report(path: str, extra: dict | None = None) -> dict:
    """Dump the serving.engine.* metric slice after a continuous-batching
    run (docs/SERVING.md): requests by outcome, prefill/decode token and
    step counts, page-pool utilization/fragmentation, COW copies and
    shared prefix tokens. The totals line makes 'did every admitted
    request complete' a one-field check; pass the throughput row as
    `extra` so the artifact records rate AND what the engine actually did
    (shares, copies, pool pressure) in one file. The `slo` section
    carries the per-request latency percentiles (p50/p90/p99 TTFT /
    TPOT / e2e / queue-wait from the tracing histograms) so SERVING_BENCH
    rows report tail latency beside throughput. Returns the report dict;
    writes JSON to `path`."""
    import json
    import os

    from paddle_tpu import serving as srv

    snap = srv.metrics()
    totals = {}
    for name, m in snap.items():
        if m.get("kind") == "counter":
            totals[name] = sum(s["value"] for s in m["series"])
    report = {
        "totals": totals,
        "slo": srv.slo(),
        "metrics": snap,
    }
    if extra:
        report.update(extra)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    return report


def write_watchdog_report(path: str, extra: dict | None = None) -> dict:
    """Dump the watchdog.* metric slice plus the live flight-recorder ring
    after a run (docs/RESILIENCE.md): collectives recorded, timeouts per
    op, dumps written, last-completed seq, and the in-memory ring itself —
    the hang post-mortem in one file even when no on-disk flightdump was
    triggered. Returns the report dict; writes JSON to `path`."""
    import json
    import os

    from paddle_tpu.distributed import watchdog as wd

    snap = wd.metrics()
    totals = {}
    for name, m in snap.items():
        if m.get("kind") == "counter":
            totals[name] = sum(s["value"] for s in m["series"])
    report = {
        "totals": totals,
        "metrics": snap,
        "flight": wd.recorder().dump(),
    }
    if extra:
        report.update(extra)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    return report
