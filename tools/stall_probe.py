"""Why was that step long?  Runs a serving cell exactly as
`benchmarks/run.py` does, with every `ServingEngine.step()` watched from
OUTSIDE the program (nothing in `paddle_tpu/` or `benchmarks/` is edited
or slowed: two clock reads a step):

- wall time against the calling thread's CPU time (Python or C work
  on the host shows as CPU; a wait for the device or the runtime does
  not);
- every garbage collection's pause (`gc.callbacks`), and which step it
  fell into;
- a second thread that only sleeps 5 ms at a time: it wakes late where
  the WHOLE process was not run (or the interpreter lock was held), not
  where the main thread slept in the runtime with the lock released;
- the device's `memory_stats()` before and after a long step;
- the step record's own phases (`serving.engine.admit` ... `.account`)
  and counts of that step.

At exit it prints the steps over 100 ms with those readings.  PR 50's
runs (PERF.md section 6; ROADMAP S9): the one 0.25-0.33 s step of every
run is a generation-2 collection (~400 k tracked objects, 250-265 ms of
thread CPU inside whichever host phase it falls into); the 0.13-0.15 s
steps and the seconds-long stall are the whole process not being run
(thread CPU 0-30 ms, under `sync`, the idle thread late by 108-116 ms /
3,651 ms; no collection, no compile, the device's memory unchanged).

    chiprun -- python3 tools/stall_probe.py --workload xing4-serve-assistant-steady --seed 2147491091 --seconds 50 --trace 0
    JAX_PLATFORMS=cpu python3 tools/stall_probe.py --workload xing4-serve-assistant-steady --seed 7 --seconds 3 --trace 0 --rehearse

(`/proc/stat`, `/proc/loadavg`, the thread's context switches and the
cgroup's throttling read zero or nothing on the chip's machine, so they
are not taken.)
"""

import atexit
import gc
import os
import runpy
import sys
import threading
import time

LONG_STEP_NS = 100e6
LATE_WAKE_NS = 30e6

_pauses, _gc_start = [], [0]      # (start_ns, ns, generation, freed)
_late = []                        # (start_ns, ms) of the idle thread
_steps, _mem_prev = [], {}


def _on_gc(phase, info):
    if phase == "start":
        _gc_start[0] = time.perf_counter_ns()
        return
    took = time.perf_counter_ns() - _gc_start[0]
    if took > 5e6:
        _pauses.append((_gc_start[0], took, info["generation"],
                        info["collected"]))


def _device_memory():
    try:
        import jax
        m = jax.local_devices()[0].memory_stats() or {}
        return {k: m[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                  "num_allocs", "largest_free_block_bytes")
                if k in m}
    except Exception as e:      # a probe must not fail the run
        return {"error": repr(e)[:80]}


def _idle_thread():
    prev = time.perf_counter_ns()
    while True:
        time.sleep(0.005)
        now = time.perf_counter_ns()
        if now - prev > LATE_WAKE_NS:
            _late.append((prev, (now - prev) / 1e6))
        prev = now


def _watch_steps():
    from paddle_tpu.serving import engine as eng
    inner = eng.ServingEngine.step

    def step(self, *a, **k):
        w0, c0 = time.perf_counter_ns(), time.thread_time_ns()
        out = inner(self, *a, **k)
        w1 = time.perf_counter_ns()
        if w1 - w0 > LONG_STEP_NS:
            _steps.append(dict(
                start_ns=w0, end_ns=w1, wall_ms=(w1 - w0) / 1e6,
                cpu_ms=(time.thread_time_ns() - c0) / 1e6,
                mem_before=dict(_mem_prev), mem_after=_device_memory()))
        _mem_prev.clear()
        _mem_prev.update(_device_memory())
        return out

    eng.ServingEngine.step = step
    threading.Thread(target=_idle_thread, daemon=True).start()


def _report():
    from paddle_tpu.observability import tracing
    recs = [s for s in tracing.recorder().steps()
            if s.get("start_ns") is not None]
    say = lambda t: print("[probe] " + t, flush=True)      # noqa: E731
    say(f"collections over 5 ms: {len(_pauses)}: " + "; ".join(
        f"gen{g} {ns / 1e6:.0f} ms ({n} freed)"
        for _, ns, g, n in _pauses[-40:]))
    say(f"gc counts {gc.get_count()}, {len(gc.get_objects())} objects "
        f"tracked, thresholds {gc.get_threshold()}; {os.cpu_count()} cores")
    keep = ("seq", "decode_rows", "prefill_rows", "live", "admitted",
            "finished", "compiles", "pool_pages_used", "launch_ahead")
    for s in sorted(_steps, key=lambda s: -s["wall_ms"])[:10]:
        a, b = s["start_ns"], s["end_ns"]
        rec = next((r for r in recs if a <= r["start_ns"] <= b), None)
        phases = {n.rsplit(".", 1)[-1]: round((e - st) / 1e6, 1)
                  for n, st, e in (rec["phases"] if rec else ())}
        say(f"step {s['wall_ms']:.0f} ms wall, thread cpu "
            f"{s['cpu_ms']:.0f}; collections inside "
            f"{[(round(ns / 1e6), g) for t, ns, g, _ in _pauses if a <= t <= b]}"
            f"; the idle thread's late wake-ups ms "
            f"{[round(ms) for t, ms in _late if a <= t <= b]}; phases "
            f"{phases}; device memory before {s['mem_before']} after "
            f"{s['mem_after']}; record "
            f"{ {k: rec[k] for k in keep if k in rec} if rec else None}")
    say(f"{len(_steps)} steps over {LONG_STEP_NS / 1e6:.0f} ms; the idle "
        f"thread woke over {LATE_WAKE_NS / 1e6:.0f} ms late {len(_late)} "
        f"times: {[round(ms) for _, ms in _late[-30:]]}")


if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    gc.callbacks.append(_on_gc)
    _watch_steps()
    atexit.register(_report)
    sys.argv = [os.path.join(root, "benchmarks", "run.py")] + sys.argv[1:]
    runpy.run_path(sys.argv[0], run_name="__main__")
