"""What every run shares: the device check, the compile cache, host
spans, the traced window and the result line."""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
from typing import Dict, Optional

from . import trace as _trace
from .compile_log import CompileLog
from .peaks import PEAKS, require_peak

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
#: traces of the --trace 1 run; git-ignored, inside the checkout
TRACE_DIR = os.path.join(REPO, ".scratch", "bench_trace")


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def as_run(table: dict, rehearse: bool) -> dict:
    """A configuration or a mix as run: under the rehearsal switch its
    ``rehearsal`` table overrides the sizes, so that toy widths work."""
    out = {k: v for k, v in table.items() if k != "rehearsal"}
    if rehearse:
        out.update(table.get("rehearsal", {}))
    return out


class Spans:
    """The benchmark's own host spans: written into the profiler's trace
    (as ``bench.<name>``) while one is on, free otherwise.  The traced
    run reads them back on the trace's own clock."""

    def __init__(self):
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        if self.tracing:
            import jax
            with jax.profiler.TraceAnnotation(_trace.SPAN_PREFIX + name):
                yield
        else:
            yield


class Harness:
    """One run of one cell.  ``t_start`` is the process's start as
    ``run.py`` took it on its first line."""

    def __init__(self, t_start: float, chips: int, rehearse: bool,
                 trace: bool):
        self.t_start = t_start
        self.chips = chips
        self.rehearse = rehearse
        self.trace = trace
        self.spans = Spans()
        self._xplane: Optional[str] = None
        self._reduced: Optional[_trace.Reduced] = None
        self.counters: Dict[str, object] = {}
        import jax
        devs = jax.devices()
        self.devices = devs[:chips]
        d0 = devs[0]
        if rehearse:
            self.peak = PEAKS["TPU v5 lite"]    # shapes only; no metric
        else:
            if d0.platform != "tpu":
                sys.exit(f"the benchmark needs a TPU; jax found platform "
                         f"{d0.platform!r} ({d0.device_kind!r}). "
                         f"--rehearse runs the control flow off the chip "
                         f"and reports no device metric.")
            self.peak = require_peak(d0.device_kind)
        if len(devs) < chips:
            sys.exit(f"the cell asks for {chips} chips; jax found "
                     f"{len(devs)}")
        from paddle_tpu._bootstrap import configure_compile_cache
        self.cache_dir = configure_compile_cache()
        # every program of a run is cached, however short its compile:
        # a second run in the same checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        self.compiles = CompileLog()
        say(f"device: {len(devs)} x {d0.device_kind} ({d0.platform}); "
            f"using {chips}; compile cache {self.cache_dir}")

    # ---------------------------------------------------------- tracing
    def start_trace(self) -> None:
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR, exist_ok=True)
        # device operations and the benchmark's own spans; no Python
        # function tracer (millions of events, and it slows the host)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self.spans.tracing = True
        self._window = jax.profiler.TraceAnnotation(_trace.WINDOW_SPAN)
        self._window.__enter__()

    def stop_trace(self) -> None:
        import jax
        self._window.__exit__(None, None, None)
        self.spans.tracing = False
        jax.profiler.stop_trace()
        self._xplane = _trace.find_xplane(TRACE_DIR)
        if self._xplane is None:
            say("the profiler wrote no .xplane.pb")

    @property
    def reduced(self) -> Optional[_trace.Reduced]:
        """The traced window, reduced; read after the measured window so
        that parsing the trace does not hold up the system."""
        if self._reduced is None and self._xplane is not None:
            red = _trace.reduce_trace(_trace.load_xplane(self._xplane))
            self._reduced, self._xplane = red, None
            say(f"trace: window {red.window_s:.3f}s, device busy "
                f"{red.busy_s:.3f}s, {len(red.op_seconds)} distinct ops "
                f"on {len(red.busy_s_by_device)} device planes, "
                f"{len(red.spans)} host spans")
            out = os.path.join(REPO, "chiprun_out")
            if os.path.isdir(out):  # the builder's chip tool brings it back
                top = sorted(red.op_seconds.items(), key=lambda kv: -kv[1])
                with open(os.path.join(out, "trace_ops.json"), "w") as f:
                    json.dump({"window_s": red.window_s,
                               "busy_s": red.busy_s, "ops": top[:300],
                               "idle_by_span": red.idle_by_span}, f,
                              indent=1)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return self._reduced

    # ----------------------------------------------------------- result
    def device_block(self) -> dict:
        d0 = self.devices[0]
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        block = {"platform": d0.platform, "kind": d0.device_kind,
                 "count": len(self.devices), "memory_peak_bytes": peak}
        if self.trace and self.reduced is not None and not self.rehearse:
            block["busy_s"] = self.reduced.busy_s
            block["window_s"] = self.reduced.window_s
        return block
