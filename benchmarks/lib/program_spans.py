"""The window's records from the program's own recorder
(``paddle_tpu.observability.tracing.recorder()``): one step record per
``ServingEngine.step()`` with its phase spans and counts, and the request
timelines with their ``enqueue`` / ``admit`` / ``prefill_chunk`` / ``token``
stamps.

Every harness step is one ``engine.step()`` call and nothing steps the
engine after the window, so the last ``len(h.counters["steps"])`` step
records are the window's, in the harness's order.  "In the window" is
the harness's ``t <= seconds`` (the drain after an open-loop window is
not), "traced" is the harness's flag of the same step.  The window's
requests are those enqueued after the end of the last step record before
the window (the warm-up's).

A program without step records (a parent of the PR that brought them, or
one run with ``FLAGS_request_tracing`` off) gives ``None`` everywhere,
and the metric is left out of the line.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from . import stats
from .harness import say

SPAN_PREFIX = "serving.engine."
PHASES = ("admit", "build", "launch", "sync", "sample", "account")


class Window(NamedTuple):
    steps: List[Tuple[dict, dict]]   # (harness observation, step record)
    n_in: int                        # the first n_in are in the window
    cut_ns: int                      # end of the last record before them


def mean(values: Sequence[float]) -> float:
    return sum(values) / len(values)


def _records() -> Optional[List[dict]]:
    from paddle_tpu.observability import tracing
    steps = getattr(tracing.recorder(), "steps", None)
    return steps() if steps is not None else None


def pair(seen: Optional[Sequence[dict]], n_in: Optional[int],
         records: Optional[Sequence[dict]]) -> Optional[Window]:
    """Pair the harness's step observations with the program's records;
    ``None`` (with a line saying why) where they cannot be paired."""
    if not seen or not records:
        return None
    if len(records) < len(seen):
        say(f"program spans: {len(seen)} harness steps but only "
            f"{len(records)} step records (the ring holds "
            f"FLAGS_trace_ring_size); not reported")
        return None
    mine = list(records[-len(seen):])
    for s, r in zip(seen, mine):
        if s["prefill"] != r["prefill_rows"]:
            say(f"program spans: harness step {s} does not match record "
                f"seq {r['seq']}; not reported")
            return None
    before = records[-len(seen) - 1] if len(records) > len(seen) else None
    return Window(list(zip(seen, mine)),
                  len(seen) if n_in is None else int(n_in),
                  before["end_ns"] if before else 0)


def window(h) -> Optional[Window]:
    """The run's window, paired once and kept with the run."""
    if "program_window" not in h.counters:
        h.counters["program_window"] = pair(
            h.counters.get("steps"), h.counters.get("steps_in_window"),
            _records())
    return h.counters["program_window"]


def in_window(w: Window) -> List[Tuple[dict, dict]]:
    return w.steps[:w.n_in]


def phase_ms_of(record: dict) -> Dict[str, float]:
    """Milliseconds in each phase of one step (a phase that ran twice,
    as on the split path, adds up), and ``step`` for the whole span."""
    out = dict.fromkeys(PHASES, 0.0)
    for name, a, b in record["phases"]:
        key = name[len(SPAN_PREFIX):]
        out[key] = out.get(key, 0.0) + (b - a) / 1e6
    out["step"] = (record["end_ns"] - record["start_ns"]) / 1e6
    return out


def phase_ms(h, phase: str) -> Optional[float]:
    """Mean length of ``phase`` over the window's steps NOT under the
    profiler; says the median, the count and the traced steps' mean."""
    w = window(h)
    if w is None:
        return None
    plain = [phase_ms_of(r)[phase] for s, r in in_window(w)
             if not s["traced"]]
    traced = [phase_ms_of(r)[phase] for s, r in in_window(w) if s["traced"]]
    if not plain:
        return None
    say(f"engine phase {phase}: mean {mean(plain):.3f} ms, median "
        f"{stats.percentile(plain, 50):.3f}, max {max(plain):.3f} over "
        f"{len(plain)} steps not under the profiler; under it mean "
        + (f"{mean(traced):.3f} ms over {len(traced)}" if traced
           else "(none traced)"))
    return mean(plain)


def count_mean(h, key: str) -> Optional[float]:
    """Mean of one count of the step records over the window's steps."""
    w = window(h)
    if w is None or not in_window(w):
        return None
    vals = [r[key] for _, r in in_window(w)]
    say(f"step count {key}: mean {mean(vals):.3f}, median "
        f"{stats.percentile(vals, 50):g}, max {max(vals)} over "
        f"{len(vals)} steps")
    return mean(vals)


# ------------------------------------------------------------- requests

def window_requests(w: Window) -> list:
    """The program's timelines of the requests enqueued in the window
    (finished or still live)."""
    from paddle_tpu.observability import tracing
    rec = tracing.recorder()
    out = []
    for t in rec.finished(kind="request") + rec.live():
        enq = t.first("enqueue")
        if t.kind == "request" and enq is not None \
                and enq.t_us * 1000 > w.cut_ns:
            out.append(t)
    return out


def request_phase_p95_ms(h, method: str) -> Optional[float]:
    """95th percentile over the window's requests of one of
    ``queue_wait_s`` / ``prefill_wait_s`` / ``prefill_run_s``."""
    w = window(h)
    if w is None:
        return None
    traces = window_requests(w)
    vals = [1e3 * v for v in (getattr(t, method)() for t in traces)
            if v is not None]
    if not vals:
        return None
    say(f"program {method}: {stats.summary(vals)} ms over {len(vals)} of "
        f"{len(traces)} requests enqueued in the window")
    return stats.percentile(vals, 95)


def say_request_books(h) -> None:
    """The three request phases against the program's TTFT, and the
    program's TTFT against the harness's (which starts at the due time
    and ends when ``step()`` returns)."""
    w = window(h)
    if w is None:
        return
    worst, ttft = 0.0, []
    for t in window_requests(w):
        parts = [t.queue_wait_s(), t.prefill_wait_s(), t.prefill_run_s()]
        if None in parts or t.ttft_s() is None:
            continue
        worst = max(worst, abs(sum(parts) - t.ttft_s()))
        ttft.append(1e3 * t.ttft_s())
    outside = h.counters.get("ttft_ms") or []
    if ttft and len(ttft) == len(outside):
        over = [a - b for a, b in zip(sorted(outside), sorted(ttft))]
        say(f"request books: queue + prefill wait + prefill run differ "
            f"from the program's TTFT by at most {worst * 1e3:.6f} ms "
            f"over {len(ttft)} requests; the harness's TTFT exceeds the "
            f"program's by {stats.summary(over)} ms (rank by rank)")
    elif ttft:
        say(f"request books: {len(ttft)} program TTFTs, {len(outside)} "
            f"harness TTFTs; sum of phases off by at most "
            f"{worst * 1e3:.6f} ms")


# ---------------------------------------------------------- the books

def say_step_books(h) -> None:
    """Over the TRACED steps: the six phases against the benchmark's own
    span around ``step()``, and the host's share against the outside
    metric ``engine_host_ms_per_step``."""
    w = window(h)
    if w is None or h.reduced is None:
        return
    from .trace import busy_inside
    traced = [phase_ms_of(r) for s, r in in_window(w) if s["traced"]]
    pairs = busy_inside(h.reduced, "engine.step")
    if not traced or not pairs:
        return
    six = mean([sum(p[k] for k in PHASES) for p in traced])
    whole = mean([p["step"] for p in traced])
    outside = 1e3 * mean([length for length, _ in pairs])
    device = 1e3 * mean([busy for _, busy in pairs])
    say(f"step books over {len(traced)} traced steps ({len(pairs)} "
        f"bench.engine.step spans): six phases {six:.3f} ms, "
        f"serving.engine.step {whole:.3f} ms, bench.engine.step "
        f"{outside:.3f} ms (six - bench = {six - outside:+.3f}); device "
        f"busy {device:.3f} ms, so host by phases {six - device:.3f} ms "
        f"against engine_host_ms_per_step {outside - device:.3f}")
    say("traced phase means ms: " + ", ".join(
        f"{k} {mean([p[k] for p in traced]):.3f}" for k in PHASES))
    plain = [phase_ms_of(r) for s, r in in_window(w) if not s["traced"]]
    if plain:
        say(f"between the phases, inside serving.engine.step (the spans' "
            f"own exits and entries): {whole - six:.3f} ms a traced step, "
            f"{mean([p['step'] - sum(p[k] for k in PHASES) for p in plain]):.3f}"
            f" ms a step not under the profiler ({len(plain)})")
