"""The serving window: one host thread offers requests to a
``ServingEngine`` and steps it, as a deployment's front end would.

Everything is seen from outside the engine: ``add_request`` returns the
request, ``step()`` returns its counts, and after each step the loop
reads how many tokens each live request holds.  A token's time is the
return of the step that produced it.  Open and closed loops differ only
in the ``Source`` that says which requests are due.
"""

from __future__ import annotations

import time
from typing import Dict, List, Mapping, Optional, Sequence

import numpy as np

from . import stats
from .harness import Harness, say
from .traffic import Req


class OpenSource:
    """Requests due on a schedule, whatever the system does."""

    def __init__(self, reqs: Sequence[Req]):
        self.reqs = list(reqs)
        self.i = 0

    def take(self, now: float) -> List[Req]:
        out = []
        while self.i < len(self.reqs) and self.reqs[self.i].due <= now:
            out.append(self.reqs[self.i])
            self.i += 1
        return out

    def next_due(self) -> Optional[float]:
        return self.reqs[self.i].due if self.i < len(self.reqs) else None

    def finished(self, n: int, now: float) -> None:
        pass


class ClosedSource:
    """``clients`` callers, each sending its next request when the last
    one completes."""

    def __init__(self, reqs: Sequence[Req], clients: int):
        self.reqs = list(reqs)
        self.i = 0
        self.ready = clients

    def take(self, now: float) -> List[Req]:
        out = []
        while self.ready:
            if self.i == len(self.reqs):
                self.i = 0      # round the pool again
            out.append(self.reqs[self.i]._replace(due=now))
            self.i += 1
            self.ready -= 1
        return out

    def next_due(self) -> Optional[float]:
        return None

    def finished(self, n: int, now: float) -> None:
        self.ready += n


class Live:
    __slots__ = ("req", "due", "seen", "times", "admitted", "kv")

    def __init__(self, req, due: float):
        self.req, self.due = req, due
        self.seen = 0
        self.kv = 0
        self.times: List[float] = []
        self.admitted: Optional[float] = None


def run_requests(engine, reqs: Sequence[Req]) -> List[np.ndarray]:
    """Warm-up and correctness sample: the requests all at once, run to
    completion, outside any window."""
    handles = [engine.add_request(r.prompt, max_new_tokens=r.max_new)
               for r in reqs]
    times = []
    while engine.has_work():
        t0 = time.perf_counter()
        engine.step()
        times.append(time.perf_counter() - t0)
        if len(times) > 100000:
            raise RuntimeError("the engine made no progress")
    engine.collect()
    slow = sorted(times)[-3:]
    say(f"warm-up: {len(times)} steps, the slowest "
        f"{[round(t, 3) for t in slow]}s, median "
        f"{sorted(times)[len(times) // 2]:.3f}s")
    return [np.asarray(h.tokens, np.int32) for h in handles]


def window(engine, source, h: Harness, seconds: float, drain_s: float,
           kv_tokens_of, trace_at: Optional[float] = None,
           trace_s: float = 0.0) -> dict:
    """Measure for ``seconds``; then, if ``drain_s`` > 0, keep stepping
    with no new arrivals until the window's requests are done or the
    limit passes.  Returns the raw observations."""
    span = h.spans.span
    live: Dict[int, Live] = {}
    done: List[Live] = []
    failed = 0
    steps: List[dict] = []
    lateness: List[float] = []
    tracing = False
    traced = trace_at is None
    clock = time.perf_counter
    t0 = clock()
    while True:
        now = clock() - t0
        if not traced and not tracing and now >= trace_at:
            h.start_trace()
            tracing, trace_end = True, clock() - t0 + trace_s
        if tracing and now >= trace_end:
            h.stop_trace()
            tracing, traced = False, True
            now = clock() - t0
        in_window = now < seconds
        if not in_window and (not live or now >= seconds + drain_s
                              or drain_s <= 0):
            break
        if in_window:
            with span("arrivals"):
                for r in source.take(now):
                    sent = clock() - t0
                    try:
                        req = engine.add_request(r.prompt,
                                                 max_new_tokens=r.max_new)
                    except Exception as e:  # noqa: BLE001 - a refusal
                        failed += 1         # counts, the run goes on
                        say(f"request refused: {type(e).__name__}: {e}")
                        source.finished(1, sent)
                        continue
                    live[id(req)] = Live(req, r.due)
                    lateness.append(sent - r.due)
        if not engine.has_work():
            if not in_window:
                break
            nxt = source.next_due()
            target = seconds if nxt is None else min(nxt, seconds)
            time.sleep(min(max(target - now, 0.0005), 0.05))
            continue
        traced_step = h.spans.tracing
        with span("engine.step"):
            out = engine.step()
        t = clock() - t0
        if t - now > 1.0:       # a stall is worth a line of its own
            say(f"slow step at {now:.2f}s: {t - now:.2f}s, {out}, "
                f"{len(live)} live")
        with span("collect"):
            n_done, kv, seqs = 0, 0, []
            for key in list(live):
                lv = live[key]
                n = len(lv.req.tokens)
                if lv.admitted is None and (lv.req.slot is not None or n):
                    lv.admitted = t
                if n > lv.seen:
                    lv.times.extend([t] * (n - lv.seen))
                    lv.seen = n
                held = kv_tokens_of(lv.req)
                if held > lv.kv:        # (new tokens, cache length after)
                    seqs.append((held - lv.kv, held))
                    lv.kv = held
                if lv.req.result is not None:
                    done.append(live.pop(key))
                    n_done += 1
                else:
                    kv += held
            engine.collect()
            if n_done:
                source.finished(n_done, t)
            steps.append({"t": t, "prefill": int(out["prefill_tokens"]),
                          "decoded": int(out["decoded"]), "seqs": seqs,
                          "live_kv": kv, "live": len(live),
                          "traced": traced_step})
    if tracing:
        h.stop_trace()
    return {"seconds": seconds, "done": done, "cut": list(live.values()),
            "failed": failed, "steps": steps, "lateness": lateness,
            "elapsed": clock() - t0}


def reduce_window(obs: Mapping, open_loop: bool) -> dict:
    """From raw observations to the end-to-end numbers and counters."""
    seconds = obs["seconds"]
    ttft, gaps, queue = [], [], []
    everyone = list(obs["done"]) + list(obs["cut"])
    for lv in everyone:
        if lv.times:
            ttft.append((lv.times[0] - lv.due) * 1e3)
        if lv.admitted is not None:
            queue.append((lv.admitted - lv.due) * 1e3)
        for a, b in zip(lv.times, lv.times[1:]):
            if open_loop or b <= seconds:
                gaps.append((b - a) * 1e3)
    in_steps = [s for s in obs["steps"] if s["t"] <= seconds]
    new_tokens = sum(sum(1 for t in lv.times if t <= seconds)
                     for lv in everyone)
    prefill = sum(s["prefill"] for s in in_steps)
    # a request cut by the end of a closed-loop window is not a failure;
    # in an open loop every request due in the window has to finish
    unfinished = len(obs["cut"]) if open_loop else 0
    res = {
        "attempted": len(everyone) + obs["failed"],
        "failed": obs["failed"] + unfinished,
        "ttft_ms": ttft, "tpot_ms": gaps, "queue_wait_ms": queue,
        "serve_tok_s": (prefill + new_tokens) / seconds,
        "steps_in_window": len(in_steps),
        "prefill_tokens": prefill, "new_tokens": new_tokens,
        "completed": len(obs["done"]), "cut_at_end": len(obs["cut"]),
        "mean_live_kv_tokens": (sum(s["live_kv"] for s in in_steps)
                                / max(len(in_steps), 1)),
        "mean_live_requests": (sum(s["live"] for s in in_steps)
                               / max(len(in_steps), 1)),
        "lateness_ms": stats.summary([x * 1e3 for x in obs["lateness"]]),
    }
    return res
