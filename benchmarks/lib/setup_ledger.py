"""The parts of ``setup_s``, from the program's own set-up ledger
(``paddle_tpu.observability.tracing.recorder().setup()``, ISSUE 68): the
spans that belong to no step (``paddle_tpu.import``,
``serving.engine.construct`` / ``trainer.build`` and their sections), a
copy of every step in which a program was traced or compiled (a step
program's first launch), one PROGRAM RECORD for each program jax traced,
lowered and compiled or read from the compile cache, and the records'
exact totals by span.

SET-UP ENDS at the end of the last record or span BEFORE THE WINDOW.  The
window is found from inside the run: a serving cell's is the start of its
first step record (``program_spans.window``); a training cell steps the
trainer from outside the program, so its window is the LAST stretch at
least nine tenths of the window's own length (tokens over tokens per
second) in which the ledger holds nothing — a correct window compiles
nothing.  What comes after it is a reader's own (``compiled_programs``
lowers the step again for ``lib/scoped_ops.py``, under spans named
``*.compiled_programs``) and is left out by span and by time.

PROCESS START is the harness's ``t_start`` (``time.time()`` on ``run.py``'s
first line), brought onto the spans' clock (``time.perf_counter_ns``) by
one offset taken when the ledger is read.

A program without the ledger (the parent of the PR that brought it, or a
run with ``FLAGS_request_tracing`` off) gives ``None`` everywhere and the
metric is left out of the line.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from .harness import REPO, say

READERS_SPAN = ".compiled_programs"     # a reader's own lowering
STAGES = ("trace_ns", "lower_ns", "compile_ns")


class Ledger(NamedTuple):
    spans: List[dict]       # set-up's: before the window, no reader's
    programs: List[dict]    # the records KEPT that ended before it
    totals: Dict[str, int]  # exact sums over every record of set-up
    not_kept: int           # records of set-up that were not kept
    start_ns: int           # process start, on the spans' clock
    end_ns: int             # the end of the last span or record
    window_ns: Optional[int]    # where the window began, if it was found


def total_ns(rec: dict) -> int:
    return sum(rec[k] for k in STAGES)


def union_ns(intervals: Sequence[Tuple[int, int]]) -> int:
    """The length covered by the intervals, overlaps counted once."""
    covered, reach = 0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            covered, reach = covered + (b - a), b
        elif b > reach:
            covered, reach = covered + (b - reach), b
    return covered


def last_quiet_stretch(intervals: Sequence[Tuple[int, int]], now_ns: int,
                       at_least_ns: int) -> Optional[int]:
    """Where the LAST stretch of `at_least_ns` that no interval touches
    begins (the end of what comes before it); None if there is none."""
    found, reach = None, None
    for a, b in sorted(intervals) + [(now_ns, now_ns)]:
        if reach is not None and a - reach >= at_least_ns:
            found = reach
        reach = b if reach is None else max(reach, b)
    return found


def _theirs(name: Optional[str]) -> bool:
    return bool(name) and name.endswith(READERS_SPAN)


def build(recorded: dict, start_ns: int, window_ns: Optional[int]) -> Ledger:
    """The set-up's share of what the recorder holds: `window_ns` cuts
    it (None: everything is set-up)."""
    cut = window_ns if window_ns is not None else float("inf")
    spans = [sp for sp in recorded["spans"]
             if sp["end_ns"] is not None and sp["end_ns"] <= cut
             and not _theirs(sp["name"])]
    mine = [r for r in recorded["programs"] if not _theirs(r["span"])]
    kept = [r for r in mine if r["end_ns"] <= cut]
    totals: Dict[str, int] = {}
    for name, sums in recorded["totals"].items():
        if not _theirs(name):
            for k, v in sums.items():
                totals[k] = totals.get(k, 0) + v
    # what the totals hold of the window and after it: by the records
    for r in mine:
        if r["end_ns"] > cut:
            totals["programs"] -= 1
            for k in STAGES + ("cache_read_ns",):
                totals[k] -= r[k]
            if r["cache"] in ("hit", "miss"):
                totals["hits" if r["cache"] == "hit" else "misses"] -= 1
    ends = [sp["end_ns"] for sp in spans] + [r["end_ns"] for r in kept]
    return Ledger(spans, kept, totals,
                  max(totals.get("programs", 0) - len(kept), 0), start_ns,
                  max(ends) if ends else start_ns, window_ns)


def _window_ns(h, recorded: dict, now_ns: int) -> Optional[int]:
    from . import program_spans
    w = program_spans.window(h)
    if w is not None and w.steps and w.steps[0][1]["start_ns"]:
        return w.steps[0][1]["start_ns"]
    c = h.counters
    if c.get("tok_s_chip") and "system" in c:   # a training cell
        chips = len(getattr(h, "devices", ())) or 1
        seconds = c["steps_in_window"] * c["system"].tokens_per_step \
            / (c["tok_s_chip"] * chips)
    elif c.get("steps"):        # a serving cell whose ring turned over
        seconds = c["steps"][-1]["t"]
    else:
        return None
    entries = [(e["start_ns"], e["end_ns"])
               for e in recorded["spans"] + recorded["programs"]
               if e["start_ns"] is not None and e["end_ns"] is not None
               and e["name"] != "paddle_tpu.import"]
    quiet = last_quiet_stretch(entries, now_ns, int(0.9e9 * seconds))
    # (everything that ends by the stretch's start is set-up)
    return None if quiet is None else quiet + 1


def jsonable(recorded: dict) -> dict:
    """`recorder().setup()` with its totals' None key (no span) as ""."""
    return dict(recorded, totals={k or "": v
                                  for k, v in recorded["totals"].items()})


def ledger(h) -> Optional[Ledger]:
    """The run's set-up, read once and kept with the run."""
    if "setup_ledger" not in h.counters:
        h.counters["setup_ledger"] = _read(h)
    return h.counters["setup_ledger"]


def _read(h) -> Optional[Ledger]:
    from paddle_tpu.observability import tracing
    ask = getattr(tracing.recorder(), "setup", None)
    if ask is None:
        return None
    recorded = jsonable(ask())
    if not recorded["spans"] and not recorded["programs"]:
        return None             # FLAGS_request_tracing off
    now_ns = time.perf_counter_ns()
    start_ns = now_ns - int((time.time() - h.t_start) * 1e9)
    led = build(recorded, start_ns, _window_ns(h, recorded, now_ns))
    out = os.path.join(REPO, "chiprun_out")
    if os.path.isdir(out):      # the builder's chip tool brings it back
        with open(os.path.join(out, "setup_ledger.json"), "w") as f:
            json.dump({"start_ns": start_ns, "window_ns": led.window_ns,
                       "recorded": recorded}, f)
    said = "not found: everything the ledger holds counts as set-up" \
        if led.window_ns is None else \
        f"{(led.window_ns - start_ns) / 1e9:.3f}s after process start"
    say(f"set-up ledger: {len(led.spans)} spans, {led.totals.get('programs', 0)} "
        f"program records ({led.not_kept} not kept one by one) before the "
        f"window, which began {said}; set-up's last span or record ended "
        f"at {(led.end_ns - start_ns) / 1e9:.3f}s")
    return led


# ------------------------------------------------------------ the parts

def ms(ns: int) -> float:
    return ns / 1e6


def spans_named(led: Ledger, name: str) -> List[dict]:
    return [sp for sp in led.spans if sp["name"] == name]


def span_ms(h, name: str) -> Optional[float]:
    """Milliseconds under the spans `name` (a cell builds one engine, or
    one trainer step; a check that builds another adds up); the line
    says each child, a child's own children, and the records under it."""
    led = ledger(h)
    if led is None:
        return None
    whole = spans_named(led, name)
    if not whole:
        return None

    def told(parent: str) -> str:
        return ", ".join(
            f"{sp['name'][len(parent):]} "
            f"{ms(sp['end_ns'] - sp['start_ns']):.1f}"
            + (f" ({told(sp['name'])})"
               if any(k["parent"] == sp["name"] for k in led.spans)
               else "") for sp in led.spans if sp["parent"] == parent)
    total = sum(sp["end_ns"] - sp["start_ns"] for sp in whole)
    kids = sum(sp["end_ns"] - sp["start_ns"] for sp in led.spans
               if sp["parent"] == name)
    say(f"{name}: {ms(total):.1f} ms in {len(whole)} span(s)"
        + (f", its sections {100.0 * kids / max(total, 1):.1f} % of it: "
           f"{told(name)}" if kids else "")
        + f"; programs under it: {_under(led, name)}")
    return ms(total)


def _under(led: Ledger, prefix: str) -> str:
    """The kept records under spans that start with `prefix`, summed."""
    recs = [r for r in led.programs if (r["span"] or "").startswith(prefix)]
    if not recs:
        return "none kept"
    return (f"{len(recs)} kept, trace {ms(sum(r['trace_ns'] for r in recs)):.1f}"
            f" + lower {ms(sum(r['lower_ns'] for r in recs)):.1f} + backend "
            f"{ms(sum(r['compile_ns'] for r in recs)):.1f} ms, "
            f"{sum(r['cache'] == 'miss' for r in recs)} misses")


def first_launches_ms(h) -> Optional[float]:
    """The summed step spans of the steps before the window in which a
    program record landed; the line says each step, its longest program
    and what that program's stages took."""
    led = ledger(h)
    if led is None or not spans_named(led, "serving.engine.construct"):
        return None             # no engine was built: not a serving cell
    steps = [sp for sp in led.spans
             if sp["step"] is not None and sp["start_ns"] is not None]
    told = []
    for sp in steps:
        mine = [r for r in led.programs if r["step"] == sp["step"]
                and sp["start_ns"] <= r["end_ns"] <= sp["end_ns"]]
        top = max(mine, key=total_ns, default=None)
        told.append(
            f"step {sp['step']} {ms(sp['end_ns'] - sp['start_ns']):.1f} ms"
            + (f" ({top['name']}: trace {ms(top['trace_ns']):.1f} + lower "
               f"{ms(top['lower_ns']):.1f} + backend "
               f"{ms(top['compile_ns']):.1f} [{top['cache']}], "
               f"{len(mine)} records)" if top else ""))
    say(f"first launches before the window: {'; '.join(told) or 'none'}")
    return ms(sum(sp["end_ns"] - sp["start_ns"] for sp in steps))


def trace_lower_ms(h) -> Optional[float]:
    """Self trace + lowering time over EVERY record of set-up (Python,
    which no cache answers); the line says the five largest programs
    with their span and the sum under no span of the program's."""
    led = ledger(h)
    if led is None:
        return None
    top = sorted(led.programs, key=lambda r: -(r["trace_ns"]
                                               + r["lower_ns"]))[:5]
    bare = [r for r in led.programs if r["span"] is None]
    say(f"set-up trace + lower: trace {ms(led.totals['trace_ns']):.1f} + "
        f"lower {ms(led.totals['lower_ns']):.1f} ms over "
        f"{led.totals['programs']} records; the largest: "
        + "; ".join(f"{r['name']} {ms(r['trace_ns']):.1f} + "
                    f"{ms(r['lower_ns']):.1f} under {r['span']}"
                    for r in top)
        + f"; under no span of the program's (the reference's and the "
        f"harness's own jits): "
        f"{ms(sum(r['trace_ns'] + r['lower_ns'] for r in bare)):.1f} ms "
        f"in {len(bare)} kept records")
    return ms(led.totals["trace_ns"] + led.totals["lower_ns"])


def compile_ms(h) -> Optional[float]:
    """The backend stage over every record of set-up: a compile on a
    miss, the cache's read (and the executable's load) on a hit."""
    led = ledger(h)
    if led is None:
        return None
    by = {c: [r for r in led.programs if r["cache"] == c]
          for c in ("miss", "hit", "off")}
    say(f"set-up backend stage: {ms(led.totals['compile_ns']):.1f} ms over "
        f"{led.totals['hits']} hits + {led.totals['misses']} misses; of "
        f"the kept records: misses "
        f"{ms(sum(r['compile_ns'] for r in by['miss'])):.1f} ms, hits "
        f"{ms(sum(r['compile_ns'] for r in by['hit'])):.1f} ms of which "
        f"the cache's read {ms(led.totals['cache_read_ns']):.1f}, cache "
        f"off {ms(sum(r['compile_ns'] for r in by['off'])):.1f} ms; the "
        f"longest: " + "; ".join(
            f"{r['name']} {ms(r['compile_ns']):.1f} [{r['cache']}] under "
            f"{r['span']}" for r in sorted(
                led.programs, key=lambda r: -r["compile_ns"])[:5]))
    return ms(led.totals["compile_ns"])


def cache_miss_programs(h) -> Optional[int]:
    """Records of set-up the compile cache did not answer: 0 on a warm
    side.  The number to read before anyone is refused for ``setup_s``."""
    led = ledger(h)
    if led is None:
        return None
    missed = [r for r in led.programs if r["cache"] == "miss"]
    if missed:
        say(f"set-up compiled {led.totals['misses']} programs the cache "
            f"did not hold; the longest: " + "; ".join(
                f"{r['name']} {ms(r['compile_ns']):.1f} ms under "
                f"{r['span']}" for r in sorted(
                    missed, key=lambda r: -r["compile_ns"])[:8]))
    return int(led.totals["misses"])


def named_pct(h) -> Optional[float]:
    """100 x the union of what the program names of set-up — its import,
    the constructor's or the builder's span, the first-launch steps and
    every other program record — over (end of set-up - process start);
    the line says the remainder, which is the benchmark's own and the
    machine's."""
    led = ledger(h)
    if led is None:
        return None
    whole = led.end_ns - led.start_ns
    if whole <= 0:
        return None
    parts = {
        "import": [sp for sp in led.spans
                   if sp["name"] == "paddle_tpu.import"],
        "construct / build": [sp for sp in led.spans if sp["name"] in (
            "serving.engine.construct", "trainer.build")],
        "first launches": [sp for sp in led.spans
                           if sp["step"] is not None
                           and sp["start_ns"] is not None],
        "other programs": led.programs}
    clip = [[(max(e["start_ns"], led.start_ns), e["end_ns"])
             for e in entries if e["end_ns"] > led.start_ns]
            for entries in parts.values()]
    named = union_ns([iv for ivs in clip for iv in ivs])
    alone = ", ".join(f"{k} {union_ns(ivs) / 1e9:.2f}"
                      for k, ivs in zip(parts, clip))
    before = min((a for ivs in clip for a, _ in ivs), default=led.end_ns)
    say(f"set-up named: {named / 1e9:.3f} s of {whole / 1e9:.3f} s "
        f"({100.0 * named / whole:.2f} %; each alone, in s: {alone}); the "
        f"remainder {(whole - named) / 1e9:.3f} s "
        f"({100.0 * (whole - named) / whole:.2f} %) is the benchmark's and "
        f"the machine's — {(before - led.start_ns) / 1e9:.3f} s before the "
        f"first named instant (interpreter, backend), the rest between "
        f"named stretches (the weights' draw, the warm-up's device time, "
        f"the reference check)"
        + (f"; from set-up's end to the window "
           f"{(led.window_ns - led.end_ns) / 1e9:.3f} s"
           if led.window_ns is not None else "")
        + (f"; {led.not_kept} short records were not kept one by one and "
           f"count as unnamed" if led.not_kept else ""))
    return 100.0 * named / whole
