"""Device time by the program's OWN names for the parts of its steps.

The program runs each part of a step under a scope of one vocabulary
(``paddle_tpu.observability.attribution.SCOPES``) and says, for every
instruction of its compiled programs, which scope it belongs to
(``op_scopes``: ``{"%fusion.12 bf16[288,4096]": OpScope}``).  The trace
gives seconds by the event's whole HLO line (``Reduced.op_seconds``).
This file joins the two by ``attribution.op_key`` of the line — no
shape, operand or kernel name is matched here, so a reader survives a
change of tile, chunk or batch.

The table is built once a traced run, after the window, and only when
a reader asks: ``ServingEngine.compiled_programs()`` (or the trainer's
``meta["compiled_programs"]``) lowers the programs again at the shapes
they ran with and the compile cache answers.  What that costs is said
in the run's output.  A program without the table (a parent of the PR
that brought it), a run without a trace or a trace without device
events gives ``None`` everywhere, and the metric is left out of the
line.
"""

from __future__ import annotations

import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from .harness import say
from .trace import COLLECTIVE, busy_inside

#: what the five serving metrics add up, by the vocabulary's names
SERVE_PARTS = {"attention": ("attention",),
               "proj": ("qkv_proj", "attn_out"),
               "ffn": ("ffn", "routed_ffn", "shared_expert"),
               "cache_write": ("cache_write",),
               "head": ("head",)}
TRAIN_PARTS = {"attn": ("qkv_proj", "attention", "attn_out"),
               "mlp": ("ffn",),
               "head_loss": ("head_loss",),
               "update": ("update",)}
UNSCOPED, UNKNOWN = "(no scope)", "(not in the table)"


class Row(NamedTuple):
    name: str               # the event's name: its whole HLO line
    seconds: float          # mean over devices, inside the traced window
    rec: object             # its OpScope, or None if the table lacks it


class Joined(NamedTuple):
    rows: List[Row]
    total_s: float          # every event but loops (their bodies count)
    by_scope: Dict[str, float]


def kept(h, key: str, build):
    """``build(h)``, made once a run and kept with it (``None`` too)."""
    if key not in h.counters:
        h.counters[key] = build(h)
    return h.counters[key]


def _programs(h):
    """The system's compiled programs, or ``None`` where it cannot give
    them."""
    system = h.counters.get("system")
    engine = getattr(system, "engine", None)
    if engine is not None:
        get = getattr(engine, "compiled_programs", None)
        return None if get is None else get()
    meta = getattr(system, "meta", None) or {}
    get = meta.get("compiled_programs")
    return None if get is None else get(system.state)


def _table(h) -> Optional[dict]:
    red = h.reduced
    if red is None or not red.op_seconds:
        return None
    try:
        from paddle_tpu.observability.attribution import op_scopes
    except ImportError:             # a program from before the table
        return None
    t0 = time.perf_counter()
    programs = _programs(h)
    t1 = time.perf_counter()
    if programs is None:
        return None
    tab = op_scopes(programs)
    named = sum(r.scope is not None for r in tab.values())
    say(f"scoped ops: what tracing ON costs after the window: "
        f"compiled_programs {t1 - t0:.2f}s + op_scopes "
        f"{time.perf_counter() - t1:.2f}s; {len(tab)} instructions of "
        f"{sorted(programs)}, {named} under a name")
    return tab


def table(h) -> Optional[dict]:
    """``op_scopes`` of the run's programs, built once and kept."""
    return kept(h, "op_table", _table)


def _joined(h) -> Optional[Joined]:
    tab = table(h)
    if not tab:
        return None
    from paddle_tpu.observability.attribution import op_key
    rows, by_scope, total = [], {}, 0.0
    for name, sec in h.reduced.op_seconds.items():
        rec = tab.get(op_key(name))
        rows.append(Row(name, sec, rec))
        if rec is not None and rec.kind == "control":
            continue
        total += sec
        key = UNKNOWN if rec is None else rec.scope or UNSCOPED
        by_scope[key] = by_scope.get(key, 0.0) + sec
    rows.sort(key=lambda r: -r.seconds)
    return Joined(rows, total, by_scope)


def joined(h) -> Optional[Joined]:
    """Every traced device event beside what the program says of it."""
    return kept(h, "op_joined", _joined)


def _parts(j: Joined, parts, per: float):
    """(ms a step of each part, of every other name, share under a
    name) at ``per`` ms a step for each traced second."""
    ms = {part: per * sum(j.by_scope.get(s, 0.0) for s in scopes)
          for part, scopes in parts.items()}
    named = {s for scopes in parts.values() for s in scopes}
    rest = {k: per * v for k, v in j.by_scope.items() if k not in named}
    loose = j.by_scope.get(UNSCOPED, 0.0) + j.by_scope.get(UNKNOWN, 0.0)
    return ms, rest, 100.0 * (1.0 - loose / j.total_s)


def short(row: Row, n: int = 72) -> str:
    """An event for a line of output: stem, opcode, shape — and for a
    copy the parameter it copies."""
    from .trace import base_name
    extra = f" of %{row.rec.reads}" if row.rec is not None \
        and row.rec.reads else ""
    return (base_name(row.name) + extra)[:n]


def _top(rows: Sequence[Row], per: float, n: int = 4) -> str:
    return "; ".join(f"{short(r)} {r.seconds * per:.3f}" for r in rows[:n])


# ----------------------------------------------------------------- serving

def _serve_books(h) -> Optional[dict]:
    """Device ms a step by part, closing against the accepted
    ``unified_step_device_ms``: each part is its share of the traced
    events' seconds times the device-busy time inside a step span."""
    books = None
    j = joined(h)
    pairs = busy_inside(h.reduced, "engine.step") if j else []
    if j and pairs and j.total_s > 0:
        step_ms = 1e3 * sum(busy for _, busy in pairs) / len(pairs)
        per = step_ms / j.total_s           # ms a step per traced second
        ms, rest, scoped = _parts(j, SERVE_PARTS, per)
        books = {"ms": ms, "scoped_pct": scoped}
        red = h.reduced
        say(f"scoped device ms a step over {len(pairs)} traced steps "
            f"(unified_step_device_ms {step_ms:.3f}; the traced events add "
            f"to {100.0 * j.total_s / red.busy_s:.2f} % of the device-busy "
            f"time, {1e3 * j.total_s / len(pairs):.3f} ms a step): "
            + ", ".join(f"{k} {v:.3f}" for k, v in ms.items())
            + "; the rest by name: "
            + ", ".join(f"{k} {v:.3f}" for k, v in
                        sorted(rest.items(), key=lambda kv: -kv[1])))
        for part, scopes in SERVE_PARTS.items():
            mine = [r for r in j.rows if r.rec is not None
                    and r.rec.scope in scopes and r.rec.kind != "control"]
            split: Dict[str, float] = {}
            for r in mine:      # by the scope's own name and the opcode
                for k in (r.rec.scope, r.rec.opcode):
                    split[k] = split.get(k, 0.0) + r.seconds * per
            say(f"  {part}: " + ", ".join(
                f"{k} {v:.3f}" for k, v in sorted(
                    split.items(), key=lambda kv: -kv[1]) if v >= 0.0005)
                + f"; the largest: {_top(mine, per)}")
        loose = [r for r in j.rows
                 if r.rec is None or r.rec.scope is None]
        if loose:
            say(f"  under no name: {_top(loose, per, 6)}")
        copies = [r for r in j.rows
                  if r.rec is not None and r.rec.kind == "copy"]
        if copies:
            by: Dict[Tuple[str, str], float] = {}
            for r in copies:
                k = (r.rec.scope or UNSCOPED, r.rec.shape)
                by[k] = by.get(k, 0.0) + r.seconds * per
            say("  copies by (scope, shape) ms a step: " + ", ".join(
                f"{s} {shape} {v:.3f}" for (s, shape), v in
                sorted(by.items(), key=lambda kv: -kv[1])[:8])
                + "; the largest: " + _top(copies, per, 3))
        spanning = [r for r in j.rows if r.rec is not None and r.rec.scopes]
        if spanning:
            say(f"  fusions over several names ({len(spanning)}, counted "
                f"under their root's): "
                + "; ".join(f"{short(r, 48)} {'+'.join(r.rec.scopes)} "
                            f"{r.seconds * per:.3f}" for r in spanning[:4]))
    return books


def serve_ms(h, part: str) -> Optional[float]:
    books = kept(h, "serve_books", _serve_books)
    return None if books is None else books["ms"][part]


def serve_scoped_pct(h) -> Optional[float]:
    books = kept(h, "serve_books", _serve_books)
    return None if books is None else books["scoped_pct"]


# ---------------------------------------------------------------- training

def _train_books(h) -> Optional[dict]:
    books = None
    j = joined(h)
    steps = len(busy_inside(h.reduced, "train_step")) if j else 0
    if j and steps and j.total_s > 0:
        per = 1e3 / steps                   # ms a step per traced second
        ms, rest, scoped = _parts(j, TRAIN_PARTS, per)
        by_dir: Dict[str, float] = {}
        for r in j.rows:
            if r.rec is not None and r.rec.kind != "control":
                d = r.rec.direction
                by_dir[d] = by_dir.get(d, 0.0) + r.seconds
        books = {"ms": ms, "scoped_pct": scoped,
                 "remat_pct": 100.0 * by_dir.get("remat", 0.0) / j.total_s}
        say(f"scoped device ms a step over {steps} traced steps, mean over "
            f"chips (the traced events add to "
            f"{100.0 * j.total_s / h.reduced.busy_s:.2f} % of the "
            f"device-busy time, {per * j.total_s:.1f} ms a step): "
            + ", ".join(f"{k} {v:.1f}" for k, v in ms.items())
            + "; the rest by name: "
            + ", ".join(f"{k} {v:.1f}" for k, v in
                        sorted(rest.items(), key=lambda kv: -kv[1]))
            + "; by direction: "
            + ", ".join(f"{k} {per * v:.1f}" for k, v in
                        sorted(by_dir.items(), key=lambda kv: -kv[1])))
        kinds: Dict[Tuple[str, str, str, str], float] = {}
        for r in j.rows:
            if r.rec is not None and r.rec.kind != "control":
                k = (r.rec.scope or UNSCOPED, r.rec.direction,
                     r.rec.opcode, r.rec.shape)
                kinds[k] = kinds.get(k, 0.0) + r.seconds
        say("  the largest by (scope, direction, opcode, shape) ms a step: "
            + "; ".join(f"{' '.join(k)} {per * v:.1f}" for k, v in
                        sorted(kinds.items(), key=lambda kv: -kv[1])[:40]))
    return books


def train_ms(h, part: str) -> Optional[float]:
    books = kept(h, "train_books", _train_books)
    return None if books is None else books["ms"][part]


def train_pct(h, key: str) -> Optional[float]:
    books = kept(h, "train_books", _train_books)
    return None if books is None else books[key]


def _exposed_books(h) -> Optional[dict]:
    """The accepted ``collective_exposed_pct``'s seconds, split by what
    the program says of each event it counts as a collective.  ``Reduced``
    keeps no intervals by event, only seconds by event and the exposed
    total; on a TPU's op line one core's events do not overlap, so an
    event's seconds ARE its exposed seconds, and the line below says by
    how much the two totals differ.  Each group is scaled by that ratio,
    so that the three add up to the accepted metric's seconds."""
    books = None
    j = joined(h)
    red = h.reduced
    steps = len(busy_inside(red, "train_step")) if j else 0
    rows = [r for r in j.rows if COLLECTIVE.search(r.name)] if j else []
    counted = sum(r.seconds for r in rows)
    if steps and counted > 0 and red.collective_exposed_s > 0:
        scale = red.collective_exposed_s / counted
        per = 1e3 * scale / steps
        groups = {"layers_fwd": 0.0, "layers_bwd": 0.0, "update": 0.0}
        kinds: Dict[Tuple[str, str, str, str, str], float] = {}
        for r in rows:
            scope = UNKNOWN if r.rec is None else r.rec.scope or UNSCOPED
            direction = "-" if r.rec is None else r.rec.direction
            g = "update" if scope == "update" else \
                "layers_bwd" if direction == "bwd" else "layers_fwd"
            groups[g] += r.seconds * per
            real = "?" if r.rec is None else r.rec.kind
            k = (g, scope, direction, short(r, 60), real)
            kinds[k] = kinds.get(k, 0.0) + r.seconds * per
        books = groups
        real_s = sum(r.seconds for r in rows
                     if r.rec is not None and r.rec.kind == "collective")
        say(f"exposed collectives over {steps} traced steps: the accepted "
            f"metric counts {len(rows)} events, {counted:.4f}s a chip, "
            f"exposed {red.collective_exposed_s:.4f}s (x{scale:.4f}); of "
            f"the counted seconds {100.0 * real_s / counted:.1f} % are "
            f"collective instructions, the rest compute that reads one or "
            f"holds one the compiler hid inside it (the metric's pattern "
            f"matches the whole line, operand names included); ms a step: "
            + ", ".join(f"{k} {v:.1f}" for k, v in groups.items()))
        for k, v in sorted(kinds.items(), key=lambda kv: -kv[1])[:24]:
            say(f"  {k[0]}: {k[1]} {k[2]} [{k[4]}] {k[3]} {v:.1f}")
    return books


def exposed_ms(h, group: str) -> Optional[float]:
    books = kept(h, "exposed_books", _exposed_books)
    return None if books is None else books[group]
