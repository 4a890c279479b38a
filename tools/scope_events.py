"""Every device event a traced run of ONE serving cell shows under ONE of
the program's own names, by (opcode, shape), in us a launch that ran it
(PERF.md section 6, PR 59: the step-0 table of `kda_chunk_scan`).

    chiprun -- python tools/scope_events.py --own kda_chunk_scan -- \
        --workload ling3-flash-serve-reason-steady --seed 7 --seconds 50

runs `benchmarks/run.py ... --trace 1` in this process (``--tree`` names
another checkout's root, e.g. the parent unpacked under .archive_check/),
keeps the run's harness, and after the result line joins the traced
events to the program's table (`benchmarks/lib/scoped_ops.joined`: no
shape or kernel name is matched to find them).  A line is one (opcode,
result shape with its layout): its instructions' count, its traced
seconds, and us a launch that carried a chunk (the traced step records
whose `ssm_scan_rows` or `prefill_rows` is not 0; the share of such
launches over the WHOLE window is printed beside the traced window's).
Conditionals, loops and calls span their bodies' events and are listed
apart.  It prints; ``--dump PATH`` also writes the rows.
"""

from __future__ import annotations

import argparse
import os
import re
import runpy
import sys


_HEAD = re.compile(r"^\s*(?:ROOT\s+)?%?[\w\-.]+ = (\(?\w+\[[\d,]*\](?:\{[^}]*\})?)")
_OPCODE = re.compile(r" ([\w\-]+)\(")


def _kind(name: str) -> str:
    """`opcode shape{layout}` of an event's HLO line."""
    head = _HEAD.match(name)
    if not head:
        return name[:96]
    op = _OPCODE.search(name, head.end())
    return f"{op.group(1) if op else '?'} {head.group(1).lstrip('(')}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--own", required=True,
                    help="the innermost name as the program wrote it")
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--top", type=int, default=60)
    ap.add_argument("--dump", default=None, metavar="PATH",
                    help="also write every row under the name as JSON")
    ap.add_argument("cell", nargs=argparse.REMAINDER,
                    help="-- and benchmarks/run.py's arguments")
    args = ap.parse_args(argv)
    tree = os.path.abspath(args.tree)
    os.chdir(tree)
    sys.path.insert(0, tree)
    from benchmarks.lib import harness, scoped_ops
    from benchmarks.lib.program_spans import in_window, window
    from benchmarks.lib.trace import base_name, busy_inside
    runs = []
    init = harness.Harness.__init__

    def keep(self, *a, **k):
        init(self, *a, **k)
        runs.append(self)
    harness.Harness.__init__ = keep
    sys.argv = ["benchmarks/run.py"] + [a for a in args.cell if a != "--"] \
        + ["--trace", "1"]
    try:
        runpy.run_path(os.path.join(tree, "benchmarks", "run.py"),
                       run_name="__main__")
    except SystemExit as e:
        if e.code not in (0, None):
            return int(e.code) if isinstance(e.code, int) else 1
    h = runs[-1]
    j = scoped_ops.joined(h)
    if j is None:
        print("no table: the run was not traced, or the program gives none")
        return 1
    spans = busy_inside(h.reduced, "engine.step")
    mine = [r for r in j.rows if r.rec is not None and r.rec.own == args.own]
    if args.dump:
        import json
        os.makedirs(os.path.dirname(os.path.abspath(args.dump)), exist_ok=True)
        with open(args.dump, "w") as f:
            json.dump([{"name": r.name, "seconds": r.seconds,
                        "kind": r.rec.kind, "program": r.rec.program}
                       for r in mine], f)
    programs = sorted({r.rec.program for r in mine})
    print(f"{args.own}: {len(mine)} instructions of {programs}; "
          f"{len(spans)} step spans traced")

    def with_chunk(rs):
        return sum(1 for r in rs if r.get("ssm_scan_rows", 0) > 0
                   or r.get("prefill_rows", 0) > 0)

    w = window(h)
    pairs = in_window(w) if w else []
    every = [r for _, r in pairs]
    recs = [r for s, r in pairs if s.get("traced")]
    chunk = with_chunk(recs)
    print(f"traced records {len(recs)}, of them with a chunk {chunk}; the "
          f"whole window's {len(every)}, with a chunk {with_chunk(every)} "
          f"(`ssm_scan_rows` or `prefill_rows` > 0)")
    n = max(chunk, 1)
    groups, control = {}, {}
    for r in mine:
        kind = _kind(r.name)
        into = control if r.rec.kind == "control" else groups
        c, s = into.get(kind, (0, 0.0))
        into[kind] = (c + 1, s + r.seconds)
    total = sum(s for _, s in groups.values())
    print(f"total {total:.5f} s traced = {1e6 * total / n:.1f} us a launch "
          f"with a chunk (control events apart)")
    for title, g in (("events", groups), ("control (span their bodies)",
                                          control)):
        print(f"-- {title}: instructions, traced s, us a launch with a chunk")
        for kind, (c, s) in sorted(g.items(),
                                   key=lambda kv: -kv[1][1])[:args.top]:
            print(f"  {c:3d}  {s:.5f}  {1e6 * s / n:9.1f}  {kind}")
    print("-- the largest single instructions")
    for r in sorted(mine, key=lambda r: -r.seconds)[:12]:
        print(f"  {1e6 * r.seconds / n:9.1f}  {r.rec.kind:8s} "
              f"{base_name(r.name)[:150]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
