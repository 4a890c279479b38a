"""100 x pages that hold the launches' tokens / page-table entries the
ragged kernel's grid walks for each KV head (every row of the table,
live or idle, x ``pages_per_seq``), summed over the window's steps:
useful work over attempts of the kernel."""

from benchmarks.lib.harness import say
from benchmarks.lib.program_spans import in_window, window


def read(h):
    w = window(h)
    if w is None:
        return None
    live = sum(r["pages_live"] for _, r in in_window(w))
    visited = sum(r["pages_visited"] for _, r in in_window(w))
    if not visited:
        return None
    say(f"ragged pages over {len(in_window(w))} steps: {live} live of "
        f"{visited} visited")
    return 100.0 * live / visited
