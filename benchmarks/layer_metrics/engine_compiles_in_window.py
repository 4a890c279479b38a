"""Programs compiled while a step of the window ran, by the program's
own count (one ``jax.monitoring`` listener of its recorder): 0 in a
window that was warmed up."""

from benchmarks.lib.harness import say
from benchmarks.lib.program_spans import in_window, window


def read(h):
    w = window(h)
    if w is None:
        return None
    hit = [(r["seq"], r["compiles"]) for _, r in in_window(w)
           if r["compiles"]]
    if hit:
        say(f"steps that compiled (seq, programs): {hit[:20]}")
    return sum(n for _, n in hit)
