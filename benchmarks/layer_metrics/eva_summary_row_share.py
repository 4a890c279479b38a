"""100 x pooled rows / all rows that attention read in one layer
(``summary_rows_live`` / (``summary_rows_live`` + ``window_rows_live``)
of the step records), summed over the window's steps: how much of what
a query sees is a summary."""

from benchmarks.lib import laguna_spans as ls
from benchmarks.lib.harness import say


def read(h):
    rows = ls.counts(h, "summary_rows_live", "window_rows_live")
    if rows is None:
        return None
    pooled, exact = (sum(r[i] for r in rows) for i in range(2))
    if not pooled + exact:
        return None
    say(f"rows attention read over {len(rows)} steps, one layer: {pooled} "
        f"pooled, {exact} exact")
    return 100.0 * pooled / (pooled + exact)
