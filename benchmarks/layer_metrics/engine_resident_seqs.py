"""Mean ``live`` of the step records over the window's steps where the
layer list runs several times a token: the sequences that hold a slot
AND pages, which a cache of ``cache_row_bytes`` a token sets, not the
slots.  The line says who waits and how full the pool is."""

from benchmarks.lib.harness import say
from benchmarks.lib.laguna_spans import counts
from benchmarks.lib.program_spans import mean


def read(h):
    rows = counts(h, "live", "waiting", "pool_pages_used",
                  "pool_pages_total", "cache_row_bytes", "ut_steps")
    if rows is None:
        return None
    live, waiting, used, total, row_bytes, _ = zip(*rows)
    say(f"resident sequences over {len(rows)} steps: mean {mean(live):.2f}"
        f", max {max(live)}; waiting mean {mean(waiting):.2f}; pool pages "
        f"used mean {mean(used):.1f} of {total[-1]} (max {max(used)}); "
        f"{max(row_bytes)} B a token")
    return mean(live)
