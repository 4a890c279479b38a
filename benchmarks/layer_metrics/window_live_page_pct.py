"""The window's cut of the cache a layer reads: 100 x pages a
sliding-window layer's queries can still see / pages a full layer's see
(``pages_live.window`` / ``pages_live.full``), summed over the window's
steps; with it, the pages the allocator took back as the window passed
them (``window_pages_freed``)."""

from benchmarks.lib import laguna_spans as ls
from benchmarks.lib.harness import say


def read(h):
    rows = ls.counts(h, "pages_live.window", "pages_live.full",
                     "window_pages_freed")
    if rows is None:
        return None
    win, full, freed = (sum(r[i] for r in rows) for i in range(3))
    if not full:
        return None
    say(f"live pages a layer over {len(rows)} steps: {win} under the "
        f"window of {full}; {freed} window pages went back to their pool")
    return 100.0 * win / full
