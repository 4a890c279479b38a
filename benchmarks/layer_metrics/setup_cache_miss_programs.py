"""Program records of set-up that the compile cache did not answer: 0
on a warm side.  The number to read before a ``setup_s`` is compared."""

from benchmarks.lib.setup_ledger import cache_miss_programs


def read(h):
    return cache_miss_programs(h)
