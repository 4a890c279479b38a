"""The backend stage over every program record of set-up: a compile
where the compile cache missed, the cache's read and the executable's
load where it hit.  Its line splits the two."""

from benchmarks.lib.setup_ledger import compile_ms


def read(h):
    return compile_ms(h)
