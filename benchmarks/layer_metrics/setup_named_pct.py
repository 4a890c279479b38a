"""Share of (end of set-up - process start) that the program names: the
union of ``paddle_tpu.import``, the constructor's or the builder's span,
the first-launch steps and every other program record, overlaps counted
once.  Its line says the remainder in seconds: the benchmark's own and
the machine's."""

from benchmarks.lib.setup_ledger import named_pct


def read(h):
    return named_pct(h)
