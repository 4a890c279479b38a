"""Self trace + lowering time over every program record of set-up: the
Python that no compile cache answers.  Its line says the five largest
programs with their span, and the sum under no span of the program's
(the reference's and the harness's own jits)."""

from benchmarks.lib.setup_ledger import trace_lower_ms


def read(h):
    return trace_lower_ms(h)
