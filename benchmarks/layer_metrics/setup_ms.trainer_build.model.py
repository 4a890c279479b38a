"""Milliseconds under ``trainer.build.model``: the eager float32 model
the step's state is extracted from, thousands of one-primitive programs
(ROADMAP D17)."""

from benchmarks.lib.setup_ledger import span_ms


def read(h):
    return span_ms(h, "trainer.build.model")
