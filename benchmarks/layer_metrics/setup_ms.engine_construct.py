"""Milliseconds under the program's ``serving.engine.construct`` span
(the whole of ``ServingEngine.__init__``, before the window); its line
says each section — weights, layout, pools, accounting, programs — and
the program records under them."""

from benchmarks.lib.setup_ledger import span_ms


def read(h):
    return span_ms(h, "serving.engine.construct")
