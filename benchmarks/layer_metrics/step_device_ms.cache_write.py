"""Device time a step of the operations the program runs under its
``cache_write`` names (``lib/scoped_ops.SERVE_PARTS``), mean over the traced
steps: the part's share of the traced events' seconds times the
device-busy time inside a step span (``unified_step_device_ms``)."""

from benchmarks.lib import scoped_ops


def read(h):
    return scoped_ops.serve_ms(h, "cache_write")
