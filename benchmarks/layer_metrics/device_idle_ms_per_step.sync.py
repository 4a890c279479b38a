"""Device-idle time a step while the host sat in the program's ``sync``
phase (it was waiting for the device: the runtime's or the queue's
time, not Python's), on the trace's clock (``lib/phase_idle.py``)."""

from benchmarks.lib import phase_idle


def read(h):
    return phase_idle.idle_ms(h, "sync")
