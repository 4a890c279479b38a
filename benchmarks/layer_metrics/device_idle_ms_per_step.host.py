"""Device-idle time a step under any phase of the program but ``sync``,
or between two calls: the device waited for the host
(``lib/phase_idle.py``)."""

from benchmarks.lib import phase_idle


def read(h):
    return phase_idle.idle_ms(h, "host")
