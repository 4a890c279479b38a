"""Host time of one ``ServingEngine.step()``: the benchmark's span around
the call minus the device-busy time inside it, mean over traced steps."""

from benchmarks.lib.trace import busy_inside


def read(h):
    if h.reduced is None:
        return None
    pairs = busy_inside(h.reduced, "engine.step")
    if not pairs:
        return None
    return 1e3 * sum(length - busy for length, busy in pairs) / len(pairs)
