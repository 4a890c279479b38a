"""Device-busy time inside one ``ServingEngine.step()`` (the unified
jitted step and whatever else the engine launches), mean over traced
steps."""

from benchmarks.lib.trace import busy_inside


def read(h):
    if h.reduced is None:
        return None
    pairs = busy_inside(h.reduced, "engine.step")
    if not pairs:
        return None
    return 1e3 * sum(busy for _, busy in pairs) / len(pairs)
