"""Device-busy time inside one ``train_step`` (first device), mean over
the traced steps."""

from benchmarks.lib.trace import busy_inside


def read(h):
    if h.reduced is None:
        return None
    pairs = busy_inside(h.reduced, "train_step")
    if not pairs:
        return None
    return 1e3 * sum(busy for _, busy in pairs) / len(pairs)
