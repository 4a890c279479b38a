"""Device time of the routed FFN a step — router, top-k, sort, the
grouped GEMMs, unsort and combine of every sparse layer — mean over the
traced steps."""

from benchmarks.lib import laguna_spans as ls
from benchmarks.lib.harness import say


def read(h):
    steps = [s for s in h.counters.get("steps", []) if s["traced"]]
    if h.reduced is None or not steps \
            or "experts_held" not in h.counters.get("cfg", {}):
        return None
    every, gmm = ls.moe_patterns(h)
    total, gemms = ls.seconds_of(h.reduced, every), \
        ls.seconds_of(h.reduced, gmm)
    if total <= 0:
        return None
    say(f"routed FFN: {total:.4f}s of device time over {len(steps)} traced "
        f"steps, of which grouped GEMMs {gemms:.4f}s")
    return 1e3 * total / len(steps)
