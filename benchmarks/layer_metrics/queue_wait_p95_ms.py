"""From when a request was due to the return of the step in which it
first held a slot, 95th percentile, seen from the harness's
``add_request`` / ``step()`` returns."""

from benchmarks.lib.stats import percentile


def read(h):
    waits = h.counters.get("queue_wait_ms")
    return percentile(waits, 95) if waits else None
