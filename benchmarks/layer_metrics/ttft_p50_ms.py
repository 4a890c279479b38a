"""The median of the same times to the first token whose 95th
percentile is ``ttft_p95_ms``: over 80 requests the tail is the fourth
worst request of one arrival trace, the median moves with every step."""

from benchmarks.lib.stats import percentile


def read(h):
    ttft = h.counters.get("ttft_ms")
    return percentile(ttft, 50) if ttft else None
