"""``admit`` -> first ``prefill_chunk``: the wait of a request that
holds a slot behind the chunks of the prompts admitted before it (the
engine serves one chunk a step to the oldest prefilling request), 95th
percentile over the requests enqueued in the window."""

from benchmarks.lib.program_spans import request_phase_p95_ms


def read(h):
    return request_phase_p95_ms(h, "prefill_wait_s")
