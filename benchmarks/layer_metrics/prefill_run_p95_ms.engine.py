"""First ``prefill_chunk`` -> first ``token``: the request's own chunks
(and the decode rows that ride with them), 95th percentile over the
requests enqueued in the window."""

from benchmarks.lib.program_spans import request_phase_p95_ms


def read(h):
    return request_phase_p95_ms(h, "prefill_run_s")
