"""``enqueue`` -> ``admit`` on the program's own stamps, 95th percentile
over the requests enqueued in the window.  The outside metric
``queue_wait_p95_ms`` starts at the due time and ends at a step's
return."""

from benchmarks.lib.program_spans import (request_phase_p95_ms,
                                          say_request_books)


def read(h):
    say_request_books(h)
    return request_phase_p95_ms(h, "queue_wait_s")
