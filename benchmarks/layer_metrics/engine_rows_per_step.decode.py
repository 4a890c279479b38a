"""Mean ``decode_rows`` of the step records over the window's steps:
rows of the launch that belong to decode slots."""

from benchmarks.lib.program_spans import count_mean


def read(h):
    return count_mean(h, "decode_rows")
