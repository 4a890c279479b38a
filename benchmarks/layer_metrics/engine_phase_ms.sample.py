"""Mean length of the program's ``serving.engine.sample`` span
(argmax, ``_emit``, draft verification, ``_finish``) over the window's steps NOT under the profiler."""

from benchmarks.lib.program_spans import phase_ms


def read(h):
    return phase_ms(h, "sample")
