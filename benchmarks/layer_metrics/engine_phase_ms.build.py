"""Mean length of the program's ``serving.engine.build`` span
(the per-slot loop, ``allocator.extend``, the numpy row tables and copy-on-write copies) over the window's steps NOT under the profiler."""

from benchmarks.lib.program_spans import phase_ms


def read(h):
    return phase_ms(h, "build")
