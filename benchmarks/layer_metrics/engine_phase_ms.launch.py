"""Mean length of the program's ``serving.engine.launch`` span
(the ``jnp.asarray`` transfers and the dispatch of the jitted step) over the window's steps NOT under the profiler."""

from benchmarks.lib.program_spans import phase_ms


def read(h):
    return phase_ms(h, "launch")
