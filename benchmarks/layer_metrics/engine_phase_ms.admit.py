"""Mean length of the program's ``serving.engine.admit`` span
(the expiry and deadline sweeps and admission (page reservation, prefix lookup, preemption)) over the window's steps NOT under the profiler."""

from benchmarks.lib.program_spans import phase_ms


def read(h):
    return phase_ms(h, "admit")
