"""Mean length of the program's ``serving.engine.sync`` span
(``np.asarray(logits)``: the wait for the device and the copy back) over the window's steps NOT under the profiler."""

from benchmarks.lib.program_spans import phase_ms


def read(h):
    return phase_ms(h, "sync")
