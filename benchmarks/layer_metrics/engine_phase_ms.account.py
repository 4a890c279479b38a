"""Mean length of the program's ``serving.engine.account`` span
(``_account_step``, gauges, ``sample_gauges``, ``controller.on_step``: what the observability itself costs) over the window's steps NOT under the profiler."""

from benchmarks.lib.program_spans import phase_ms


def read(h):
    return phase_ms(h, "account")
