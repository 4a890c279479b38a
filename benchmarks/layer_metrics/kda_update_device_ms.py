"""Device ms a step of the decode rows' delta-rule update — each live
slot's state once through the kernel, what the program runs under
``kda_state_update``, all blocks — mean over the traced steps."""

from benchmarks.lib import ling_spans as lg


def read(h):
    return lg.ms_a_step(h, ("kda_state_update",))
