"""Device ms a step of the recurrence itself — the state update of the
decode rows, the chunk's scan and its state's write, what the program
runs under ``ssm_scan``, all blocks — mean over the traced steps."""

from benchmarks.lib import nemotron_spans as ns


def read(h):
    return ns.ms_a_step(h, ("ssm_scan",))
