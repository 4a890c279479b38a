"""Device ms a step of the state-space mixers — everything the program
runs under ``ssm_in_proj``, ``ssm_conv``, ``ssm_scan`` and ``ssm_out``,
all blocks — mean over the traced steps (``lib/nemotron_spans``)."""

from benchmarks.lib import nemotron_spans as ns
from benchmarks.lib.harness import say


def read(h):
    ms = ns.ms_a_step(h, ns.MIXER)
    if ms is None:
        return None
    parts = {n: ns.ms_a_step(h, (n,)) or 0.0 for n in ns.MIXER}
    say("state-space mixers, device ms a step: "
        + ", ".join(f"{k} {v:.3f}" for k, v in parts.items()))
    return ms
