"""Device ms a step of the nine Mamba-1 mixers and the seven gated
memory units — everything the program runs under ``ssm1_in_proj``,
``ssm1_conv``, ``ssm1_scan``, ``ssm1_out`` and ``gmu`` — mean over the
traced steps (``lib/phi4flash_spans``)."""

from benchmarks.lib import phi4flash_spans as ps
from benchmarks.lib.harness import say


def read(h):
    ms = ps.ms_a_step(h, ps.SSM1)
    if ms is None:
        return None
    parts = {n: ps.ms_a_step(h, (n,)) or 0.0 for n in ps.SSM1}
    say("Mamba-1 mixers and gated units, device ms a step: "
        + ", ".join(f"{k} {v:.3f}" for k, v in parts.items()))
    return ms
