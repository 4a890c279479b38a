"""Device ms a step of the KDA linear-attention mixers — everything the
program runs under ``kda_in_proj``, ``kda_conv``, ``kda_state_update``,
``kda_chunk_scan`` and ``kda_out``, all blocks — mean over the traced
steps (``lib/ling_spans``)."""

from benchmarks.lib import ling_spans as lg
from benchmarks.lib.harness import say


def read(h):
    ms = lg.ms_a_step(h, lg.MIXER)
    if ms is None:
        return None
    parts = {n: lg.ms_a_step(h, (n,)) or 0.0 for n in lg.MIXER}
    say("KDA mixers, device ms a step: "
        + ", ".join(f"{k} {v:.3f}" for k, v in parts.items()))
    return ms
