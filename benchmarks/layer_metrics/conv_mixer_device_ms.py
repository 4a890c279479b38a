"""Device ms a step of the gated short convolutions — everything the
program runs under ``lfm_in_proj``, ``lfm_conv`` and ``lfm_out``, all
conv blocks — mean over the traced steps (``lib/lfm2_spans``)."""

from benchmarks.lib import lfm2_spans as fs
from benchmarks.lib.harness import say


def read(h):
    ms = fs.ms_a_step(h, fs.MIXER)
    if ms is None:
        return None
    parts = {n: fs.ms_a_step(h, (n,)) or 0.0 for n in fs.MIXER}
    say("short-convolution mixers, device ms a step: "
        + ", ".join(f"{k} {v:.3f}" for k, v in parts.items()))
    return ms
