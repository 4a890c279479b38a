"""Device ms a step of BOTH mixers of the nine Falcon-H1 blocks —
everything the program runs under the state branch's names
(``ssm_in_proj``, ``ssm_conv``, ``ssm_scan``, ``ssm_out``) and the
attention branch's (``qkv_proj``, ``cache_write``, ``attention``,
``attn_out``) — mean over the traced steps (``lib/falcon_spans``)."""

from benchmarks.lib import falcon_spans as fs
from benchmarks.lib.harness import say


def read(h):
    ms = fs.ms_a_step(h, fs.SSM + fs.ATTENTION)
    if ms is None:
        return None
    parts = {n: fs.ms_a_step(h, (n,)) or 0.0 for n in fs.SSM + fs.ATTENTION}
    say("Falcon-H1 mixers, device ms a step: "
        + ", ".join(f"{k} {v:.3f}" for k, v in parts.items()))
    return ms
