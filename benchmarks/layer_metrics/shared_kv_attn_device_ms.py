"""Device ms a step of the EIGHT ragged launches over the one shared
pool — layer 17's own and the seven cross layers' — everything the
program runs under ``shared_attention`` (the launch and the queries'
padding around it), mean over the traced steps
(``lib/phi4flash_spans``).  In every other cell a layer reads its own
pages once; here one pool is read eight times a step."""

from benchmarks.lib import phi4flash_spans as ps
from benchmarks.lib.harness import say


def read(h):
    ms = ps.ms_a_step(h, ps.SHARED)
    if ms is None:
        return None
    pairs = ps.traced_pairs(h)
    readers = max((r["shared_pool_readers"] for _, r in pairs), default=0)
    own = ps.ms_a_step(h, ("attention",)) or 0.0
    say(f"attention, device ms a step: {readers} launches over the shared "
        f"pool {ms:.3f}, the window layers' launches over their own pages "
        f"{own:.3f}")
    return ms
