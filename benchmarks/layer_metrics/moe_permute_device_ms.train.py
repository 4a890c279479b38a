"""Device ms a training step of everything the routed FFN does AROUND
its grouped GEMMs: the router, softmax, top-k and statistics
(``moe_route``), the sort and gather of all k x T pair rows
(``moe_dispatch``), the unsort and weighted scatter-add
(``moe_combine``) — forward, recomputed forward and backward together.
What keeping the absent experts' pair rows costs a trainer (ROADMAP
S12)."""

from benchmarks.lib import mellum_spans as ms
from benchmarks.lib.harness import say


def read(h):
    steps = ms.traced_steps(h)
    parts = {name: ms.seconds(h, (name,)) for name in ms.PERMUTE}
    if not steps or sum(parts.values()) <= 0:
        return None
    say("routed FFN around its GEMMs, ms a step: " + ", ".join(
        f"{k} {1e3 * v / steps:.2f}" for k, v in parts.items()))
    return 1e3 * sum(parts.values()) / steps
