"""Mean share of the state pool's slots that hold a request after a
step, over the window's steps (``state_pool_slots_used`` /
``state_pool_slots_total``); its line says the bytes a slot holds in
one state-space block as stored (``ssm_state_bytes``)."""

from benchmarks.lib.harness import say
from benchmarks.lib.laguna_spans import counts
from benchmarks.lib.program_spans import mean


def read(h):
    rows = counts(h, "state_pool_slots_used", "state_pool_slots_total")
    if rows is None:
        return None
    shares = [100.0 * used / total for used, total in rows if total]
    if not shares:
        return None
    held = counts(h, "ssm_state_bytes") or [(0,)]
    say(f"state pool used: mean {mean(shares):.2f} %, max "
        f"{max(shares):.2f} % over {len(shares)} steps; a slot holds "
        f"{max(b for b, in held)} B a state-space block")
    return mean(shares)
