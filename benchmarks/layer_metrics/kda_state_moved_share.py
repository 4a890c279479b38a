"""100 x the recurrent-state bytes the launches' named slots NEED (each
once in and once out a KDA block; a slot that starts its sequence is
not read) over ``ssm_state_bytes_moved`` of the step records, over the
window's steps: 100 means no idle slot's state was touched."""

from benchmarks.lib import costs_ling as costs, ling_spans as lg
from benchmarks.lib.harness import say
from benchmarks.lib.laguna_spans import counts


def read(h):
    rows = counts(h, "ssm_slots_live", "ssm_state_resets",
                  "ssm_state_bytes_moved")
    if rows is None or not lg.kda(h):
        return None
    cfg = h.counters["cfg"]
    per = costs.kinds(cfg)["K"] * costs.state_only_bytes(cfg)
    need = sum(per * (2 * live - starts) for live, starts, _ in rows)
    moved = sum(m for _, _, m in rows)
    if not moved:
        return None
    say(f"delta-rule state over {len(rows)} steps: {need / 1e9:.2f} GB "
        f"needed, {moved / 1e9:.2f} GB moved")
    return 100.0 * need / moved
