"""What the Laguna cell's per-layer readers share: the step records'
counts by cache kind and of the routed layers
(``tracing.STEP_COUNTS_BY_KIND`` / ``STEP_COUNTS_MOE``), and how the
routed FFN's operations show in the device trace.

A program without those counts (a parent of the PR that brought them)
gives ``None`` everywhere, and the metric is left out of the line.
"""

from __future__ import annotations

import re
from typing import List, Optional

from .harness import say
from .program_spans import in_window, mean, window


def counts(h, *keys: str) -> Optional[List[tuple]]:
    """The named counts of each step record in the window that has all
    of them."""
    w = window(h)
    if w is None:
        return None
    rows = [tuple(r[k] for k in keys) for _, r in in_window(w)
            if all(k in r for k in keys)]
    return rows or None


def pool_used_pct(h, kind: str) -> Optional[float]:
    rows = counts(h, f"pool_pages_used.{kind}", f"pool_pages_total.{kind}")
    if rows is None:
        return None
    shares = [100.0 * used / total for used, total in rows if total]
    if not shares:
        return None
    say(f"KV pool ({kind} layers) used: mean {mean(shares):.2f} %, max "
        f"{max(shares):.2f} % over {len(shares)} steps")
    return mean(shares)


def live_page_share(h, kind: str) -> Optional[float]:
    """As ``ragged_live_page_share``, for one layer of one cache kind."""
    rows = counts(h, f"pages_live.{kind}", f"pages_visited.{kind}")
    if rows is None:
        return None
    live, visited = (sum(r[i] for r in rows) for i in range(2))
    if not visited:
        return None
    say(f"ragged pages ({kind} layers, one layer) over {len(rows)} steps: "
        f"{live} live of {visited} visited")
    return 100.0 * live / visited


# ------------------------------------------------------- device trace
def moe_patterns(h):
    """(every operation of the routed FFN, its grouped GEMMs) as regular
    expressions over the device trace's HLO lines.  The grouped GEMM is
    XLA's ``ragged-dot`` (``jax.lax.ragged_dot``: a custom call named
    ``ragged-dot-none``, with a ``ragged-dot-metadata`` call before it).
    The rest has no stable name (PERF.md, Open questions) and is found
    by RESULT shapes only the routed FFN has: T x k pair rows, the
    router's [T, E] scores and its [T, k] choices.  Left out, because
    their results are plain [T, hidden]: the last fusion of the combine
    (it reads [T, k, hidden]) and the shared expert."""
    c = h.counters["cfg"]
    eng = h.counters["system"].engine
    # rows of the step's flat buffer: the decode slots' and the chunk's
    T = eng.max_slots * (1 + eng.spec_k) + eng.prefill_chunk
    k, E = c["num_experts_per_tok"], c["num_experts"]
    gmm = r"ragged-dot-none"
    shapes = rf" = \(?\w+\[(?:{T * k}[,\]]|{T},{k}[,\]]|{T},{E}\])"
    return (re.compile(rf"ragged-dot|{shapes}"), re.compile(gmm))


def seconds_of(red, rx) -> float:
    return sum(t for name, t in red.op_seconds.items() if rx.search(name))
