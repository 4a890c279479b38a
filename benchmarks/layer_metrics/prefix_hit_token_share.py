"""Share of the window's admitted prompt tokens that were ADOPTED from
the prefix cache and not prefilled: the step records'
``prefix_tokens_adopted`` over that plus ``prefill_rows``
(``tracing.STEP_COUNTS_PREFIX``: any engine that holds a prefix cache,
whatever its family; every step of the window, drain included)."""

from benchmarks.lib import lfm2_spans as fs
from benchmarks.lib.harness import say


def read(h):
    recs = fs.records(h, "prefix_tokens_adopted", "prefill_rows")
    adopted = sum(r["prefix_tokens_adopted"] for r in recs)
    prefilled = sum(r["prefill_rows"] for r in recs)
    if not adopted + prefilled:
        return None
    say(f"prefix cache over {len(recs)} steps: {adopted} tokens adopted in "
        f"{sum(r['prefix_pages_adopted'] for r in recs)} pages, {prefilled} "
        f"prefilled, {sum(r['prefix_pages_evicted'] for r in recs)} trie "
        f"pages evicted, "
        f"{sum(r.get('tail_snapshots_written', 0) for r in recs)} pages' "
        f"snapshots written, "
        f"{sum(r.get('tail_restores', 0) for r in recs)} restored")
    return adopted / (adopted + prefilled)
