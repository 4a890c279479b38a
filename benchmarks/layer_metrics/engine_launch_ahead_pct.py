"""Share of the window's ``step()`` calls that dispatched their launch
BEFORE reading the previous one back (the step record's
``launch_ahead``); says the other counts PRs 29, 34 and 36 brought."""

from benchmarks.lib.harness import say
from benchmarks.lib.program_spans import in_window, window


def read(h):
    w = window(h)
    rows = [r for _, r in in_window(w)] if w is not None else []
    if not rows or "launch_ahead" not in rows[0]:
        return None
    ahead = sum(r["launch_ahead"] for r in rows)
    runs = sum(r.get("append_runs", 0) for r in rows)
    tokens = sum(r["decode_rows"] + r["prefill_rows"] for r in rows)
    say(f"launch ahead: {ahead} of {len(rows)} calls; rows_dropped "
        f"{sum(r.get('rows_dropped', 0) for r in rows)} in all, "
        f"pools_in_place at least "
        f"{min(r.get('pools_in_place', 0) for r in rows)}, append_runs "
        f"{runs} for {tokens} rows"
        + (f" ({tokens / runs:.2f} rows a run)" if runs else ""))
    return 100.0 * ahead / len(rows)
