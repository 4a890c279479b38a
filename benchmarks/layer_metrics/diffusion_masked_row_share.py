"""Of the decode rows the window's launches computed for live blocks,
the share whose logits the transfer rule reads: the rows still masked
going into a denoise pass (`diffusion_rows_masked` / `decode_rows`).
(4 + 3 + 2 + 1) / 20 = 0.5 at four denoise passes and a commit over
blocks of four."""

from benchmarks.lib import sdar_spans as ds
from benchmarks.lib.harness import say


def read(h):
    recs = ds.records(h, "diffusion_rows_masked", "decode_rows")
    rows = sum(r["decode_rows"] for r in recs)
    if not rows:
        return None
    masked = sum(r["diffusion_rows_masked"] for r in recs)
    say(f"block rows over {len(recs)} steps: {masked} still masked of "
        f"{rows} computed")
    return masked / rows
