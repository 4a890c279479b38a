"""Mean ``chunk_kv_len`` of the step records over the window's steps:
the prefill chunk's KV length after the step (0 where a step carried no
chunk), which is what each of the chunk's query tiles walks."""

from benchmarks.lib import laguna_spans as ls
from benchmarks.lib.harness import say
from benchmarks.lib.program_spans import mean


def read(h):
    rows = ls.counts(h, "chunk_kv_len", "latent_row_bytes")
    if rows is None:
        return None
    ctx = [r[0] for r in rows]
    say(f"prefill chunk context over {len(rows)} steps: mean "
        f"{mean(ctx):.0f}, max {max(ctx)} tokens; a cache row is stored "
        f"in {rows[0][1]} B a token a layer")
    return mean(ctx)
