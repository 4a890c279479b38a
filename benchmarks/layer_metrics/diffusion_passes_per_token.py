"""Passes of a block a committed token: (denoise + commit passes of the
window's launches, a slot a launch) / the tokens their commits emitted
(`diffusion_passes_*`, `diffusion_tokens_committed` of the step
records).  1.25 by construction at five launches for four tokens; first
blocks with given tokens and cut last blocks cost more."""

from benchmarks.lib import sdar_spans as ds
from benchmarks.lib.harness import say


def read(h):
    recs = ds.records(h, "diffusion_tokens_committed")
    tokens = sum(r["diffusion_tokens_committed"] for r in recs)
    if not tokens:
        return None
    den = sum(r["diffusion_passes_denoise"] for r in recs)
    com = sum(r["diffusion_passes_commit"] for r in recs)
    say(f"diffusion over blocks, {len(recs)} steps: {den} denoise + {com} "
        f"commit passes for {tokens} committed tokens")
    return (den + com) / tokens
