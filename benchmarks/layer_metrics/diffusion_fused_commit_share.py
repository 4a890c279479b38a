"""Of the window's block commits, the share that cost no launch: the
commits that rode in the launch of their request's next block's first
pass (`diffusion_passes_fused`) over all commits, riding or alone
(`diffusion_passes_commit`), of the step records.  A request's last
block commits alone, as does a slot that finds the riding region full;
a program without the count (the commit always a launch of its own)
gives nothing."""

from benchmarks.lib import sdar_spans as ds
from benchmarks.lib.harness import say


def read(h):
    recs = ds.records(h, "diffusion_passes_fused", "diffusion_passes_commit")
    fused = sum(r["diffusion_passes_fused"] for r in recs)
    alone = sum(r["diffusion_passes_commit"] for r in recs)
    if not fused + alone:
        return None
    say(f"block commits over {len(recs)} steps: {fused} rode in the next "
        f"block's first launch, {alone} took a launch of their own")
    return fused / (fused + alone)
