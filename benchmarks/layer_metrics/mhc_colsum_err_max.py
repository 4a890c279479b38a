"""The largest |column sum - 1| of any residual mixing matrix of any
row a sequence owned, over the window's steps (the step records'
``mhc_colsum_err_max``, taken on the device): whether the Sinkhorn
iterations the configuration states converged on this traffic.  Rows
sum to 1 by construction (they are normalised last)."""

from benchmarks.lib import xing_spans as xs
from benchmarks.lib.harness import say
from benchmarks.lib.laguna_spans import counts
from benchmarks.lib.program_spans import mean


def read(h):
    rows = counts(h, "mhc_colsum_err_max") if xs.wide(h) else None
    if rows is None:
        return None
    errs = [e for (e,) in rows]
    say(f"residual matrices' column sums over {len(errs)} steps: the "
        f"largest departure from 1 is {max(errs):.4g}, a step's largest "
        f"on average {mean(errs):.4g}")
    return max(errs)
