"""A row statistic of an online softmax, LANE-REPLICATED.

The running maximum and sum of the attention kernels (and a visit's
m_new / alpha) are [rows, 128] arrays with a row's value in every lane,
as in the bundled flash kernel, so they meet the [rows, keys] scores and
the [rows, D] accumulator lane for lane. As [rows, 1] columns (one live
lane a register, spread along the lanes again at every use) the training
cell's flash forward took 8.3 ms where this form takes 5.0 (PERF.md
section 6, PR 49), and a (tile, page) update of the ragged kernel's
latent launch 0.52-0.56 us where this form takes 0.42-0.47 (PR 55).
Shared by `pallas_flash` (training) and `pallas_ragged` (serving).
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["LANES", "lanes"]

#: lanes of a vector register: the minor extent of a replicated statistic
LANES = 128


def lanes(x, n: int):
    """The lane-replicated statistic `x` ([rows, 128]) against [rows, n]
    data: itself at 128 columns, a lane slice under them (the ragged
    kernel's pages of 64, toy widths), tiled along a multiple of them
    (256 score columns, 512 value columns).

    A width over 128 that is no multiple of it gets the one-lane column,
    which broadcasts: correct, and the slow form this layout replaces.
    No cell's shape reaches it (scores are pages or key blocks of 64,
    128, 256 or 512; values 128, 256 or 512 wide; `flash_kernel_eligible`
    refuses such a head), only the CPU suite's toy widths could."""
    if n < LANES:
        return x[:, :n]
    if n % LANES:
        return x[:, :1]
    return jnp.tile(x, (1, n // LANES)) if n > LANES else x
