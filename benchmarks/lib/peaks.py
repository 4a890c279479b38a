"""Published per-chip peaks, keyed by the ``device_kind`` jax reports.

The benchmark's own copy of ``paddle_tpu/device/peaks.py`` (same source:
Google Cloud TPU documentation, "System architecture" page of each
generation).  A later PR may not move the yardstick, so the benchmark
does not import the program's table.  A device that is not here has no
peak: the benchmark fails on it.
"""

from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    bf16_flops: float        # FLOP/s
    hbm_bytes_per_s: float   # B/s
    hbm_bytes: float         # B
    ici_bits_per_s: float    # chip-to-chip, bit/s


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9, 16e9, 1600e9),      # v5e
}


def require_peak(device_kind: str) -> Peak:
    if device_kind not in PEAKS:
        raise RuntimeError(
            f"no published peak for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
