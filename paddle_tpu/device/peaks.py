"""Published per-chip peak rates, keyed by the ``device_kind`` string
jax reports — the ONE table every utilisation / roofline figure in the
repo divides by.

Source: Google Cloud TPU documentation, "System architecture" pages of
each generation (TPU v4, v5e, v5p, v6e): peak dense bf16 FLOP/s, HBM
bandwidth and HBM capacity of one chip.  A device that is not in the
table has no peak: a benchmark fails on it (:func:`require_peak`), a
log writes null (:func:`peak` returns None).  Nothing defaults to
another chip's figures.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

__all__ = ["Peak", "PEAKS", "peak", "require_peak"]


class Peak(NamedTuple):
    bf16_flops: float        # FLOP/s
    hbm_bytes_per_s: float   # B/s
    hbm_bytes: float         # B


PEAKS = {
    "TPU v4": Peak(275e12, 1228e9, 32e9),
    "TPU v5 lite": Peak(197e12, 819e9, 16e9),      # v5e
    "TPU v5p": Peak(459e12, 2765e9, 95e9),
    "TPU v6 lite": Peak(918e12, 1640e9, 32e9),     # v6e
}


def peak(device=None) -> Optional[Peak]:
    """The peaks of ``device`` (default: ``jax.devices()[0]``), or None
    when its ``device_kind`` is not in the table."""
    if device is None:
        import jax
        device = jax.devices()[0]
    return PEAKS.get(device.device_kind)


def require_peak(device=None) -> Peak:
    """:func:`peak`, or an error naming the device — for benchmarks,
    where a figure against an assumed peak would be a wrong figure."""
    if device is None:
        import jax
        device = jax.devices()[0]
    p = PEAKS.get(device.device_kind)
    if p is None:
        raise RuntimeError(
            f"no published peak for device_kind {device.device_kind!r} "
            f"(platform {device.platform!r}); known: {sorted(PEAKS)}")
    return p
