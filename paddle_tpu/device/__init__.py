"""Device management (ref surface: python/paddle/device/).

On TPU, placement is owned by shardings/PJRT rather than per-tensor device
moves; set_device selects the default jax backend for eager ops.
"""

from __future__ import annotations

import jax

__all__ = ["set_device", "get_device", "device_count", "is_compiled_with_cuda",
           "is_compiled_with_xpu", "is_compiled_with_tpu", "get_all_devices",
           "synchronize", "memory_stats", "max_memory_allocated",
           "memory_allocated"]

_current = None


def _platform_of(spec: str) -> str:
    base = spec.split(":")[0]
    return {"gpu": "tpu", "cuda": "tpu", "tpu": "tpu",
            "cpu": "cpu"}.get(base, base)


def set_device(device: str):
    """'tpu', 'tpu:0', 'cpu' — 'gpu' aliases to the accelerator for
    code written against the reference API."""
    global _current
    plat = _platform_of(device)
    idx = int(device.split(":")[1]) if ":" in device else 0
    for d in jax.devices():
        if d.id == idx:
            _current = d
            break
    else:
        _current = jax.devices()[0]
    jax.config.update("jax_default_device", _current)
    return _current


def get_device() -> str:
    if _current is None:
        d = jax.devices()[0]
    else:
        d = _current
    return f"{d.platform}:{d.id}"


def device_count() -> int:
    return jax.device_count()


def get_all_devices():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform != "cpu" for d in jax.devices())


def synchronize(device=None) -> None:
    """Fence all async work (parity: paddle.device.synchronize)."""
    for d in jax.live_arrays():
        d.block_until_ready()


def memory_stats(device=None) -> dict:
    d = jax.devices()[0] if device is None else device
    try:
        return d.memory_stats() or {}
    except Exception:
        return {}


def memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("peak_bytes_in_use", 0))
