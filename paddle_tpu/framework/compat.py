"""Framework-level flat-namespace API (ref: python/paddle/base/framework.py
+ python/paddle/device/__init__.py + python/paddle/base/core compile-info
queries — the non-tensor tail of paddle's ~700-name flat namespace,
SURVEY §2.2 row 2 / VERDICT r2 item 5).

TPU-native readings:
  - Places: the runtime is PJRT; `CustomPlace("tpu", i)` is the honest
    device identity, the CUDA/XPU/IPU places exist for API compatibility
    and compare equal only to themselves.
  - is_compiled_with_cuda/rocm/xpu/ipu: False — this build targets TPU
    through the PJRT plugin seam (device/ package).
  - get/set_cuda_rng_state: alias the accelerator generator state (the
    reference keeps a per-device generator list; here one JAX key chain
    drives the accelerator, see framework/random.py).
"""

from __future__ import annotations

import numpy as np

__all__ = [
    "iinfo", "finfo", "set_printoptions",
    "is_compiled_with_cuda", "is_compiled_with_rocm",
    "is_compiled_with_xpu", "is_compiled_with_cinn",
    "is_compiled_with_ipu", "is_compiled_with_mkldnn",
    "is_compiled_with_distribute", "is_compiled_with_custom_device",
    "CPUPlace", "CUDAPlace", "CUDAPinnedPlace", "XPUPlace", "IPUPlace",
    "CustomPlace", "get_cuda_rng_state", "set_cuda_rng_state", "batch",
]


# ---------------------------------------------------------------------------
# dtype info (ref: paddle.iinfo / paddle.finfo over paddle dtypes)
# ---------------------------------------------------------------------------
class iinfo:
    """Integer-dtype machine limits (ref: paddle.iinfo)."""

    def __init__(self, dtype):
        from ..core.dtypes import convert_dtype
        np_dt = np.dtype(convert_dtype(dtype) or dtype)
        info = np.iinfo(np_dt)
        self.bits = info.bits
        self.min = int(info.min)
        self.max = int(info.max)
        self.dtype = str(np_dt)

    def __repr__(self):
        return (f"iinfo(min={self.min}, max={self.max}, "
                f"bits={self.bits}, dtype={self.dtype})")


class finfo:
    """Floating-dtype machine limits (ref: paddle.finfo; bfloat16 via
    ml_dtypes, same as the reference's phi::dtype::bfloat16 table)."""

    def __init__(self, dtype):
        from ..core.dtypes import convert_dtype
        import ml_dtypes
        dt = convert_dtype(dtype) or dtype
        np_dt = np.dtype(dt)
        # ml_dtypes.finfo handles bfloat16/float8* AND the standard
        # floats; np.finfo rejects the ml_dtypes ones
        try:
            info = np.finfo(np_dt)
        except ValueError:
            info = ml_dtypes.finfo(np_dt)
        self.bits = info.bits
        self.min = float(info.min)
        self.max = float(info.max)
        self.eps = float(info.eps)
        self.tiny = float(info.tiny)
        self.smallest_normal = float(info.tiny)
        self.resolution = float(info.resolution)
        self.dtype = str(np_dt)

    def __repr__(self):
        return (f"finfo(min={self.min}, max={self.max}, eps={self.eps}, "
                f"bits={self.bits}, dtype={self.dtype})")


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Tensor print formatting (ref: paddle.set_printoptions). Tensor
    repr renders through numpy, so numpy's printoptions are the single
    source of truth."""
    kw = {}
    if precision is not None:
        kw["precision"] = int(precision)
    if threshold is not None:
        kw["threshold"] = int(threshold)
    if edgeitems is not None:
        kw["edgeitems"] = int(edgeitems)
    if linewidth is not None:
        kw["linewidth"] = int(linewidth)
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    np.set_printoptions(**kw)


# ---------------------------------------------------------------------------
# compile-info queries (ref: paddle.is_compiled_with_* → base/core)
# ---------------------------------------------------------------------------
def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    # the fusion compiler lives behind FLAGS_use_fusion_compiler (jit/
    # fusion.py); it is always built in, so the honest answer is True
    return True


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_mkldnn() -> bool:
    return False


def is_compiled_with_distribute() -> bool:
    return True


def is_compiled_with_custom_device(device_type: str) -> bool:
    """PJRT plugin seam: 'tpu' (and the test-time 'cpu') are the custom
    devices this build drives (ref: paddle.is_compiled_with_custom_device)."""
    return device_type in ("tpu", "cpu")


# ---------------------------------------------------------------------------
# places (ref: paddle.CPUPlace / CUDAPlace(i) / ... — base/core places)
# ---------------------------------------------------------------------------
class _Place:
    _kind = "place"
    _has_id = False

    def __init__(self, device_id: int = 0):
        self._id = int(device_id)

    def get_device_id(self) -> int:
        return self._id

    def __eq__(self, other):
        return (type(self) is type(other)
                and (not self._has_id or self._id == other._id))

    def __hash__(self):
        return hash((type(self).__name__, self._id if self._has_id else 0))

    def __repr__(self):
        return (f"Place({self._kind}:{self._id})" if self._has_id
                else f"Place({self._kind})")


class CPUPlace(_Place):
    _kind = "cpu"

    def __init__(self):
        super().__init__(0)


class CUDAPlace(_Place):
    _kind = "gpu"
    _has_id = True


class CUDAPinnedPlace(_Place):
    _kind = "gpu_pinned"

    def __init__(self):
        super().__init__(0)


class XPUPlace(_Place):
    _kind = "xpu"
    _has_id = True


class IPUPlace(_Place):
    _kind = "ipu"

    def __init__(self):
        super().__init__(0)


class CustomPlace(_Place):
    _has_id = True

    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_id)
        self._kind = str(device_type)

    def get_device_type(self) -> str:
        return self._kind

    def __eq__(self, other):
        return (type(self) is type(other) and self._kind == other._kind
                and self._id == other._id)

    def __hash__(self):
        return hash(("CustomPlace", self._kind, self._id))


# ---------------------------------------------------------------------------
# accelerator RNG state (ref: paddle.get_cuda_rng_state — per-device
# generator list; one JAX key chain here)
# ---------------------------------------------------------------------------
def get_cuda_rng_state():
    from .random import get_rng_state
    return get_rng_state()


def set_cuda_rng_state(state):
    from .random import set_rng_state
    return set_rng_state(state)


# ---------------------------------------------------------------------------
# legacy reader combinator (ref: paddle.batch — python/paddle/batch.py)
# ---------------------------------------------------------------------------
def batch(reader, batch_size, drop_last=False):
    """Wrap a sample-generator factory into a minibatch-generator factory
    (ref: paddle.batch legacy reader decorator)."""
    if batch_size <= 0:
        raise ValueError(f"batch_size must be positive, got {batch_size}")

    def batched():
        buf = []
        for sample in reader():
            buf.append(sample)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched
