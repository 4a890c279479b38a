"""Version metadata (ref: python/paddle/version/__init__.py, generated
at build time upstream)."""

from __future__ import annotations

full_version = "0.1.0"
major = "0"
minor = "1"
patch = "0"
rc = "0"
cuda_version = "False"   # no CUDA in the TPU build (string per reference)
cudnn_version = "False"
xpu_version = "False"
istaged = False
commit = "unknown"
with_pip_cuda_libraries = "OFF"

__all__ = ["full_version", "major", "minor", "patch", "rc", "cuda",
           "cudnn", "show"]


def cuda() -> str:
    return cuda_version


def cudnn() -> str:
    return cudnn_version


def xpu() -> str:
    return xpu_version


def show() -> None:
    print(f"full_version: {full_version}")
    print(f"major: {major}\nminor: {minor}\npatch: {patch}\nrc: {rc}")
    print(f"cuda: {cuda_version}\ncudnn: {cudnn_version}")
    print("tpu: PJRT (libtpu)")
