"""Run a Mosaic kernel on a multi-device mesh.

The TPU lowering refuses a Mosaic kernel the partitioner would have to
split ("cannot be automatically partitioned"): under a multi-device
mesh it takes one only inside a shard_map that is manual over EVERY
mesh axis.  Attention-shaped kernels are independent per batch row and
per head, so each device runs the kernel on its own shard — batch over
the data axes, heads over mp, everything else whole (the hybrid layouts
of trainer/pretrain.py).  Inside the pipeline's pp-manual region the
remaining axes go manual here.

Callers wrap the kernel launches INSIDE their custom_vjp rules, so
autodiff never transposes this shard_map (nested under the pp region
its residual specs fail Shardy's verifier).
"""

from __future__ import annotations

import contextlib

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["kernel_mesh", "step_mesh", "on_mesh"]

_kernel_mesh = None


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Name the device mesh a traced step runs on (the trainer's jitted
    step is traced outside any mesh_context)."""
    global _kernel_mesh
    prev, _kernel_mesh = _kernel_mesh, mesh
    try:
        yield
    finally:
        _kernel_mesh = prev


def step_mesh():
    """The mesh :func:`kernel_mesh` names, None outside it."""
    return _kernel_mesh


def on_mesh(kernel, args, layouts, out_layouts):
    """``kernel(*args)`` per shard of the step's mesh (:func:`kernel_mesh`,
    else the ambient mesh_context); a plain call on one device.

    ``layouts`` / ``out_layouts`` name each operand's / result's axes:
    ``"b"`` batch, ``"h"`` heads, anything else unsharded — e.g.
    ``"bhsd"``.  The first operand's sizes decide which mesh axes
    divide."""
    from ..distributed.mesh import get_mesh
    mesh = _kernel_mesh or get_mesh()
    if mesh is None or mesh.size == 1:
        return kernel(*args)
    outer = set(jax.sharding.get_abstract_mesh().manual_axes)
    size = dict(mesh.shape)
    B = args[0].shape[layouts[0].index("b")]
    H = args[0].shape[layouts[0].index("h")]
    batch = []
    for ax in ("dcn_dp", "dp", "sharding"):
        n = size.get(ax, 1)
        if n > 1 and ax not in outer and B % n == 0:
            batch.append(ax)
            B //= n
    heads = "mp" if size.get("mp", 1) > 1 and "mp" not in outer \
        and H % size["mp"] == 0 else None
    roles = {"b": tuple(batch) or None, "h": heads}

    def spec(layout):
        return P(*(roles.get(r) for r in layout))

    return jax.shard_map(
        kernel, mesh=None if outer else mesh,
        in_specs=tuple(spec(l) for l in layouts),
        out_specs=jax.tree.map(spec, out_layouts),
        axis_names=set(mesh.axis_names) - outer,
        check_vma=bool(outer))(*args)
