"""ZeRO sharding stages 1-3 (SURVEY §2.3 P2/P3).

Reference capability:
- Stage 1: DygraphShardingOptimizer (fleet/meta_optimizers/dygraph_optimizer/
  dygraph_sharding_optimizer.py) — optimizer states partitioned across the
  sharding group, tensor-fusion buffers, comm overlap.
- Stage 2/3: group_sharded_parallel(model, opt, level="os_g"/"p_g_os")
  (fleet/meta_parallel/sharding/group_sharded_stage{2,3}.py) — grad
  reduce-scatter hooks; param sharding with per-layer allgather/release.

TPU-native rework: every stage is a SHARDING-SPEC CHOICE, not an engine.
- stage 1 ("os"):   optimizer state arrays get the param's spec composed
  with the `sharding` axis on their first divisible dim; GSPMD keeps the
  Adam math local to each shard.
- stage 2 ("os_g"): grads inherit the same placement when the step runs
  under jit; eagerly we re-place grads at step time (the reduce-scatter is
  GSPMD's when the param update consumes a sharded grad).
- stage 3 ("p_g_os"): parameters themselves are sharded dim-0 on the
  sharding axis (fleet.distributed_model(shard_params_on="sharding")); the
  forward all-gather + post-use release the reference implements by hand is
  XLA's all-gather + live-range analysis.

The trainer's stacked decoder parameters (`[stage, (chunk,) layer, ...]`)
take the axis on a dim of the PARAMETER, never on the stack dims
(`compose_sharding_spec(..., n_lead=)`): every rank then holds a shard of
every layer, a layer's weights are an all-gather of shards and its
gradient a reduce-scatter, where whole layers on one rank would be
broadcast and their gradients all-reduced whole.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from .mesh import get_mesh, sanitize_spec

__all__ = ["compose_sharding_spec", "DygraphShardingOptimizer",
           "group_sharded_parallel", "save_group_sharded_model",
           "HybridParallelOptimizer"]

SHARDING_AXIS = "sharding"


def compose_sharding_spec(spec: Optional[P], shape, axis: str, size: int,
                          n_lead: int = 0) -> P:
    """Add ZeRO sharding on the first free dim divisible by the axis size
    (mirrors the reference's rank-partition of flattened state).

    `n_lead` leading dims are never taken: the trainer's stacked decoder
    parameters are `[stage, (chunk,) layer, ...param dims]`, and the axis
    has to split each layer's weights (an all-gather of shards brings a
    layer together), not hand whole layers to one rank (a broadcast)."""
    spec = spec or P()
    if size <= 1:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    if any(axis in (e if isinstance(e, tuple) else (e,))
           for e in entries if e is not None):
        return P(*entries)
    for d in range(n_lead, len(shape)):
        if entries[d] is None and shape[d] % size == 0:
            entries[d] = axis
            break
    return P(*entries)


def _placement_fn(mesh, axis: str):
    size = mesh.shape.get(axis, 1)

    def place(p: Tensor, arr):
        base = sanitize_spec(mesh, getattr(p, "_sharding_spec", None))
        spec = compose_sharding_spec(base, arr.shape, axis, size)
        return jax.device_put(arr, NamedSharding(mesh, spec))
    return place


class DygraphShardingOptimizer:
    """Stage-1 wrapper (ref: DygraphShardingOptimizer): optimizer states are
    partitioned over the sharding axis. Delegates everything else."""

    def __init__(self, optimizer, hcg=None, axis: str = SHARDING_AXIS):
        self._inner = optimizer
        self.axis = axis
        mesh = get_mesh()
        if mesh is not None and mesh.shape.get(axis, 1) > 1:
            optimizer._acc_placement = _placement_fn(mesh, axis)

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def step(self):
        self._inner.step()

    def clear_grad(self, set_to_zero: bool = False):
        self._inner.clear_grad(set_to_zero)


class _Stage2Optimizer(DygraphShardingOptimizer):
    """Stage-2 ("os_g"): additionally re-places grads at step time so the
    update consumes sharded grads (GSPMD reduce-scatter parity)."""

    def step(self):
        mesh = get_mesh()
        if mesh is not None and mesh.shape.get(self.axis, 1) > 1:
            place = _placement_fn(mesh, self.axis)
            for p in self._inner._param_groups:
                if p.grad is not None and not p.stop_gradient:
                    p.grad._data = place(p, p.grad._data)
        self._inner.step()


def group_sharded_parallel(model, optimizer, level: str = "os_g",
                           scaler=None, group=None, sync_buffers=False,
                           buffer_max_size=2 ** 23, segment_size=2 ** 20,
                           sync_comm=False, dp_group=None,
                           exclude_layer=None, axis: str = SHARDING_AXIS):
    """ref: python/paddle/distributed/sharding/group_sharded.py.
    level: "os" (stage1) | "os_g" (stage2) | "p_g_os" (stage3)."""
    if level not in ("os", "os_g", "p_g_os"):
        raise ValueError(f"bad level: {level}")
    mesh = get_mesh()
    if level == "p_g_os" and mesh is not None and \
            mesh.shape.get(axis, 1) > 1:
        from . import fleet
        model = fleet.distributed_model(model, shard_params_on=axis)
    if level == "os":
        optimizer = DygraphShardingOptimizer(optimizer, axis=axis)
    else:
        optimizer = _Stage2Optimizer(optimizer, axis=axis)
    return model, optimizer, scaler


def save_group_sharded_model(model, output, optimizer=None):
    """ref: save_group_sharded_model — gathers shards then saves; on TPU
    state arrays are addressable global views, so plain save works."""
    import os
    from ..framework.io import save
    os.makedirs(output, exist_ok=True)
    save(model.state_dict(), os.path.join(output, "model.pdparams"))
    if optimizer is not None:
        save(optimizer.state_dict(), os.path.join(output, "model.pdopt"))


class HybridParallelOptimizer:
    """ref: fleet/meta_optimizers/dygraph_optimizer/hybrid_parallel_optimizer
    — fixes global-norm grad clip across mp/pp/sharding axes. Under GSPMD a
    norm over sharded grads IS the global norm (psum inserted by the
    compiler), so this wrapper only needs to delegate; it exists for API
    parity and as the hook point for future per-axis scaling."""

    def __init__(self, optimizer, hcg=None, strategy=None):
        self._inner = optimizer

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def step(self):
        self._inner.step()

    def clear_grad(self, set_to_zero: bool = False):
        self._inner.clear_grad(set_to_zero)
