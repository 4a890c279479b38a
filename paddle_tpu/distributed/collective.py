"""Eager collective communication API (ref: python/paddle/distributed/
communication/ — all_reduce/all_gather/… over ProcessGroupNCCL; SURVEY §2.3
P13 and §5.8 altitude (1)).

TPU-native mechanism: each collective is a small jitted shard_map program
over the current mesh axis — the XLA collective (psum/all_gather/ppermute)
runs on ICI exactly where NCCL rings ran. On a 1-device (or axis-less) mesh
they degrade to identity, which is how the reference's tests run single-rank.

In-place semantics preserved: `all_reduce(t)` rewrites t's buffer.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from .. import observability as _obs
from .. import resilience as _res
from ..core.tensor import Tensor
from . import watchdog as _wd
from .mesh import get_mesh

# per-collective visibility (ISSUE 1): calls, input-payload bytes, and
# host wall-time per call. Latency includes XLA dispatch only — PJRT runs
# collectives async, so device time shows up here only when the call
# itself materializes results (the eager in-place rewrite paths do).
_COLL_CALLS = _obs.registry().counter(
    "pt_collective_calls_total", "collective API calls",
    labels=("collective",))
_COLL_BYTES = _obs.registry().counter(
    "pt_collective_bytes_total", "input payload bytes per collective",
    labels=("collective",))
_COLL_LAT = _obs.registry().histogram(
    "pt_collective_seconds", "collective call wall time",
    labels=("collective",))


def _payload_bytes(args) -> int:
    n = 0
    for a in args:
        if isinstance(a, Tensor):
            n += int(a._data.size) * jnp.dtype(a._data.dtype).itemsize
        elif isinstance(a, (list, tuple)):
            n += _payload_bytes(a)
    return n


def _describe(args, shapes=None, dtypes=None):
    """Tensor shapes/dtypes of a call's inputs, for the flight record."""
    if shapes is None:
        shapes, dtypes = [], []
    for a in args:
        if isinstance(a, Tensor):
            shapes.append(list(a._data.shape))
            dtypes.append(str(a._data.dtype))
        elif isinstance(a, (list, tuple)):
            _describe(a, shapes, dtypes)
    return shapes, dtypes


def _maybe_fault(name: str) -> None:
    """Fault-injection hook shared by every collective entry point:
    collective_delay@collective=<name>[:ms=N] sleeps before dispatch,
    collective_hang@collective=<name>[:ms=N] simulates a dead-peer hang
    (bounded at ms, default 30 s; the watchdog is expected to cancel it
    first and raise CollectiveTimeout), collective_error@collective=<name>
    raises InjectedFault. `collective` may also be `all` to target every
    collective."""
    plan = _res.active_plan()
    if plan is None:
        return
    for site in (name, "all"):      # delays first: a delayed call can
        rule = _res.inject("collective_delay", collective=site)
        if rule is not None:        # ALSO error below, like real flakes
            time.sleep(float(rule.opts.get("ms", 50.0)) / 1e3)
    for site in (name, "all"):
        rule = _res.inject("collective_hang", collective=site)
        if rule is not None:
            _wd.simulate_hang(name, float(rule.opts.get("ms", 30000.0)) / 1e3)
    for site in (name, "all"):
        rule = _res.inject("collective_error", collective=site)
        if rule is not None:
            raise _res.InjectedFault(
                f"collective_error injected in {name}", rule)


def _instrumented(fn):
    """Wrap a collective: count calls/bytes, time the call, and log it to
    the watchdog flight recorder. Disabled metrics / disabled watchdog
    each cost one attribute check."""
    name = fn.__name__

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        rec = None
        if _wd.enabled():
            shapes, dtypes = _describe(args)
            try:
                axis = _axis_of(kwargs.get("group"))
            except TypeError:
                axis = None
            rec = _wd.start_record(name, shapes, dtypes,
                                   _payload_bytes(args), axis)
        try:
            _maybe_fault(name)
            if not _obs.enabled():
                out = fn(*args, **kwargs)
            else:
                t0 = time.perf_counter()
                try:
                    out = fn(*args, **kwargs)
                finally:
                    _COLL_CALLS.labels(collective=name).inc()
                    _COLL_BYTES.labels(collective=name).inc(
                        _payload_bytes(args))
                    _COLL_LAT.labels(collective=name).observe(
                        time.perf_counter() - t0)
        except _wd.CollectiveTimeout:
            _wd.end_record(rec, "timeout")
            raise
        except BaseException:
            _wd.end_record(rec, "error")
            raise
        _wd.end_record(rec, "ok")
        return out
    return wrapper

__all__ = ["ReduceOp", "all_reduce", "all_gather", "reduce_scatter",
           "broadcast", "scatter", "reduce", "alltoall", "send", "recv",
           "barrier", "new_group", "get_group", "wait", "stream"]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A mesh axis standing in for a comm group (ref: ProcessGroup gid)."""

    def __init__(self, axis: str, mesh: Optional[Mesh] = None):
        self.axis = axis
        self.mesh = mesh

    @property
    def nranks(self) -> int:
        m = self.mesh or get_mesh()
        return m.shape.get(self.axis, 1) if m is not None else 1

    def __repr__(self):
        return f"Group(axis={self.axis}, nranks={self.nranks})"


_groups = {}


def new_group(ranks=None, backend=None, axis: str = "dp") -> Group:
    g = Group(axis)
    _groups[axis] = g
    return g


def get_group(axis: str = "dp") -> Group:
    return _groups.get(axis) or new_group(axis=axis)


def _axis_of(group) -> str:
    if group is None:
        return "dp"
    if isinstance(group, Group):
        return group.axis
    if isinstance(group, str):
        return group
    raise TypeError(f"bad group: {group}")


def _active_mesh(axis: str) -> Optional[Mesh]:
    m = get_mesh()
    if m is None or axis not in m.axis_names or m.shape[axis] == 1:
        return None
    return m


def _collective(mesh: Mesh, axis: str, fn, x):
    """Run fn inside shard_map over `axis`, fully replicated on other axes."""
    spec = P(axis)
    # operate on a leading stacked axis: we gather per-device values by
    # treating the tensor as replicated except along the comm axis.
    out = shard_map(fn, mesh=mesh, in_specs=(P(*([None] * x.ndim)),),
                    out_specs=P(*([None] * x.ndim)), check_vma=False)(x)
    return out


@_instrumented
def all_reduce(tensor: Tensor, op: str = ReduceOp.SUM, group=None,
               sync_op: bool = True) -> Tensor:
    axis = _axis_of(group)
    mesh = _active_mesh(axis)
    if mesh is None:
        return tensor
    red = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin,
           "avg": lambda v, a: jax.lax.pmean(v, a)}[op if isinstance(op, str) else ReduceOp.SUM]

    def fn(x):
        return red(x, axis)

    nd = tensor.ndim
    out = shard_map(fn, mesh=mesh,
                    in_specs=(P(*([None] * nd)),),
                    out_specs=P(*([None] * nd)), check_vma=False)(tensor._data)
    tensor._data = out
    return tensor


@_instrumented
def all_gather(tensor_list: Optional[List], tensor: Tensor = None, group=None,
               sync_op: bool = True):
    """paddle signature: all_gather(out_list, in_tensor). With a 1-axis mesh
    this returns each rank's replica-view concatenated along dim 0."""
    if tensor is None:  # also allow functional style: all_gather(t)
        tensor, tensor_list = tensor_list, None
    axis = _axis_of(group)
    mesh = _active_mesh(axis)
    if mesh is None:
        if tensor_list is not None:
            tensor_list.append(tensor)
            return tensor_list
        return Tensor(tensor._data[None])
    n = mesh.shape[axis]

    def fn(x):
        return jax.lax.all_gather(x, axis)

    nd = tensor.ndim
    out = shard_map(fn, mesh=mesh, in_specs=(P(*([None] * nd)),),
                    out_specs=P(*([None] * (nd + 1))), check_vma=False)(
        tensor._data)
    if tensor_list is not None:
        for i in range(n):
            tensor_list.append(Tensor(out[i]))
        return tensor_list
    return Tensor(out)


@_instrumented
def reduce_scatter(tensor: Tensor, tensor_or_tensor_list, op=ReduceOp.SUM,
                   group=None, sync_op=True) -> Tensor:
    axis = _axis_of(group)
    mesh = _active_mesh(axis)
    src = tensor_or_tensor_list
    if isinstance(src, (list, tuple)):
        src = Tensor(jnp.concatenate([t._data for t in src], axis=0))
    if mesh is None:
        tensor._data = src._data
        return tensor
    n = mesh.shape[axis]

    def fn(x):
        return jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)

    nd = src.ndim
    out = shard_map(fn, mesh=mesh, in_specs=(P(*([None] * nd)),),
                    out_specs=P(axis, *([None] * (nd - 1))),
                    check_vma=False)(src._data)
    # out is sharded along dim0; each rank's shard is this rank's result —
    # materialize the local view replicated for eager parity
    tensor._data = out
    return tensor


@_instrumented
def broadcast(tensor: Tensor, src: int = 0, group=None, sync_op=True) -> Tensor:
    """Within a mesh axis all replicas already hold identical values under
    SPMD; broadcast selects the src rank's value for all."""
    axis = _axis_of(group)
    mesh = _active_mesh(axis)
    if mesh is None:
        return tensor

    def fn(x):
        idx = jax.lax.axis_index(axis)
        val = jax.lax.all_gather(x, axis)[src]
        return val

    nd = tensor.ndim
    out = shard_map(fn, mesh=mesh, in_specs=(P(*([None] * nd)),),
                    out_specs=P(*([None] * nd)), check_vma=False)(tensor._data)
    tensor._data = out
    return tensor


def reduce(tensor: Tensor, dst: int = 0, op=ReduceOp.SUM, group=None,
           sync_op=True) -> Tensor:
    # SPMD: reduce == all_reduce with the result meaningful on dst
    return all_reduce(tensor, op if isinstance(op, str) else ReduceOp.SUM,
                      group, sync_op)


@_instrumented
def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    axis = _axis_of(group)
    mesh = _active_mesh(axis)
    if isinstance(in_tensor_list, Tensor):
        stacked = in_tensor_list._data
    else:
        stacked = jnp.stack([t._data for t in in_tensor_list], axis=0)
    if mesh is None:
        outs = [Tensor(s) for s in stacked]
        if out_tensor_list is not None:
            out_tensor_list.extend(outs)
            return out_tensor_list
        return Tensor(stacked)

    def fn(x):
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=True)

    nd = stacked.ndim
    out = shard_map(fn, mesh=mesh, in_specs=(P(*([None] * nd)),),
                    out_specs=P(*([None] * nd)), check_vma=False)(stacked)
    outs = [Tensor(o) for o in out]
    if out_tensor_list is not None:
        out_tensor_list.extend(outs)
        return out_tensor_list
    return Tensor(out)


def send(tensor, dst=0, group=None, sync_op=True):
    raise NotImplementedError(
        "eager point-to-point send/recv maps to compiled collective_permute "
        "on TPU — use distributed.pipeline (SURVEY §5.8: NCCL p2p has no "
        "eager ICI analog; pipeline schedules compile their permutes)")


def recv(tensor, src=0, group=None, sync_op=True):
    raise NotImplementedError(
        "eager point-to-point send/recv maps to compiled collective_permute "
        "on TPU — use distributed.pipeline")


@_instrumented
def barrier(group=None):
    """Fence all outstanding device work (SPMD: program order is the sync).

    With `FLAGS_collective_timeout` > 0 the fence runs in a helper thread
    and a dead peer raises a diagnostic `CollectiveTimeout` (flight dump +
    lagging rank) instead of hanging the pod forever on
    `block_until_ready`."""
    tmo = _wd.timeout_s()
    if tmo <= 0:
        for a in jax.live_arrays():
            a.block_until_ready()
        return
    err: List[BaseException] = []

    def _fence():
        try:
            for a in jax.live_arrays():
                a.block_until_ready()
        except BaseException as e:       # surfaced in the caller below
            err.append(e)

    t = threading.Thread(target=_fence, daemon=True, name="pt-barrier-fence")
    t0 = time.monotonic()
    t.start()
    while True:
        t.join(timeout=0.005)
        if not t.is_alive():
            break
        rec = _wd.current_record()
        if rec is not None and rec.cancelled:
            raise _wd.timeout_error(rec, "barrier", rec.elapsed_s)
        if time.monotonic() - t0 > tmo:
            elapsed = time.monotonic() - t0
            if rec is not None:
                _wd.handle_timeout(rec)
            raise _wd.timeout_error(rec, "barrier", elapsed)
    if err:
        raise err[0]


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor) and isinstance(tensor._data, jax.Array):
        tensor._data.block_until_ready()


class stream:
    """paddle.distributed.stream.* parity: explicit-stream variants are
    no-ops on TPU (PJRT owns ordering); same functions re-exported."""
    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    reduce_scatter = staticmethod(reduce_scatter)
    broadcast = staticmethod(broadcast)
    alltoall = staticmethod(alltoall)
