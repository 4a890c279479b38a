"""Tensor/sequence-parallel layers (ref: python/paddle/distributed/fleet/
meta_parallel/parallel_layers/mp_layers.py — SURVEY §2.3 P4/P5).

TPU-native mechanism: the layers ARE plain Linear/Embedding math; parallelism
comes from (a) a sharding spec attached to each weight (materialized by
fleet.distributed_model / shard_layer), and (b) sharding constraints on
activations. GSPMD then inserts the collectives the reference codes by hand
(vocab-parallel CE: sharded logsumexp; column: the gather of its input fwd,
a reduce-scatter bwd; row and vocab embedding: a reduce-scatter fwd, a
gather bwd).

The layout of a `[B, S, H]` activation BETWEEN a row-parallel product and
the next column-parallel one is the program's choice, from the mesh and the
shape (`seq_sharded_on`): where the step's mesh has `mp` > 1 and `S`
divides by it, the activation is held `[B, S/mp, H]` a chip — the row
product is constrained to that directly (its partial sums leave the matmul
as a reduce-scatter, never an all-reduce), the residual add and the norm run
on `S/mp` rows, the norm's OUTPUT carries the same constraint, and the
gather sits at the column matmul that needs every row (Megatron sequence
parallelism, ref: fleet/utils/sequence_parallel_utils.py, without its
switch).  Everywhere else (one chip, `mp` 1, a length `mp` does not divide,
a `sep` axis that already splits the sequence, the timetable pipeline
executor's branches) the row product is replicated as it always was: the
fwd all-reduce.  Layers degrade gracefully to single-device when no mesh is
active.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from .mesh import get_mesh
from .auto_parallel import mark_sharding

__all__ = ["ColumnParallelLinear", "RowParallelLinear",
           "VocabParallelEmbedding", "ParallelCrossEntropy",
           "annotate_sequence_parallel", "annotate_column_parallel",
           "seq_sharded_on",
           "seq_layout_engages", "seq_sharded", "seq_whole", "MP_AXIS"]

MP_AXIS = "mp"
SEP_AXIS = "sep"


def _mesh_has(axis: str) -> bool:
    m = get_mesh()
    return m is not None and axis in m.axis_names and m.shape[axis] > 1


def _row_output(out: Tensor) -> Tensor:
    """A row-parallel (or vocab-parallel) product under the ambient mesh:
    sequence-sharded where that layout engages, replicated elsewhere."""
    held = annotate_sequence_parallel(out)
    return mark_sharding(out, *([None] * out.ndim)) if held is out else held


class ColumnParallelLinear(nn.Layer):
    """Weight [in, out] sharded along out (columns) on the mp axis.
    gather_output=True adds a constraint forcing replicated output (GSPMD
    all-gathers); False leaves the activation sharded on its last dim."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr,
            default_initializer=I.XavierNormal())
        self.weight._sharding_spec = P(None, MP_AXIS)
        if has_bias:
            self.bias = self.create_parameter([out_features], is_bias=True)
            self.bias._sharding_spec = P(MP_AXIS)
        else:
            self.bias = None

    def forward(self, x):
        out = F.linear(x, self.weight, self.bias)
        if _mesh_has(MP_AXIS):
            if self.gather_output:
                out = mark_sharding(out, *([None] * out.ndim))
            else:
                out = mark_sharding(out, *([None] * (out.ndim - 1) + [MP_AXIS]))
        return out


class RowParallelLinear(nn.Layer):
    """Weight [in, out] sharded along in (rows); input expected sharded on
    its last dim (input_is_parallel).  The output's partial sums leave as
    a reduce-scatter onto `[B, S/mp, H]` where the sequence layout engages
    (`seq_sharded_on`), as the fwd allreduce elsewhere."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr,
            default_initializer=I.XavierNormal())
        self.weight._sharding_spec = P(MP_AXIS, None)
        if has_bias:
            self.bias = self.create_parameter([out_features], is_bias=True)
            self.bias._sharding_spec = P()  # replicated (added post-reduce)
        else:
            self.bias = None

    def forward(self, x):
        if _mesh_has(MP_AXIS) and not self.input_is_parallel:
            x = mark_sharding(x, *([None] * (x.ndim - 1) + [MP_AXIS]))
        out = F.linear(x, self.weight, self.bias)
        if _mesh_has(MP_AXIS):
            out = _row_output(out)
        return out


class VocabParallelEmbedding(nn.Layer):
    """Embedding table sharded along vocab (dim 0) on mp (ref: range mask +
    allreduce in mp_layers.py; GSPMD derives the same from a gather on a
    sharded-operand)."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=I.Normal(0.0, 0.02))
        self.weight._sharding_spec = P(MP_AXIS, None)

    def forward(self, x):
        out = F.embedding(x, self.weight)
        if _mesh_has(MP_AXIS):
            out = _row_output(out)
        return out


class ParallelCrossEntropy(nn.Layer):
    """Vocab-sharded softmax cross-entropy (ref:
    c_softmax_with_cross_entropy_op.cu — the TP-CE that never materializes
    replicated logits). Keeping the logits' vocab dim sharded through
    logsumexp lets GSPMD reduce over the mp axis in f32."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, logits, label):
        from ..core.dispatch import apply
        lab = label._data if isinstance(label, Tensor) else jnp.asarray(label)
        mp_on = _mesh_has(MP_AXIS)
        mesh = get_mesh()

        def impl(lg):
            lg32 = lg.astype(jnp.float32)
            if mp_on:
                lg32 = jax.lax.with_sharding_constraint(
                    lg32, NamedSharding(mesh, P(*([None] * (lg.ndim - 1)
                                                  + [MP_AXIS]))))
            lse = jax.scipy.special.logsumexp(lg32, axis=-1)
            lab2 = lab[..., 0] if lab.ndim == lg.ndim else lab
            picked = jnp.take_along_axis(
                lg32, lab2[..., None].astype(jnp.int32), axis=-1)[..., 0]
            loss = lse - picked
            mask = lab2 != self.ignore_index
            return jnp.where(mask, loss, jnp.zeros((), loss.dtype))[..., None]
        return apply("parallel_cross_entropy", impl, [logits])


import threading as _threading

_sp_state = _threading.local()


class suppress_sequence_parallel_annotations:
    """Trace-time switch: inside the timetable pipeline executor
    (distributed.pp_exec), per-block seq-dim resharding hints sit inside
    lax.switch branches, where the reshard can lower to a full-mesh
    collective-permute — a collective only some devices reach, i.e. a
    deadlock (the branch-collective rule). The executor suppresses the
    hints during its trace; GSPMD sharding propagation covers the region
    instead. Thread-local so concurrent traces don't leak suppression."""

    def __enter__(self):
        self._prev = getattr(_sp_state, "off", False)
        _sp_state.off = True
        return self

    def __exit__(self, *exc):
        _sp_state.off = self._prev
        return False


def _step_mesh():
    """The mesh the code being traced runs on: the one the trainer names
    for its step (`ops.on_mesh.kernel_mesh`: its jitted step is traced
    outside any mesh_context), else the ambient one."""
    from ..ops.on_mesh import step_mesh
    return step_mesh() or get_mesh()


def seq_sharded_on(mesh, seq_len: int) -> bool:
    """Whether a `[B, S, H]` activation of `seq_len` rows is held
    `[B, S/mp, H]` a chip between a row-parallel product and the next
    column-parallel one on `mesh`: `mp` > 1 divides the length, no `sep`
    axis already splits it (that composition runs in no cell and keeps
    the layout it had), and the trace is not the timetable pipeline
    executor's (`suppress_sequence_parallel_annotations`).  Chosen from
    what can be observed; no switch selects it."""
    if mesh is None or getattr(_sp_state, "off", False):
        return False
    size = dict(mesh.shape)
    mp = size.get(MP_AXIS, 1)
    return mp > 1 and size.get(SEP_AXIS, 1) == 1 and seq_len % mp == 0


def seq_layout_engages(x) -> bool:
    """`seq_sharded_on` for the mesh being traced on and the `[B, S, ...]`
    array or Tensor `x`."""
    return x.ndim >= 3 and seq_sharded_on(_step_mesh(), x.shape[1])


def _held(a, seq, last):
    """Raw `a` [B, S, ..., H] constrained to `seq` on dim 1 and `last` on
    its last dim where the layout engages for its S; the batch dim is
    left to propagation (the data axes are the caller's)."""
    mesh = _step_mesh()
    if a.ndim < 3 or not seq_sharded_on(mesh, a.shape[1]):
        return a
    spec = P(P.UNCONSTRAINED, seq, *([None] * (a.ndim - 3)), last)
    # inside a region that is manual over other axes (the compiled
    # pipeline's `shard_map` over pp) the constraint is on ITS mesh
    inner = jax.sharding.get_abstract_mesh()
    return jax.lax.with_sharding_constraint(a, NamedSharding(
        inner if inner.manual_axes else mesh, spec))


def seq_sharded(a):
    """Raw `[B, S, H]` held `[B, S/mp, H]` a chip (`seq_sharded_on`), the
    identity elsewhere.  On a row-parallel product it is what turns the
    matmul's partial sums into a reduce-scatter; on a norm's output, what
    keeps the gather behind the norm."""
    return _held(a, MP_AXIS, None)


def seq_whole(a):
    """Raw `[B, S, N]` of a column-parallel product held `[B, S, N/mp]` —
    every row, its own columns — where `seq_sharded` engages for the same
    S: it says which operand of the product the partitioner gathers (the
    activation's rows, not the weight's columns)."""
    return _held(a, None, MP_AXIS)


def _on_tensor(constrain, x: Tensor) -> Tensor:
    """`constrain` (`seq_sharded` / `seq_whole`) on a Tensor, `x` itself
    where the layout does not engage."""
    if not seq_layout_engages(x):
        return x
    from ..core.dispatch import apply
    return apply("sharding_constraint", constrain, [x])


def annotate_sequence_parallel(x: Tensor) -> Tensor:
    """Megatron-SP parity (ref: sequence_parallel_utils.py ScatterOp/
    GatherOp): `seq_sharded` on a Tensor — the sequence dim (dim 1 of
    [B,S,H]) sharded on the mp axis between blocks where the layout
    engages, `x` itself elsewhere.  One annotation replaces the
    allreduce→rs/ag rewrite."""
    return _on_tensor(seq_sharded, x)


def annotate_column_parallel(x: Tensor) -> Tensor:
    """`seq_whole` on a Tensor: a column-parallel product, or a
    row-parallel product's input, held to every row and this chip's
    columns where the sequence layout engages."""
    return _on_tensor(seq_whole, x)
