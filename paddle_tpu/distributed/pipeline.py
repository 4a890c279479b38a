"""Pipeline parallelism, compiled (ref: python/paddle/distributed/fleet/
meta_parallel/pipeline_parallel.py + pp_utils/p2p_communication.py +
fleet_executor actors — SURVEY §2.3 P6, §7.2.1).

TPU-native rework: NO actor runtime, NO NCCL send/recv. The microbatch
schedule is COMPILED into one XLA program: a `shard_map` over the `pp` mesh
axis runs every stage in SPMD; activations rotate stage→stage+1 with
`lax.ppermute` once per tick; `lax.scan` drives the M+S-1 ticks. Autodiff
through the scan+ppermute yields the reverse schedule (backward pipeline)
automatically — the transpose of a ppermute is the reversed ppermute, so
gradient traffic flows stage s → s-1 exactly like the reference's backward
p2p. Remat (`jax.checkpoint`) on the stage body keeps the activation
footprint at GPipe levels; interleaved/1F1B-style memory scheduling is XLA's
latency-hiding scheduler's job once the program is expressed this way.

Layout contract: the decoder stack must be homogeneous; per-layer params are
stacked to a leading [num_layers, ...] dim, reshaped [S, L/S, ...], sharded
on `pp` dim 0. Embedding/head stay outside the pipelined region (they belong
to first/last stage conceptually; XLA places their compute with dp/mp
sharding, and the boundary transfers are two ppermutes' worth of traffic).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["spmd_pipeline", "stack_layer_params", "PP_AXIS"]

PP_AXIS = "pp"


def _pvary(x, axis):
    return jax.lax.pcast(x, axis, to="varying")


def _pp_shard_map(f, mesh, in_specs, out_specs):
    """shard_map manual ONLY over the pp axis; dp/mp/sharding/sep stay
    'auto' so GSPMD keeps tensor/data parallelism inside each stage body."""
    # check_vma=True is load-bearing: jax 0.9's eager partial-manual path
    # (_unmatch) mis-builds an all-axes dst spec when check_vma=False
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=frozenset({PP_AXIS}), check_vma=True)


@jax.custom_vjp
def _pvary_safe(x):
    """pvary whose TRANSPOSE we own: AD's transpose of pvary is a
    psum_invariant on the cotangent, and a sub-f32 psum crashes XLA CPU
    under partial-manual sharding ("Invalid binary instruction opcode
    copy"). Routing the transpose through an f32 psum keeps the stage
    compute (and the carried activations) genuinely bf16 on every
    backend — this replaces the old whole-region _cpu_f32_upcast for
    the compiled pipeline paths."""
    return _pvary(x, PP_AXIS)


def _pvary_safe_fwd(x):
    return _pvary(x, PP_AXIS), None


def _pvary_safe_bwd(_, g):
    if jnp.issubdtype(g.dtype, jnp.floating) \
            and jnp.dtype(g.dtype).itemsize < 4:
        return (jax.lax.psum(g.astype(jnp.float32),
                             PP_AXIS).astype(g.dtype),)
    return (jax.lax.psum(g, PP_AXIS),)


_pvary_safe.defvjp(_pvary_safe_fwd, _pvary_safe_bwd)


def _gather_last_stage(out_buf, stage, S):
    """Broadcast the last stage's output buffer to every pp rank (zeros
    elsewhere). psum in f32: sub-f32 psum crashes XLA CPU under
    partial-manual sharding, and f32 is the safe accumulation dtype."""
    masked = jnp.where(stage == S - 1, out_buf, jnp.zeros_like(out_buf))
    return jax.lax.psum(masked.astype(jnp.float32),
                        PP_AXIS).astype(out_buf.dtype)


def stack_layer_params(per_layer_states: List[Dict[str, Any]], n_stages: int):
    """[{name: array} × L] → {name: [S, L/S, ...] array} (stage-stacked)."""
    L = len(per_layer_states)
    if L % n_stages != 0:
        raise ValueError(f"{L} layers not divisible into {n_stages} stages")
    per_stage = L // n_stages
    out = {}
    for k in per_layer_states[0]:
        stacked = jnp.stack([s[k] for s in per_layer_states], axis=0)
        out[k] = stacked.reshape((n_stages, per_stage) + stacked.shape[1:])
    return out


def spmd_pipeline(stage_fn: Callable, stacked_params: Dict[str, Any],
                  microbatches, mesh: Mesh, n_microbatches: int,
                  extra_args=(), remat: bool = True):
    """Run the pipelined stack.

    stage_fn(layer_params_slice, x, *extra_args) -> x
      applies ONE stage's [L/S, ...] params to activation x (typically an
      inner lax.scan over the L/S layers).
    stacked_params: {name: [S, L/S, ...]} — dim 0 sharded on pp.
    microbatches: [M, mb_batch, ...] activations entering stage 0
      (already embedded); returns [M, mb_batch, ...] outputs of last stage.
    """
    S = mesh.shape[PP_AXIS]
    M = n_microbatches
    if S == 1:
        return _no_pp_fallback(stage_fn, stacked_params, microbatches,
                               extra_args)

    body = stage_fn
    if remat:
        body = jax.checkpoint(stage_fn)

    perm = [(i, (i + 1) % S) for i in range(S)]

    param_specs = {k: P(PP_AXIS, *([None] * (v.ndim - 1)))
                   for k, v in stacked_params.items()}
    mb_spec = P(*([None] * microbatches.ndim))

    def per_device(params, mbs, *extra):
        # params: {name: [1, L/S, ...]} local stage slice
        params = {k: v[0] for k, v in params.items()}
        stage = jax.lax.axis_index(PP_AXIS)
        # _pvary_safe: mbs' cotangent re-invariants through OUR f32 psum
        # instead of an AD-inserted sub-f32 one (XLA-CPU crash)
        mbs = _pvary_safe(mbs)
        mb_shape = mbs.shape[1:]
        # pvary: the carry is device-varying over pp from tick 1 on (ppermute
        # output), so the initial carry must carry the same vma type
        state = _pvary_safe(jnp.zeros(mb_shape, mbs.dtype))
        out_buf = _pvary_safe(jnp.zeros((M,) + mb_shape, mbs.dtype))

        def tick(carry, t):
            state, out_buf = carry
            # stage 0 ingests microbatch t (while valid)
            feed = jnp.where(t < M, mbs[jnp.minimum(t, M - 1)],
                             jnp.zeros(mb_shape, mbs.dtype))
            x = jnp.where(stage == 0, feed, state)
            y = body(params, x, *extra)
            # last stage records its result for microbatch t-(S-1)
            idx = jnp.clip(t - (S - 1), 0, M - 1)
            take = jnp.logical_and(stage == S - 1, t >= S - 1)
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf,
                jnp.where(take, y, out_buf[idx]), idx, axis=0)
            # rotate activations to the next stage
            state = jax.lax.ppermute(y, PP_AXIS, perm)
            return (state, out_buf), None

        (state, out_buf), _ = jax.lax.scan(
            tick, (state, out_buf), jnp.arange(M + S - 1))
        return _gather_last_stage(out_buf, stage, S)

    extra_specs = tuple(P(*([None] * jnp.ndim(e))) for e in extra_args)
    fn = _pp_shard_map(
        per_device, mesh,
        in_specs=(param_specs, mb_spec) + extra_specs,
        out_specs=P(*([None] * microbatches.ndim)))
    # jit: eager shard_map can't evaluate the remat-wrapped scan body
    # (closed_call); a no-op when already inside an outer trace
    return jax.jit(fn)(stacked_params, microbatches, *extra_args)


def _no_pp_fallback(stage_fn, stacked_params, microbatches, extra_args):
    """pp=1: just scan the layers over each microbatch sequentially."""
    merged = {k: v.reshape((v.shape[0] * v.shape[1],) + v.shape[2:])
              for k, v in stacked_params.items()}

    def one_mb(x):
        return stage_fn(merged, x, *extra_args)

    M = microbatches.shape[0]
    if M <= 4:
        # unrolled: avoids the per-iteration while-loop host round-trip
        # (the microbatch count is static, so this is just M copies)
        outs = jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[one_mb(microbatches[i]) for i in range(M)])
    else:
        outs = jax.lax.map(one_mb, microbatches)
    return outs


# ---------------------------------------------------------------------------
# Interleaved VPP (ref: PipelineParallelWithInterleave, virtual_pp_degree —
# SURVEY §2.3 P6). Compiled formulation: V = S*v virtual stages laid out
# round-robin over S devices; every activation hops device→device once per
# tick via ppermute, carrying its virtual-stage counter. Device 0 injects
# fresh microbatches on a statically precomputed collision-free schedule
# (returning activations have priority), which is exactly what shrinks the
# bubble from (S-1)/(M+S-1) to ~(S-1)/(M*v+S-1): the drain of chunk column
# j overlaps the fill of column j+1. Zero-bubble (ZBH1) splitting of
# backward into dgrad/wgrad is owned by XLA's latency-hiding scheduler in
# this compiled formulation (documented in docs/PARITY.md).
# ---------------------------------------------------------------------------
def _vpp_injection_schedule(S: int, v: int, M: int):
    """Greedy static schedule: inject[t] = microbatch entering at tick t
    (-1 = none; returning activations occupy device 0 that tick)."""
    V = S * v
    entries = []
    busy = set()  # ticks when a returning activation reaches device 0
    t = 0
    for m in range(M):
        while t in busy:
            t += 1
        entries.append(t)
        for k in range(1, v):
            busy.add(t + k * S)
        t += 1
    total = entries[-1] + V
    inject = [-1] * total
    for m, e in enumerate(entries):
        inject[e] = m
    return inject, total


def spmd_pipeline_interleaved(stage_fn, stacked_params: Dict[str, Any],
                              microbatches, mesh: Mesh, n_microbatches: int,
                              v: int, extra_args=(), remat: bool = True):
    """Interleaved-VPP pipelined stack.

    stacked_params: {name: [S, v, L/(S*v), ...]} — dim 0 sharded on pp,
      dim 1 indexes the v chunk columns hosted by each device.
    stage_fn(layer_params_slice, x, *extra) applies one [L/(S*v), ...] chunk.
    """
    S = mesh.shape[PP_AXIS]
    M = n_microbatches
    chunk_dim = next(iter(stacked_params.values())).shape[1]
    if chunk_dim != v:
        raise ValueError(
            f"stacked_params chunk dim {chunk_dim} != v={v}; stack with "
            f"stack_layer_params_interleaved(layers, {S}, {v})")
    if S == 1:
        merged = {k: x.reshape((1, x.shape[1] * x.shape[2]) + x.shape[3:])
                  for k, x in stacked_params.items()}
        return _no_pp_fallback(stage_fn, merged, microbatches, extra_args)
    V = S * v

    body = jax.checkpoint(stage_fn) if remat else stage_fn
    inject, total = _vpp_injection_schedule(S, v, M)
    inject_t = jnp.asarray(inject, jnp.int32)
    perm = [(i, (i + 1) % S) for i in range(S)]

    param_specs = {k: P(PP_AXIS, *([None] * (x.ndim - 1)))
                   for k, x in stacked_params.items()}
    mb_spec = P(*([None] * microbatches.ndim))

    def per_device(params, mbs, *extra):
        params = {k: x[0] for k, x in params.items()}  # [v, L/V, ...]
        stage = jax.lax.axis_index(PP_AXIS)
        mbs = _pvary_safe(mbs)
        mb_shape = mbs.shape[1:]
        zero = jnp.zeros(mb_shape, mbs.dtype)
        state = _pvary_safe(zero)
        h0 = _pvary(jnp.zeros((), jnp.int32), PP_AXIS)
        m0 = _pvary(jnp.zeros((), jnp.int32), PP_AXIS)
        out_buf = _pvary_safe(jnp.zeros((M,) + mb_shape, mbs.dtype))

        def tick(carry, t):
            state, h, m, out_buf = carry
            inj = inject_t[t]
            fresh = jnp.logical_and(stage == 0, inj >= 0)
            x = jnp.where(fresh, mbs[jnp.maximum(inj, 0)], state)
            h = jnp.where(fresh, 0, h)
            m = jnp.where(fresh, jnp.maximum(inj, 0), m)
            chunk = jnp.clip(h // S, 0, v - 1)
            cp = {k: jax.lax.dynamic_index_in_dim(x_, chunk, 0,
                                                  keepdims=False)
                  for k, x_ in params.items()}
            # live = this device holds a real activation whose virtual
            # stage belongs to it this tick
            live = jnp.logical_and(h % S == stage, h < V)
            y = body(cp, x, *extra)
            y = jnp.where(live, y, x)
            done = jnp.logical_and(jnp.logical_and(stage == S - 1,
                                                   h == V - 1), live)
            idx = jnp.clip(m, 0, M - 1)
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(done, y, out_buf[idx]), idx, axis=0)
            state = jax.lax.ppermute(y, PP_AXIS, perm)
            h = jax.lax.ppermute(h + 1, PP_AXIS, perm)
            m = jax.lax.ppermute(m, PP_AXIS, perm)
            return (state, h, m, out_buf), None

        (state, h, m, out_buf), _ = jax.lax.scan(
            tick, (state, h0, m0, out_buf), jnp.arange(total))
        return _gather_last_stage(out_buf, stage, S)

    extra_specs = tuple(P(*([None] * jnp.ndim(e))) for e in extra_args)
    fn = _pp_shard_map(
        per_device, mesh,
        in_specs=(param_specs, mb_spec) + extra_specs,
        out_specs=P(*([None] * microbatches.ndim)))
    return jax.jit(fn)(stacked_params, microbatches, *extra_args)


def stack_layer_params_interleaved(per_layer_states: List[Dict[str, Any]],
                                   n_stages: int, v: int):
    """[{name: arr} × L] → {name: [S, v, L/(S*v), ...]} with the VPP
    round-robin layout: virtual stage j = chunk (j // S) on device (j % S),
    so device s hosts layers [s, s+S, s+2S, ...] grouped into v chunks —
    the reference's interleave assignment (pp_layers round robin)."""
    L = len(per_layer_states)
    V = n_stages * v
    if L % V != 0:
        raise ValueError(f"{L} layers not divisible into {V} virtual stages")
    per_chunk = L // V
    out = {}
    for k in per_layer_states[0]:
        stacked = jnp.stack([s[k] for s in per_layer_states], axis=0)
        # layer index l = (chunk*S + stage)*per_chunk + i
        stacked = stacked.reshape((v, n_stages, per_chunk)
                                  + stacked.shape[1:])
        out[k] = jnp.swapaxes(stacked, 0, 1)
    return out


__all__ += ["spmd_pipeline_interleaved", "stack_layer_params_interleaved"]
