"""Timetable-driven pipeline EXECUTOR: runs pp_schedule.Schedule
(FThenB / 1F1B / ZBH1) as one compiled SPMD program.

Reference parity: python/paddle/distributed/fleet/meta_parallel/
pipeline_parallel.py (1F1B runtime) + distributed/passes/
pipeline_scheduler_pass.py (ZBH1) — SURVEY §2.3 P6. The reference drives
these orders with an actor runtime and NCCL p2p; here the SAME validated
timetable (distributed/pp_schedule.py) is baked into a `lax.scan` over
ticks inside a `shard_map` over the `pp` mesh axis:

  - tick t, stage s executes exactly timeline[s][t]: F (forward one
    microbatch), B (backward-dgrad; at the last stage this also runs the
    loss head and seeds the cotangent), or W (deferred weight-grad — the
    ZBH1 split).
  - activations hop downstream and cotangents upstream via lax.ppermute,
    one message per tick, matching the schedule's 1-tick p2p latency
    model.
  - each stage keeps stage-INPUTS only (remat: B/W recompute the stage
    forward), in a ring buffer whose size is the schedule's peak-liveness
    bound (~n_stages) — NOT the microbatch count. This is 1F1B's memory
    point: GPipe's compiled autodiff stores M stage-inputs per stage, the
    executor stores ≤ bound(s) ≤ S+1.

Because forward and backward INTERLEAVE inside one program, outer
autodiff cannot drive it; `scheduled_pipeline_loss` therefore computes
all gradients in its (custom_vjp) forward pass and replays them, scaled,
in the backward rule — embedding and anything upstream of the pipeline
still differentiate normally through the returned d_microbatches.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .pipeline import PP_AXIS, _pp_shard_map
from .pp_schedule import Schedule

__all__ = ["scheduled_pipeline_loss", "schedule_buffer_bounds"]

_PHASES = {"F": 1, "B": 2, "W": 3}  # 0 = bubble


def _tables(schedule: Schedule):
    """timeline -> (phase[S,T], mb[S,T], chunk[S,T]) int32 numpy tables."""
    S, T = schedule.n_stages, schedule.n_ticks
    phase = np.zeros((S, T), np.int32)
    mb = np.zeros((S, T), np.int32)
    chunk = np.zeros((S, T), np.int32)
    for s, row in enumerate(schedule.timeline):
        for t, op in enumerate(row):
            if op is not None:
                phase[s, t] = _PHASES[op.phase]
                mb[s, t] = op.mb
                chunk[s, t] = op.chunk
    return phase, mb, chunk


def _stage_intervals(schedule: Schedule):
    """Per-(stage, chunk) liveness intervals derived from the timetable —
    the ONE source both the buffer sizing and the slot-collision guard
    use. Virtual stage v = chunk*S + stage (Megatron ordering); v's F
    input arrives from vstage v-1 (device (s-1) mod S, wrapping chunk),
    its cotangent from vstage v+1. Yields
    (stage, chunk, {"in_buf": [(mb, start, end)], "cot_buf": ...,
    "w_buf": ...})."""
    S, M, C = schedule.n_stages, schedule.n_microbatches, schedule.n_chunks
    V = S * C
    fin: Dict[Tuple[str, int, int], int] = {}
    start: Dict[Tuple[str, int, int], int] = {}
    for s, row in enumerate(schedule.timeline):
        for t, op in enumerate(row):
            if op is not None:
                v = op.chunk * S + s
                fin[(op.phase, v, op.mb)] = t + 1
                start[(op.phase, v, op.mb)] = t
    for s in range(S):
        for c in range(C):
            v = c * S + s
            iv = {"in_buf": [], "cot_buf": [], "w_buf": []}
            for m in range(M):
                arr = fin[("F", v - 1, m)] if v > 0 \
                    else start[("F", v, m)]
                iv["in_buf"].append((m, arr, fin[("B", v, m)]))
                if v < V - 1:
                    iv["cot_buf"].append((m, fin[("B", v + 1, m)],
                                          fin[("B", v, m)]))
                if schedule.split_w:
                    iv["w_buf"].append((m, fin[("B", v, m)],
                                        fin[("W", v, m)]))
            yield s, c, iv


def schedule_buffer_bounds(schedule: Schedule) -> Dict[str, int]:
    """Peak liveness the executor must buffer, derived from the timetable:

    in_buf  — stage inputs: live from the producing stage's F (arrival)
              until this stage's B consumes them;
    cot_buf — cotangents: from downstream B until this stage's B;
    w_buf   — (ZBH1) retained (input, cotangent) pairs from B until W.

    For 1F1B these are O(n_stages); for FThenB in_buf is O(M) — the
    executor allocates what the schedule needs, so the memory claim is
    checkable per schedule. Buffers are PER DEVICE: max over stages.
    """
    def peak(intervals):
        events = []
        for _, a, b in intervals:
            events.append((a, 1))
            events.append((b, -1))
        live = best = 0
        for _, d in sorted(events, key=lambda e: (e[0], -e[1])):
            live += d
            best = max(best, live)
        return best
    out = {"in_buf": 0, "cot_buf": 1, "w_buf": 0}
    for _, _, iv in _stage_intervals(schedule):
        for name in out:
            out[name] = max(out[name], peak(iv[name]))
    if not schedule.split_w:
        out["w_buf"] = 0
    return out


def _check_slots(schedule: Schedule, K: int, KC: int, KW: int) -> None:
    """Simulate ring-buffer occupancy against the timetable: writing slot
    m % K while a DIFFERENT live microbatch occupies it is a hard error
    (would corrupt an activation). Guards the contiguous-window assumption
    the modulo slotting relies on."""
    def check(intervals, nslots, name, stage, chunk):
        occupied: Dict[int, Tuple[int, int]] = {}
        for m, a, b in sorted(intervals, key=lambda iv: iv[1]):
            slot = m % nslots
            if slot in occupied:
                m0, b0 = occupied[slot]
                if a < b0 and m0 != m:
                    raise AssertionError(
                        f"{name} slot collision at stage {stage} chunk "
                        f"{chunk}: mb {m} overwrites live mb {m0} "
                        f"(slots={nslots})")
            occupied[slot] = (m, b)
    sizes = {"in_buf": K, "cot_buf": KC, "w_buf": KW}
    for s, c, iv in _stage_intervals(schedule):
        for name, nslots in sizes.items():
            if name == "w_buf" and not schedule.split_w:
                continue
            check(iv[name], nslots, name, s, c)


def scheduled_pipeline_loss(schedule: Schedule, stage_fn: Callable,
                            head_fn: Callable, mesh: Mesh,
                            stacked_params: Dict[str, Any], head_params,
                            microbatches, labels, extra_args=(),
                            mb_auto_spec: Any = None):
    """Execute `schedule` over the pp axis of `mesh`; returns the SUMMED
    loss (caller normalizes). Differentiable in (stacked_params,
    head_params, microbatches).

    stage_fn(local_params, x, *extra) -> y          (one stage's layers)
    head_fn(head_params, y, labels_mb) -> scalar    (last-stage loss head,
                                                     SUM over tokens)
    stacked_params: {name: [S, L/S, ...]}, dim 0 on pp.
    microbatches: [M, mb, ...] stage-0 inputs (already embedded).
    labels: [M, mb, ...] int labels per microbatch.
    mb_auto_spec: optional PartitionSpec giving ONE microbatch's sharding
      over the AUTO (non-pp) mesh axes, e.g. P(("dp","sharding"), "sep",
      None) for [mb, S, H]. Required when microbatches arrive sharded on
      an auto axis like `sep`: the lax.switch branches each produce
      mb-shaped values (real activations vs. fresh zeros) whose inferred
      shardings differ, and the SPMD partitioner cannot unify branch
      outputs under partial-manual sharding (CHECK at
      spmd_partitioner_util.cc:495). Pinning every mb-shaped value to one
      explicit sharding keeps the branches consistent.
    """
    S = mesh.shape[PP_AXIS]
    M = schedule.n_microbatches
    C = schedule.n_chunks
    if schedule.n_stages != S:
        raise ValueError(f"schedule has {schedule.n_stages} stages, "
                         f"mesh pp={S}")
    if C > 1 and schedule.split_w:
        raise ValueError("chunked (VPP) timetables with split wgrad are "
                         "not supported (upstream VPP is F/B only)")
    if C > 1:
        # interleaved layout contract: {name: [S, C, L/(S*C), ...]}
        for k, v in stacked_params.items():
            if v.ndim < 2 or v.shape[1] != C:
                raise ValueError(
                    f"VPP executor expects stacked_params[{k!r}] with "
                    f"chunk dim {C} at axis 1 (got shape {v.shape}); "
                    f"stack with stack_layer_params_interleaved")
    if S == 1:
        raise ValueError("pp=1 needs no schedule; use spmd_pipeline")

    phase_np, mb_np, chunk_np = _tables(schedule)
    bounds = schedule_buffer_bounds(schedule)
    K = bounds["in_buf"] + 1          # +1: write-before-read margin
    KC = bounds["cot_buf"] + 1
    KW = (bounds["w_buf"] + 1) if schedule.split_w else 1
    _check_slots(schedule, K, KC, KW)
    T = schedule.n_ticks
    phase_tab = jnp.asarray(phase_np)
    mb_tab = jnp.asarray(mb_np)
    chunk_tab = jnp.asarray(chunk_np)
    down = [(i, (i + 1) % S) for i in range(S)]
    up = [((i + 1) % S, i) for i in range(S)]

    cdt = microbatches.dtype
    mb_shape = microbatches.shape[1:]

    def _f32_psum(x):
        return jax.lax.psum(x.astype(jnp.float32), PP_AXIS).astype(x.dtype)

    # with_sharding_constraint inside the pp-manual shard_map needs the
    # pp axis TYPED Manual on the sharding's mesh (vma axes must be
    # Manual); the auto axes keep their Auto type.
    if mb_auto_spec is not None:
        from jax.sharding import AxisType, NamedSharding
        _mesh_mpp = Mesh(
            mesh.devices, mesh.axis_names,
            axis_types=tuple(AxisType.Manual if n == PP_AXIS
                             else AxisType.Auto for n in mesh.axis_names))
        _mb_shd = NamedSharding(_mesh_mpp, mb_auto_spec)

        def _pin(v):
            """Pin an mb-shaped value to the caller's auto-axes sharding."""
            return jax.lax.with_sharding_constraint(v, _mb_shd)

        def _pin_buf(v):
            """Same, for buffers with extra leading (slot/chunk) dims."""
            lead = v.ndim - len(mb_shape)
            shd = NamedSharding(
                _mesh_mpp, P(*([None] * lead), *tuple(mb_auto_spec)))
            return jax.lax.with_sharding_constraint(v, shd)
    else:
        _pin = _pin_buf = lambda v: v

    # COMPOSITION LIMIT (measured, round 3): a NON-batch microbatch dim
    # sharded on an auto axis (seq on `sep`) cannot enter this executor.
    # Attention inside the lax.switch branches then needs seq
    # all-gathers, which XLA lowers to collective-permutes whose CPU
    # rendezvous wants every local device — devices in other branches
    # never arrive (runtime deadlock), and some variants die earlier in
    # the SPMD partitioner (CHECK spmd_partitioner_util.cc:495). Callers
    # must gather such axes at the boundary (trainer/pretrain.py does);
    # in-executor sequence parallelism rides the mp axis (Megatron SP),
    # and ring/Ulysses context parallelism composes with the COMPILED
    # pipeline path instead.
    if mb_auto_spec is not None:
        for _d, _entry in enumerate(tuple(mb_auto_spec)):
            if _d == 0 or _entry is None:
                continue
            for _ax in (_entry if isinstance(_entry, tuple) else (_entry,)):
                if mesh.shape.get(_ax, 1) > 1:
                    raise ValueError(
                        f"mb_auto_spec {mb_auto_spec} shards non-batch "
                        f"dim {_d} on axis {_ax!r}: unsupported inside "
                        f"the timetable executor (in-branch seq "
                        f"collectives deadlock); gather it at the "
                        f"boundary first")

    def per_device(params, head_p, mbs, labels_, *extra):
        # local slice: [L/S, ...] for C==1, [C, L/(S*C), ...] for VPP
        local = {k: v[0] for k, v in params.items()}
        stage = jax.lax.axis_index(PP_AXIS)
        zero_mb = jnp.zeros(mb_shape, cdt)

        def stage_f(p, x):
            return stage_fn(p, x, *extra)

        def chunk_params(ch):
            """The chunk's layer-parameter slice (identity for C==1)."""
            if C == 1:
                return local
            return {k: jax.lax.dynamic_index_in_dim(v_, ch, 0,
                                                    keepdims=False)
                    for k, v_ in local.items()}

        def pv(a):
            """pvary, idempotent: no-op when already device-varying."""
            return a if PP_AXIS in jax.typeof(a).vma \
                else jax.lax.pcast(a, PP_AXIS, to="varying")
        # CRITICAL: vjp w.r.t. a pp-INVARIANT value makes shard_map insert
        # a psum_invariant collective to re-invariant the cotangent — and
        # a collective inside one lax.switch branch deadlocks devices that
        # took other branches. Mark the replicated head params varying
        # BEFORE any vjp; grads are psum'd once at the end instead.
        head_v = jax.tree.map(pv, head_p)
        # message tuples: (payload, mb, receiver_chunk, valid)
        zmsg = (pv(jnp.zeros((), jnp.int32)), pv(jnp.zeros((), jnp.int32)),
                pv(jnp.zeros((), jnp.bool_)))
        carry0 = dict(
            in_buf=_pin_buf(pv(jnp.zeros((C, K) + mb_shape, cdt))),
            cot_buf=_pin_buf(pv(jnp.zeros((C, KC) + mb_shape, cdt))),
            wx_buf=_pin_buf(pv(jnp.zeros((C, KW) + mb_shape, cdt))),
            wg_buf=_pin_buf(pv(jnp.zeros((C, KW) + mb_shape, cdt))),
            dmbs=_pin_buf(pv(jnp.zeros((M,) + mb_shape, cdt))),
            accp=jax.tree.map(
                lambda v: pv(jnp.zeros(v.shape, jnp.float32)), local),
            acch=jax.tree.map(
                lambda v: pv(jnp.zeros(v.shape, jnp.float32)), head_p),
            loss=pv(jnp.zeros((), jnp.float32)),
            fmsg=(_pin(pv(zero_mb)),) + zmsg,
            bmsg=(_pin(pv(zero_mb)),) + zmsg,
        )

        def tick(carry, t):
            c = dict(carry)
            # 1) deliver last tick's messages (1-tick p2p latency).
            # Sender-side validity decides delivery: the flag rides the
            # same ppermute, so it arrives exactly at the receiver.
            fy, fm, frc, fv = c["fmsg"]
            frc = jnp.clip(frc, 0, C - 1)
            c["in_buf"] = _pin_buf(c["in_buf"].at[frc, fm % K].set(
                jnp.where(fv, fy, c["in_buf"][frc, fm % K])))
            by, bm, brc, bv = c["bmsg"]
            brc = jnp.clip(brc, 0, C - 1)
            c["cot_buf"] = _pin_buf(c["cot_buf"].at[brc, bm % KC].set(
                jnp.where(bv, by, c["cot_buf"][brc, bm % KC])))

            ph = phase_tab[stage, t]
            m = mb_tab[stage, t]
            ch = chunk_tab[stage, t]
            vstage = ch * S + stage
            v_first = vstage == 0           # feeds from mbs, writes dmbs
            v_last = vstage == S * C - 1    # runs the loss head
            # hoist every gather of a (possibly auto-sharded) global
            # buffer OUT of the switch: gathers/reshards of sep-sharded
            # operands inside a branch either trip the SPMD partitioner
            # CHECK or deadlock at the resharding collective (devices in
            # other branches never arrive)
            mbs_m = _pin(mbs[m])
            labels_m = labels_[m]
            local_c = chunk_params(ch)
            no_f = (_pin(pv(zero_mb)),) + zmsg
            no_b = (_pin(pv(zero_mb)),) + zmsg

            def do_idle(c):
                return c, no_f, no_b

            # NOTE: no _pin inside the branches below — a sharding
            # constraint can lower to a collective(-permute), and a
            # collective inside one switch branch deadlocks the devices
            # that took other branches (same rule as the pvary note
            # above). All pins live outside the switch.
            def do_f(c):
                x = jnp.where(v_first, mbs_m, c["in_buf"][ch, m % K])
                c = dict(c)
                c["in_buf"] = c["in_buf"].at[ch, m % K].set(x)
                y = stage_f(local_c, x)
                # receiver = virtual stage vstage+1, on device
                # (stage+1) % S — chunk increments on the S-1 -> 0 hop
                rc = ch + jnp.where(stage == S - 1, 1, 0)
                fmsg = (y, m, rc, vstage < S * C - 1)
                return c, fmsg, no_b

            def do_b(c):
                x = c["in_buf"][ch, m % K]
                last = v_last
                # ONE stage forward, residuals shared with the backward
                # (ZBH1 keeps the x-only vjp so W can be deferred)
                if schedule.split_w:
                    y, vjp_x = jax.vjp(lambda xx: stage_f(local_c, xx), x)
                else:
                    y, vjp_px = jax.vjp(stage_f, local_c, x)
                # the loss head runs ONLY on the last stage (lax.cond is
                # safe here: with head_v pre-pvary'd no branch contains a
                # collective); elsewhere the cotangent arrived upstream

                def head_branch():
                    loss, vjp = jax.vjp(
                        lambda hp_, y_: head_fn(hp_, y_, labels_m),
                        head_v, y)
                    dhp, dy_ = vjp(pv(jnp.ones((), loss.dtype)))
                    return loss.astype(jnp.float32), dy_, dhp

                def skip_branch():
                    return (pv(jnp.zeros((), jnp.float32)),
                            pv(jnp.zeros_like(y)),
                            jax.tree.map(lambda h: pv(jnp.zeros_like(h)),
                                         head_v))
                loss_l, dy_l, dhp_l = jax.lax.cond(last, head_branch,
                                                   skip_branch)
                dy = jnp.where(last, dy_l, c["cot_buf"][ch, m % KC])
                c = dict(c)
                c["loss"] = c["loss"] + loss_l

                def acc_params(acc, dp):
                    """Accumulate the chunk's param grads (full-slice add
                    for C==1, chunk-row scatter-add for VPP)."""
                    if C == 1:
                        return jax.tree.map(
                            lambda a, g: a + g.astype(jnp.float32),
                            acc, dp)
                    return jax.tree.map(
                        lambda a, g: a.at[ch].set(
                            a[ch] + g.astype(jnp.float32)), acc, dp)

                if schedule.split_w:
                    # ZBH1: dgrad now (critical path), wgrad deferred
                    (dx,) = vjp_x(dy)
                    c["wx_buf"] = c["wx_buf"].at[ch, m % KW].set(x)
                    c["wg_buf"] = c["wg_buf"].at[ch, m % KW].set(dy)
                else:
                    dp, dx = vjp_px(dy)
                    c["accp"] = acc_params(c["accp"], dp)
                c["acch"] = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32),
                    c["acch"], dhp_l)
                c["dmbs"] = jax.lax.dynamic_update_index_in_dim(
                    c["dmbs"],
                    jnp.where(v_first, dx, c["dmbs"][m]), m, 0)
                # receiver = vstage-1 on device (stage-1) % S — chunk
                # decrements on the 0 -> S-1 hop
                rc = ch - jnp.where(stage == 0, 1, 0)
                bmsg = (dx, m, rc, vstage > 0)
                return c, no_f, bmsg

            def do_w(c):
                x = c["wx_buf"][ch, m % KW]
                dy = c["wg_buf"][ch, m % KW]
                _, vjp_p = jax.vjp(lambda p: stage_f(p, x), local_c)
                (dp,) = vjp_p(dy)
                c = dict(c)
                if C == 1:
                    c["accp"] = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32),
                        c["accp"], dp)
                else:
                    c["accp"] = jax.tree.map(
                        lambda a, g: a.at[ch].set(
                            a[ch] + g.astype(jnp.float32)),
                        c["accp"], dp)
                return c, no_f, no_b

            c, fmsg, bmsg = jax.lax.switch(
                ph, [do_idle, do_f, do_b, do_w], c)
            # 3) rotate messages
            c["fmsg"] = tuple(
                (_pin if i == 0 else (lambda z: z))(
                    jax.lax.ppermute(v_, PP_AXIS, down))
                for i, v_ in enumerate(fmsg))
            c["bmsg"] = tuple(
                (_pin if i == 0 else (lambda z: z))(
                    jax.lax.ppermute(v_, PP_AXIS, up))
                for i, v_ in enumerate(bmsg))
            return c, None

        c, _ = jax.lax.scan(tick, carry0, jnp.arange(T))
        loss = jax.lax.psum(c["loss"], PP_AXIS)
        dmbs = _f32_psum(c["dmbs"])
        acch = jax.tree.map(lambda a: jax.lax.psum(a, PP_AXIS), c["acch"])
        accp = jax.tree.map(lambda a: a[None], c["accp"])  # [1, L/S, ...]
        return loss, accp, acch, dmbs

    param_specs = {k: P(PP_AXIS, *([None] * (v.ndim - 1)))
                   for k, v in stacked_params.items()}
    head_specs = jax.tree.map(lambda v: P(*([None] * jnp.ndim(v))),
                              head_params)
    mb_spec = P(*([None] * microbatches.ndim))
    lab_spec = P(*([None] * labels.ndim))
    extra_specs = tuple(P(*([None] * jnp.ndim(e))) for e in extra_args)

    fn = _pp_shard_map(
        per_device, mesh,
        in_specs=(param_specs, head_specs, mb_spec, lab_spec)
        + extra_specs,
        out_specs=(P(), param_specs, head_specs, mb_spec))

    pdt = {k: v.dtype for k, v in stacked_params.items()}
    hdt = jax.tree.map(lambda v: v.dtype, head_params)

    @jax.custom_vjp
    def run(sp, hp, mbs):
        loss, _, _, _ = jax.jit(fn)(sp, hp, mbs, labels, *extra_args)
        return loss

    def run_fwd(sp, hp, mbs):
        loss, accp, acch, dmbs = jax.jit(fn)(sp, hp, mbs, labels,
                                             *extra_args)
        accp = {k: v.astype(pdt[k]) for k, v in accp.items()}
        acch = jax.tree.map(lambda v, d: v.astype(d), acch, hdt)
        return loss, (accp, acch, dmbs)

    def run_bwd(res, g):
        accp, acch, dmbs = res
        scale = lambda v: (g * v.astype(jnp.float32)).astype(v.dtype)
        return (jax.tree.map(scale, accp), jax.tree.map(scale, acch),
                scale(dmbs))

    run.defvjp(run_fwd, run_bwd)
    from .parallel_layers import suppress_sequence_parallel_annotations
    with suppress_sequence_parallel_annotations():
        return run(stacked_params, head_params, microbatches)
