"""Timetable EXECUTOR for 1F1B/ZBH1/FThenB (distributed/pp_exec.py) —
loss/grad parity vs plain sequential autodiff, plus the memory-bound
claims (ref: fleet/meta_parallel/pipeline_parallel.py 1F1B runtime,
pipeline_scheduler_pass.py ZBH1; VERDICT r1 item 2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed.mesh import build_hybrid_mesh
from paddle_tpu.distributed.pp_exec import (schedule_buffer_bounds,
                                            scheduled_pipeline_loss)
from paddle_tpu.distributed.pp_schedule import (fthenb_schedule,
                                                one_f_one_b_schedule,
                                                zbh1_schedule)

S, LS, H, C = 4, 2, 8, 5   # stages, layers/stage, width, classes
M, MB = 6, 3               # microbatches, microbatch size


def _setup(seed=0):
    rng = np.random.RandomState(seed)
    stacked = {
        "w": jnp.asarray(rng.standard_normal((S, LS, H, H)) * 0.3,
                         jnp.float32),
        "b": jnp.asarray(rng.standard_normal((S, LS, H)) * 0.1,
                         jnp.float32),
    }
    head = {"wout": jnp.asarray(rng.standard_normal((H, C)) * 0.3,
                                jnp.float32)}
    mbs = jnp.asarray(rng.standard_normal((M, MB, H)), jnp.float32)
    labels = jnp.asarray(rng.randint(0, C, (M, MB)), jnp.int32)
    return stacked, head, mbs, labels


def stage_fn(local, x):
    def body(h, lp):
        return jnp.tanh(h @ lp[0] + lp[1]), None
    h, _ = jax.lax.scan(body, x, (local["w"], local["b"]))
    return h


def head_fn(hp, y, lab):
    logits = y @ hp["wout"]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
    return (lse - picked).sum()


def ref_loss(stacked, head, mbs, labels):
    total = 0.0
    for m in range(M):
        x = mbs[m]
        for s in range(S):
            x = stage_fn({"w": stacked["w"][s], "b": stacked["b"][s]}, x)
        total = total + head_fn(head, x, labels[m])
    return total


@pytest.fixture(scope="module")
def mesh():
    return build_hybrid_mesh(pp_degree=S, devices=jax.devices()[:S])


SCHEDULES = {
    "1F1B": lambda: one_f_one_b_schedule(S, M),
    "ZBH1": lambda: zbh1_schedule(S, M),
    "FThenB": lambda: fthenb_schedule(S, M),
}


@pytest.mark.parametrize("name", list(SCHEDULES))
def test_executor_matches_sequential_autodiff(mesh, name):
    schedule = SCHEDULES[name]()
    schedule.validate()
    stacked, head, mbs, labels = _setup()

    ref_l, ref_g = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(
        stacked, head, mbs, labels)

    def run(sp, hp, xb):
        return scheduled_pipeline_loss(schedule, stage_fn, head_fn, mesh,
                                       sp, hp, xb, labels)
    got_l, got_g = jax.value_and_grad(run, argnums=(0, 1, 2))(
        stacked, head, mbs)

    np.testing.assert_allclose(float(got_l), float(ref_l),
                               rtol=1e-5, atol=1e-5)
    for rg, gg, part in zip(ref_g, got_g, ["stacked", "head", "mbs"]):
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=2e-4, atol=2e-4,
            err_msg=part), rg, gg)


def test_upstream_cotangent_scaling(mesh):
    """The custom_vjp must scale grads by the incoming cotangent (e.g.
    the 1/total_tokens of a mean loss applied OUTSIDE the pipeline)."""
    schedule = one_f_one_b_schedule(S, M)
    stacked, head, mbs, labels = _setup(1)

    def mean_run(sp):
        return scheduled_pipeline_loss(schedule, stage_fn, head_fn, mesh,
                                       sp, head, mbs, labels) / (M * MB)
    def mean_ref(sp):
        return ref_loss(sp, head, mbs, labels) / (M * MB)
    g_run = jax.grad(mean_run)(stacked)
    g_ref = jax.grad(mean_ref)(stacked)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5), g_run, g_ref)


class TestMemoryBounds:
    def test_1f1b_bounds_are_stage_depth_not_microbatches(self):
        """THE 1F1B claim: executor buffers scale with S, GPipe-order
        (FThenB) buffers scale with M."""
        M_big = 32
        b_1f1b = schedule_buffer_bounds(one_f_one_b_schedule(S, M_big))
        b_fthenb = schedule_buffer_bounds(fthenb_schedule(S, M_big))
        assert b_1f1b["in_buf"] <= S + 1
        assert b_fthenb["in_buf"] >= M_big - S
        # ZBH1 keeps the 1F1B activation class (the H1 memory contract)
        b_zb = schedule_buffer_bounds(zbh1_schedule(S, M_big))
        assert b_zb["in_buf"] <= S + 1
        assert b_zb["w_buf"] <= 2 * S

    def test_zbh1_fills_bubbles(self):
        s_1f1b = one_f_one_b_schedule(S, 8)
        s_zb = zbh1_schedule(S, 8)
        # same F/B work + extra W work in comparable ticks => lower idle
        assert s_zb.bubble_ratio() < s_1f1b.bubble_ratio()


def test_pretrain_step_1f1b_matches_compiled():
    """The flagship train step with pp_schedule='1F1B' (timetable
    executor) must match the compiled GPipe-scan path: same loss every
    step given identical init."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import llama_tiny_config
    from paddle_tpu.trainer.pretrain import (PretrainConfig,
                                             build_llama_pretrain_step,
                                             make_hybrid_mesh_for)

    def build(pp_schedule):
        paddle.seed(1234)
        mc = llama_tiny_config(num_hidden_layers=4,
                               max_position_embeddings=64)
        cfg = PretrainConfig(mc, global_batch=4, seq_len=32,
                             n_microbatches=4, dp=1, mp=2, pp=2,
                             sharding=1, sep=1, pp_schedule=pp_schedule)
        mesh = make_hybrid_mesh_for(cfg,
                                    devices=jax.devices()[:4])
        return mc, build_llama_pretrain_step(cfg, mesh)

    mc, (st_a, step_a, meta_a) = build("compiled")
    _, (st_b, step_b, meta_b) = build("1F1B")
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, mc.vocab_size, (4, 32)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, mc.vocab_size, (4, 32)),
                         jnp.int32)
    ids_a = jax.device_put(ids, meta_a["data_sharding"])
    lab_a = jax.device_put(labels, meta_a["data_sharding"])
    ids_b = jax.device_put(ids, meta_b["data_sharding"])
    lab_b = jax.device_put(labels, meta_b["data_sharding"])
    for i in range(2):
        st_a, ma = step_a(st_a, ids_a, lab_a)
        st_b, mb = step_b(st_b, ids_b, lab_b)
        la, lb = float(ma["loss"]), float(mb["loss"])
        np.testing.assert_allclose(lb, la, rtol=5e-4, err_msg=f"step {i}")


def test_pretrain_step_zbh1_runs():
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import llama_tiny_config
    from paddle_tpu.trainer.pretrain import (PretrainConfig,
                                             build_llama_pretrain_step,
                                             make_hybrid_mesh_for)
    paddle.seed(7)
    mc = llama_tiny_config(num_hidden_layers=4,
                           max_position_embeddings=64)
    cfg = PretrainConfig(mc, global_batch=4, seq_len=32,
                         n_microbatches=4, pp=2, mp=2,
                         pp_schedule="ZBH1")
    mesh = make_hybrid_mesh_for(cfg, devices=jax.devices()[:4])
    st, step, meta = build_llama_pretrain_step(cfg, mesh)
    rng = np.random.RandomState(0)
    ids = jax.device_put(jnp.asarray(
        rng.randint(0, mc.vocab_size, (4, 32)), jnp.int32),
        meta["data_sharding"])
    st, m = step(st, ids, ids)
    assert np.isfinite(float(m["loss"]))


# ---------------------------------------------------------------------------
# Chunked (interleaved VPP) timetable executor — VERDICT r2 item 2
# ---------------------------------------------------------------------------
def test_vpp_executor_matches_sequential_autodiff(mesh):
    """Interleaved schedule (n_chunks=2) through the chunked executor:
    loss + grads vs sequential autodiff over the vstage-ordered stack."""
    from paddle_tpu.distributed.pp_schedule import interleaved_1f1b_schedule
    CH = 2
    schedule = interleaved_1f1b_schedule(S, M, CH)
    schedule.validate()
    rng = np.random.RandomState(3)
    # [S, CH, 1, H, H]: vstage v = c*S + s applies stacked[:, c][s]
    stacked = {
        "w": jnp.asarray(rng.standard_normal((S, CH, 1, H, H)) * 0.3,
                         jnp.float32),
        "b": jnp.asarray(rng.standard_normal((S, CH, 1, H)) * 0.1,
                         jnp.float32),
    }
    head = {"wout": jnp.asarray(rng.standard_normal((H, C)) * 0.3,
                                jnp.float32)}
    mbs = jnp.asarray(rng.standard_normal((M, MB, H)), jnp.float32)
    labels = jnp.asarray(rng.randint(0, C, (M, MB)), jnp.int32)

    def ref(sp, hp, xb):
        total = 0.0
        for m in range(M):
            x = xb[m]
            for v in range(S * CH):
                s, c = v % S, v // S
                x = stage_fn({"w": sp["w"][s, c], "b": sp["b"][s, c]}, x)
            total = total + head_fn(hp, x, labels[m])
        return total

    ref_l, ref_g = jax.value_and_grad(ref, argnums=(0, 1, 2))(
        stacked, head, mbs)

    def run(sp, hp, xb):
        return scheduled_pipeline_loss(schedule, stage_fn, head_fn, mesh,
                                       sp, hp, xb, labels)
    got_l, got_g = jax.value_and_grad(run, argnums=(0, 1, 2))(
        stacked, head, mbs)
    np.testing.assert_allclose(float(got_l), float(ref_l),
                               rtol=1e-5, atol=1e-5)
    for rg, gg, part in zip(ref_g, got_g, ["stacked", "head", "mbs"]):
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=2e-4, atol=2e-4,
            err_msg=part), rg, gg)


def test_vpp_schedule_shrinks_warmup_bubble():
    from paddle_tpu.distributed.pp_schedule import interleaved_1f1b_schedule
    s1 = one_f_one_b_schedule(S, 8)
    s2 = interleaved_1f1b_schedule(S, 8, 2)
    assert s2.bubble_ratio() < s1.bubble_ratio()


#: the flagship step under a timetable executor against the same step
#: another way, (seed, what both sides share, side a, side b, rtol).  The
#: `sequence_parallel` keyword rides in both of its values: it selects
#: nothing (PR 65) — under the executor the sequence layout is
#: suppressed whatever it says (a reshard inside a `lax.switch` branch
#: can lower to a collective only some devices reach), in the compiled
#: pipeline `mp` 2 divides the 32 rows and it engages.
TIMETABLE_STEPS = {
    # pp_schedule='VPP' (chunked timetable executor) vs the compiled
    # interleaved pipeline
    "vpp timetable against the compiled interleave": (
        77, dict(sequence_parallel=False), dict(vpp=2),
        dict(pp_schedule="compiled"), dict(pp_schedule="VPP"), 5e-4),
    # 1F1B x mp x sep (VERDICT r2 item 2): the executor gathers the sep
    # sharding at its boundary (in-branch seq collectives deadlock — see
    # pp_exec composition note), so the loss must match the sep-less
    # run bit-for-bit-ish
    "1f1b with a sep axis against without": (
        55, dict(sequence_parallel=True), dict(pp_schedule="1F1B"),
        dict(sep=1), dict(sep=2), 1e-4)}


@pytest.mark.parametrize("case", sorted(TIMETABLE_STEPS))
def test_pretrain_step_under_a_timetable_matches(case):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import llama_tiny_config
    from paddle_tpu.trainer.pretrain import (PretrainConfig,
                                             build_llama_pretrain_step,
                                             make_hybrid_mesh_for)
    seed, keyword, shared, side_a, side_b, rtol = TIMETABLE_STEPS[case]

    def build(**side):
        paddle.seed(seed)
        mc = llama_tiny_config(num_hidden_layers=4,
                               max_position_embeddings=64, **keyword)
        kw = dict(dict(sep=1, sharding=1), **shared, **side)
        cfg = PretrainConfig(mc, global_batch=4, seq_len=32,
                             n_microbatches=4, dp=1, mp=2, pp=2, **kw)
        ndev = 4 * cfg.sep
        if len(jax.devices()) < ndev:
            pytest.skip(f"needs {ndev} (virtual) devices")
        mesh = make_hybrid_mesh_for(cfg, devices=jax.devices()[:ndev])
        built = build_llama_pretrain_step(cfg, mesh)
        # the trainer's own account of the layout agrees with the trace
        assert built[2]["remat_plan"]["seq_sharded"] \
            is (cfg.pp_schedule == "compiled")
        return mc, built

    mc, (st_a, step_a, meta_a) = build(**side_a)
    _, (st_b, step_b, meta_b) = build(**side_b)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, mc.vocab_size, (4, 32)), jnp.int32)
    ids_a = jax.device_put(ids, meta_a["data_sharding"])
    ids_b = jax.device_put(ids, meta_b["data_sharding"])
    st_a, ma = step_a(st_a, ids_a, ids_a)
    st_b, mb = step_b(st_b, ids_b, ids_b)
    np.testing.assert_allclose(float(mb["loss"]), float(ma["loss"]),
                               rtol=rtol)


def test_seq_sharded_mb_auto_spec_rejected():
    """The composition limit is a loud error, not a hang."""
    from jax.sharding import PartitionSpec as P
    schedule = one_f_one_b_schedule(2, M)
    stacked, head, mbs, labels = _setup()
    stacked = {k: v.reshape((2, 2 * LS) + v.shape[2:])
               for k, v in stacked.items()}
    mesh8 = build_hybrid_mesh(pp_degree=2, sep_degree=2, mp_degree=2,
                              devices=jax.devices()[:8])
    with pytest.raises(ValueError, match="gather it at the boundary"):
        scheduled_pipeline_loss(
            schedule, stage_fn, head_fn, mesh8, stacked, head, mbs,
            labels, mb_auto_spec=P(None, "sep"))
