"""On-chip smoke: the trainer and the serving engine through their normal
entry points on ONE TPU chip, at the full width of the Llama-3-8B
per-chip shard (models/llama.py:llama3_8b_shard_config).

    python chip_smoke.py            # phases 0-3, one chip
    python chip_smoke.py --chips 4  # only the hybrid-parallel phase

One process owns the chip.  Phase 0 runs the launcher as a child BEFORE
this process touches jax; everything after runs in-process.  A failed
phase raises — nothing is caught and turned into exit 0 — and without a
TPU the script exits non-zero with no result line.  The LAST stdout
line is the result: {"ok": true, "device": {"platform", "kind",
"count"}}.  Weights are random, made from --seed; depth is the shard's.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".scratch", "chip_smoke")     # git-ignored
TRAIN_COMPILE_WARMUP = 2     # steps allowed to compile (donated layouts)
#: --rehearse: the same control flow at toy sizes on whatever jax finds
#: (the CPU, Pallas in interpret mode) — finds wrong paths and arguments
#: before a chip call; checks nothing of the chip, prints no result
#: line and exits 3
REHEARSE = False


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# phase 0 — launcher child, before the parent touches jax
# ---------------------------------------------------------------------------

def launched_worker(out_dir: str) -> int:
    """What the launcher runs: two steps of the tiny preset through
    run_pretrain.run, then the platform this worker landed on."""
    import jax
    from paddle_tpu.trainer import run_pretrain
    cfg = dict(run_pretrain.DEFAULTS, max_steps=2, save_interval=0,
               output_dir=out_dir)
    rc = run_pretrain.run(cfg)
    dev = jax.devices()[0]
    print(f"WORKER_PLATFORM={dev.platform} kind={dev.device_kind}",
          flush=True)
    return rc


def phase0_launcher() -> None:
    assert "jax" not in sys.modules, "phase 0 must precede any jax import"
    log_dir = os.path.join(WORK, "launch_logs")
    t0 = time.time()
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", "1", "--log_dir", log_dir,
           os.path.abspath(__file__), "--launched-worker",
           os.path.join(WORK, "launched_out")]
    rc = subprocess.run(cmd, cwd=REPO, timeout=600).returncode
    log_path = os.path.join(log_dir, "workerlog.0")
    log = open(log_path, errors="replace").read() \
        if os.path.exists(log_path) else ""
    say(f"phase 0 launcher child rc={rc} in {time.time() - t0:.1f}s")
    for line in log.splitlines():
        if line.startswith(("[run_pretrain]", "WORKER_PLATFORM")):
            say(f"  worker: {line}")
    if rc != 0 or ("WORKER_PLATFORM=tpu" not in log and not REHEARSE):
        sys.stderr.write(log[-4000:])
        found = [ln for ln in log.splitlines()
                 if ln.startswith("WORKER_PLATFORM")] or ["no platform line"]
        sys.exit(f"phase 0 failed: launcher rc={rc}; chip_smoke.py needs "
                 f"a TPU, the worker reported: {found[-1]}")


# ---------------------------------------------------------------------------
# compile bookkeeping (jax.monitoring; read by phases 2-3)
# ---------------------------------------------------------------------------

class CompileLog:
    """Every backend compile (or persistent-cache retrieval) jax reports,
    with the caller's progress marker at the moment it happened."""

    def __init__(self):
        import jax.monitoring as mon
        self.events = []            # (fun_name, seconds, marker)
        self.cache = {"hits": 0, "misses": 0}
        self.marker = lambda: None
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((kw.get("fun_name", "?"), secs,
                                self.marker()))

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def window(self):
        """Start a phase: returns a closure giving the phase's summary."""
        n0, c0 = len(self.events), dict(self.cache)

        def summary():
            ev = self.events[n0:]
            return {"compiles": len(ev),
                    "compile_s": round(sum(e[1] for e in ev), 2),
                    "cache_hits": self.cache["hits"] - c0["hits"],
                    "cache_misses": self.cache["misses"] - c0["misses"],
                    "events": ev}
        return summary


# ---------------------------------------------------------------------------
# phase 2 — train: run_pretrain.run at the docstring's flagship recipe
# ---------------------------------------------------------------------------

def _loss_lines(path):
    if not os.path.exists(path):
        return []
    return [json.loads(ln) for ln in open(path) if ln.strip()]


def phase2_train(clog: CompileLog, seed: int) -> None:
    import gc
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.trainer import run_pretrain
    from paddle_tpu.trainer.pretrain import (PretrainConfig,
                                             build_llama_pretrain_step,
                                             make_hybrid_mesh_for)

    out_dir = os.path.join(WORK, "pretrain_8b_shard")
    losses = os.path.join(out_dir, "losses.jsonl")
    steps_a, steps_b = 6, 2
    batch, seq = (3, 8192) if not REHEARSE else (4, 128)
    cfg = dict(run_pretrain.DEFAULTS,
               model={"preset": "llama3_8b_shard" if not REHEARSE
                      else "tiny"},
               seq_len=seq, global_batch=batch, remat="none",
               scan_layers=False, ce_chunks=2, seed=seed,
               output_dir=out_dir)
    clog.marker = lambda: len(_loss_lines(losses))

    def one_run(max_steps, save_interval, start):
        done, t0 = clog.window(), time.time()
        rc = run_pretrain.run(dict(cfg, max_steps=max_steps,
                                   save_interval=save_interval))
        s = done()
        # (compile seconds, steps this run had finished before it)
        step_compiles = [(round(secs, 1), at - start)
                         for name, secs, at in s["events"]
                         if "train_step" in name]
        say(f"phase 2 run to step {max_steps}: rc={rc} "
            f"{time.time() - t0:.1f}s, train_step compiles (seconds, "
            f"steps done before) {step_compiles}, all compiles "
            f"{s['compiles']} in {s['compile_s']}s, persistent cache "
            f"hits/misses {s['cache_hits']}/{s['cache_misses']}")
        assert rc == 0
        assert step_compiles and all(
            at < TRAIN_COMPILE_WARMUP for _, at in step_compiles), \
            "train_step must compile, and only during warm-up"

    # six steps with ONE sharded save (at the last), then the same
    # command again: auto-resume from that save for two more, no save
    one_run(steps_a, steps_a, 0)
    assert open(os.path.join(out_dir, "latest")).read().strip() \
        == f"ckpt_step{steps_a}"
    one_run(steps_a + steps_b, 0, steps_a)
    recs = _loss_lines(losses)
    say(f"phase 2 losses: {[r['loss'] for r in recs]}")
    say(f"phase 2 tokens/s per step (host clock, first steps include "
        f"compile): {[r['tokens_per_s'] for r in recs]}; mfu_6N_est "
        f"{[r['mfu_6N_est'] for r in recs]}")
    assert [r["step"] for r in recs] == list(range(1, steps_a + steps_b
                                                   + 1)), \
        "the resumed run must continue at the saved step"
    assert all(np.isfinite(r["loss"]) for r in recs)

    # attention went through the Pallas kernel: the routing says so for
    # this recipe's shapes, and the lowered step holds the custom calls
    from paddle_tpu.ops.flash_attention import sdpa_path
    mc = run_pretrain._build_model_config(cfg["model"], cfg["seq_len"])
    q = jax.ShapeDtypeStruct((batch, seq, mc.num_attention_heads,
                              mc.head_dim), jnp.bfloat16)
    path = sdpa_path(q, q, causal=True)
    pcfg = PretrainConfig(mc, global_batch=batch, seq_len=seq,
                          remat="none", scan_layers=False, ce_chunks=2)
    state, jstep, meta = build_llama_pretrain_step(
        pcfg, make_hybrid_mesh_for(pcfg))
    spec = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                sharding=meta["data_sharding"])
    n_calls = jstep.lower(state, spec, spec).as_text().count(
        "tpu_custom_call")
    del state, jstep, meta
    gc.collect()
    say(f"phase 2 sdpa_path={path} tpu_custom_call in lowered "
        f"train_step: {n_calls}")
    assert REHEARSE or (path == "flash" and n_calls > 0)
    shutil.rmtree(out_dir)      # gigabytes of checkpoint


# ---------------------------------------------------------------------------
# phase 3 — serve: ServingEngine, default paths, vs solo generate_cached
# ---------------------------------------------------------------------------

#: The engine prefills in 32-token chunks through the paged kernels,
#: solo generate_cached prefills the whole prompt through flash
#: attention: same math, other summation order, in bf16.  On a
#: random-init model (near-flat logits over 16k tokens) the greedy
#: argmax flips between near-tied candidates, so token equality is not
#: the check that is true on the chip.  The one that is: at the first
#: divergence, a float32 / highest-precision forward over the shared
#: prefix scores both candidates, and the reference's token may beat
#: the engine's by no more than this many times the reference path's
#: OWN bf16 error there (max over the vocabulary of |bf16 - float32|
#: logits) — two correct bf16 evaluations can disagree that far.
FLIP_MAX_NOISE_MULTIPLE = 2.0


def _run_engine(eng, prompts, new_tokens, stagger):
    """Requests arrive `stagger` engine steps apart; returns their token
    arrays in request order."""
    pending = list(enumerate(prompts))
    out, step = {}, 0
    while len(out) < len(prompts):
        if pending and step % stagger == 0:
            i, p = pending.pop(0)
            eng.add_request(p, max_new_tokens=new_tokens, request_id=i)
        eng.step()
        out.update(eng.collect())
        step += 1
        assert step < 20000, "engine made no progress"
    return [out[i] for i in range(len(prompts))], step


def phase3_serve(clog: CompileLog, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.generation import (_decode_params, _init_caches,
                                       _llama_weights, _make_cached_step,
                                       generate_cached)
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama3_8b_shard_config)
    from paddle_tpu.serving import ServingEngine

    ctx, new = (2048, 32) if not REHEARSE else (256, 8)
    cfg = llama3_8b_shard_config(mp=8, pp=4, max_position_embeddings=ctx)
    if REHEARSE:
        from paddle_tpu.models.llama import llama_tiny_config
        cfg = llama_tiny_config(max_position_embeddings=ctx)
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    for prm in model.parameters():
        prm._data = prm._data.astype(jnp.bfloat16)
    rng = np.random.RandomState(seed)
    # four lengths, each asked twice with other tokens: the solo
    # reference compiles once per prompt length
    lens = [100, 100, 612, 612, 1031, 1031, 1500, 1500]
    if REHEARSE:
        lens = [n // 10 for n in lens]
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]

    done, t0 = clog.window(), time.time()
    eng = ServingEngine(model, max_slots=8, page_size=16, max_context=ctx)
    say(f"phase 3 engine paths: ragged={eng.ragged} "
        f"front_half_launches={eng.front_half_launches} "
        f"back_half_launches={eng.back_half_launches}")
    assert eng.ragged
    got, steps = _run_engine(eng, prompts, new, stagger=3)
    s = done()
    say(f"phase 3 engine: 8 requests (prompts {lens}, {new} new tokens, "
        f"arrivals 3 steps apart) in {steps} steps, "
        f"{time.time() - t0:.1f}s; compiles {s['compiles']} in "
        f"{s['compile_s']}s, persistent cache hits/misses "
        f"{s['cache_hits']}/{s['cache_misses']}")
    sizes = eng.program_cache_sizes()
    say(f"phase 3 program_cache_sizes={sizes} launches={eng.launches}")
    assert all(v == 1 for v in sizes.values()), sizes
    assert all(g.shape == (new,) for g in got)

    # the same requests one at a time through the same engine: the
    # kernels and the chunking are identical, so batching, staggering
    # and paging must not change a single token
    alone = [_run_engine(eng, [p], new, stagger=1)[0][0] for p in prompts]
    same = [bool(np.array_equal(a, g)) for a, g in zip(alone, got)]
    say(f"phase 3 batched == one-at-a-time through the engine: {same}")
    assert all(same)
    assert all(v == 1 for v in eng.program_cache_sizes().values())

    # the unified step's lowering holds the Pallas custom calls
    B, C = eng.max_slots, eng.prefill_chunk
    i32 = lambda *d: jax.ShapeDtypeStruct(d, jnp.int32)  # noqa: E731
    txt = eng._jit_unified.lower(
        eng._w, i32(B + C), eng._pools, i32(B + C), i32(B + 1),
        i32(B + 1), i32(B + 1, eng.pages_per_seq), i32(B + C),
        i32(B + C)).as_text()
    say(f"phase 3 tpu_custom_call in lowered unified step: "
        f"{txt.count('tpu_custom_call')}")
    assert REHEARSE or "tpu_custom_call" in txt

    # solo generate_cached of each prompt — the repo's exactness contract
    done, t0 = clog.window(), time.time()
    p = _decode_params(model, False, None)
    p32 = dict(p, **jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, _llama_weights(p)))

    def next_logits(params, ids):
        """Logits for the token after ``ids`` [1, n], teacher-forced
        through the solo path's own prefill."""
        total = ids.shape[1] + 1
        logits, _ = _make_cached_step(params, total)(
            jnp.asarray(ids, jnp.int32), _init_caches(params, 1, total), 0)
        return np.asarray(logits, np.float32).reshape(
            -1, cfg.vocab_size)[-1]

    exact, flips = 0, []
    for i, prompt in enumerate(prompts):
        ref = generate_cached(
            model, paddle.to_tensor(prompt[None]), max_new_tokens=new,
            decode_strategy="greedy_search")[0].numpy()[0]
        diff = np.nonzero(ref != got[i])[0]
        if diff.size == 0:
            exact += 1
            continue
        j = int(diff[0])        # first divergence; the prefix is shared
        ids = np.concatenate([prompt, ref[:j]])[None]
        bf16 = next_logits(p, ids)
        with jax.default_matmul_precision("highest"):
            f32 = next_logits(p32, ids)
        noise = float(np.abs(bf16 - f32).max())
        lead = float(f32[ref[j]] - f32[got[i][j]])
        flips.append({"request": i, "position": j,
                      "ref_lead_f32": round(lead, 5),
                      "ref_path_bf16_noise": round(noise, 5),
                      "top_logit_f32": round(float(f32.max()), 4)})
    s = done()
    say(f"phase 3 vs solo generate_cached: {exact}/8 token-exact; first "
        f"divergences judged by a float32 forward: {flips}")
    say(f"phase 3 solo reference: {time.time() - t0:.1f}s; compiles "
        f"{s['compiles']} in {s['compile_s']}s, persistent cache "
        f"hits/misses {s['cache_hits']}/{s['cache_misses']}")
    bad = [f for f in flips if f["ref_lead_f32"]
           > FLIP_MAX_NOISE_MULTIPLE * f["ref_path_bf16_noise"]]
    assert not bad, f"engine diverged from solo beyond bf16 noise: {bad}"


# ---------------------------------------------------------------------------
# --chips 4 — hybrid-parallel training against a one-device reference
# ---------------------------------------------------------------------------

#: |loss - one-device loss| / one-device loss, every step.  bf16 compute
#: with other reduction orders across the mesh; three AdamW steps.  The
#: v5e host measured at most 4.0e-4 (PR 21).
HYBRID_LOSS_RTOL = 5e-3


def phase_hybrid4(seed: int) -> None:
    import gc
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import llama3_8b_config
    from paddle_tpu.trainer.pretrain import (PretrainConfig,
                                             build_llama_pretrain_step,
                                             make_hybrid_mesh_for)

    devs = jax.devices()
    if (devs[0].platform != "tpu" and not REHEARSE) or len(devs) < 4:
        sys.exit(f"--chips 4 needs four TPU devices; jax found "
                 f"{len(devs)} x {devs[0].platform!r}")
    # published Llama-3-8B layer widths (hidden 4096, 32/8 heads x 128,
    # FFN 14336); depth 2 and the mp=8 vocab slice so the one-device
    # reference (weights + f32 master + Adam + grads) fits 16 GB
    seq, batch, steps = 1024, 4, 3
    mc = llama3_8b_config(num_hidden_layers=2, vocab_size=16032,
                          max_position_embeddings=seq)
    if REHEARSE:
        from paddle_tpu.models.llama import llama_tiny_config
        seq = 64
        mc = llama_tiny_config(max_position_embeddings=seq)
    rng = np.random.RandomState(seed)
    ids_np = rng.randint(0, mc.vocab_size, (batch, seq)).astype(np.int32)
    labels_np = rng.randint(0, mc.vocab_size, (batch, seq)).astype(np.int32)

    def run(tag, devices, **par):
        paddle.seed(seed)
        pcfg = PretrainConfig(mc, global_batch=batch, seq_len=seq, **par)
        mesh = make_hybrid_mesh_for(pcfg, devices=devices)
        t0 = time.time()
        state, jstep, meta = build_llama_pretrain_step(pcfg, mesh)
        ids = jax.device_put(jnp.asarray(ids_np), meta["data_sharding"])
        labels = jax.device_put(jnp.asarray(labels_np),
                                meta["data_sharding"])
        # where the state lives: per-device bytes, and each leaf
        per_dev = {d.id: 0 for d in devices}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                {"master": state.master, "opt": state.opt_state})[0]:
            shards = leaf.addressable_shards
            for sh in shards:
                per_dev[sh.device.id] += sh.data.nbytes
            say(f"  {tag} {jax.tree_util.keystr(path)}: {leaf.shape} "
                f"{leaf.dtype} spec={getattr(leaf.sharding, 'spec', None)}"
                f" shard={shards[0].data.shape} on {len(shards)} devices")
        losses = []
        for _ in range(steps):
            state, m = jstep(state, ids, labels)
            losses.append(float(jax.device_get(m["loss"])))
        say(f"{tag}: mesh={ {k: v for k, v in mesh.shape.items() if v > 1} }"
            f" losses={losses} state GB per device="
            f"{ {k: round(v / 2**30, 2) for k, v in per_dev.items()} } "
            f"{time.time() - t0:.1f}s")
        assert all(np.isfinite(losses))
        del state, jstep, meta, ids, labels
        gc.collect()
        return losses, per_dev

    ref, ref_bytes = run("one-device reference", devs[:1])
    total = sum(ref_bytes.values())
    for tag, par in (
            ("dp2(ZeRO sharding axis) x mp2", dict(sharding=2, mp=2)),
            ("pp2 x mp2 compiled schedule",
             dict(pp=2, mp=2, n_microbatches=2))):
        losses, per_dev = run(tag, devs[:4], **par)
        rel = [abs(a - b) / abs(b) for a, b in zip(losses, ref)]
        say(f"{tag}: relative loss error vs one device {rel} "
            f"(tolerance {HYBRID_LOSS_RTOL})")
        assert max(rel) <= HYBRID_LOSS_RTOL, (tag, losses, ref)
        # "everything on device 0" cannot pass: every chip holds state,
        # and none holds more than 60% of the one-device total
        assert min(per_dev.values()) > 0, per_dev
        assert max(per_dev.values()) <= 0.6 * total, (per_dev, total)


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend; no result line, exit 3")
    ap.add_argument("--launched-worker", metavar="OUT_DIR", default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.launched_worker:
        return launched_worker(args.launched_worker)

    global REHEARSE
    REHEARSE = args.rehearse
    t_start = time.time()
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    # build outputs on disk are not the committed source: both native
    # libraries are rebuilt from csrc/*.cc by the first import
    for so in ("_native.so", "_fusion_pass.so"):
        path = os.path.join(REPO, "paddle_tpu", "native", so)
        if os.path.exists(path):
            os.unlink(path)
    if args.chips == 1:
        phase0_launcher()

    # phase 1 — device
    import jax
    import jaxlib
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not REHEARSE:
        sys.exit(f"chip_smoke.py needs a TPU; jax found platform "
                 f"{dev.platform!r} ({dev.device_kind!r})")
    from paddle_tpu import native
    from paddle_tpu._bootstrap import configure_compile_cache
    from paddle_tpu.jit import fusion_cc
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - reporting only
        libtpu = "?"
    cache_dir = configure_compile_cache()
    say(f"phase 1 device: {len(jax.devices())} x {dev.device_kind} "
        f"({dev.platform}); jax {jax.__version__} jaxlib "
        f"{jaxlib.__version__} libtpu {libtpu}; compile cache {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
        f" entries at start)")
    say(f"native.available()={native.available()} "
        f"fusion_cc.available()={fusion_cc.available()} (both rebuilt "
        f"from csrc/ in this run)")
    assert native.available() and fusion_cc.available()

    if args.chips == 4:
        phase_hybrid4(args.seed)
    else:
        clog = CompileLog()
        for phase in (phase2_train, phase3_serve):
            t0 = time.time()
            phase(clog, args.seed)
            say(f"{phase.__name__} done in {time.time() - t0:.1f}s")
    shutil.rmtree(WORK, ignore_errors=True)
    say(f"all phases passed in {time.time() - t_start:.1f}s")
    if REHEARSE:
        say("rehearsal only: nothing here ran at size or on the chip")
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
