"""One-command pretrain driver (VERDICT r4 item 5; ref: PaddleNLP
``llm/run_pretrain.py`` — the north star's named entry point: data ->
hybrid-parallel train loop -> checkpoint, SURVEY §2.4 row 2).

    python -m paddle_tpu.trainer.run_pretrain --config cfg.json

composes the framework's own pieces end to end:
  * text corpus -> in-tree BPE tokenizer (``text.train_bpe``; vocab cached
    beside the checkpoints) -> fixed-length windows, or a pre-tokenized
    ``.npy``/``.npz`` token stream, or seeded synthetic tokens,
  * ``io.DataLoader`` + ``io.DistributedBatchSampler`` (seeded, epoch
    reshuffle; every process draws the IDENTICAL global batch, the
    ``global_device_put`` contract that feeds the dp/sharding axes),
  * ``build_llama_pretrain_step`` over the ``make_hybrid_mesh_for`` mesh
    (dp/mp/pp/sharding/sep from the config's ``parallel`` table — the
    hybrid_configs equivalent),
  * per-step loss + tokens/s + MFU logging (jsonl, resumable-comparable),
  * sharded checkpoint save every ``save_interval`` steps
    (``distributed.checkpoint``: per-shard .npy + reshard-on-load) with
    AUTO-RESUME: restart with the same command and training continues
    from the last checkpoint — data order, optimizer moments and step
    count restored; SIGTERM triggers an emergency checkpoint.

Chip invocation (flagship shard; docs/FLAGSHIP.md has the recipe context):

    python -m paddle_tpu.trainer.run_pretrain --config - <<'JSON'
    {"model": {"preset": "llama3_8b_shard"}, "seq_len": 8192,
     "global_batch": 3, "max_steps": 50, "remat": "none",
     "scan_layers": false, "ce_chunks": 2, "save_interval": 25,
     "output_dir": "/tmp/pretrain_8b"}
    JSON
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

import numpy as np

__all__ = ["main", "run"]

DEFAULTS = {
    "model": {"preset": "tiny"},
    "data": {"corpus": None, "vocab_size": 512},
    "seq_len": 128,
    "global_batch": 8,
    "n_microbatches": 1,
    "max_steps": 50,
    "lr": 3e-4,
    "weight_decay": 0.1,
    "grad_clip": 1.0,
    "parallel": {"dp": 1, "mp": 1, "pp": 1, "sharding": 1, "sep": 1},
    "remat": "full",
    "scan_layers": True,
    "ce_chunks": 4,
    "pp_schedule": "compiled",
    "log_interval": 1,
    "save_interval": 50,
    "output_dir": "pretrain_out",
    "seed": 1234,
    # optional predictive OOM gate (auto-tuner trials, SURVEY §2.3 P12):
    # AOT-compile the step and refuse to run if XLA's own memory
    # accounting (args + temps + output, per device) exceeds this budget
    # — the same accounting the TPU runtime uses when it refuses an
    # allocation, surfaced BEFORE burning a trial
    "hbm_budget_bytes": None,
}


def _load_config(path: str) -> dict:
    raw = sys.stdin.read() if path == "-" else open(path).read()
    cfg = dict(DEFAULTS)
    user = json.loads(raw)
    for k, v in user.items():
        if isinstance(v, dict) and isinstance(cfg.get(k), dict):
            cfg[k] = {**cfg[k], **v}
        else:
            cfg[k] = v
    return cfg


def _build_model_config(spec: dict, seq_len: int):
    """The model configuration of a config's `model` table: a `preset`,
    or the keys of the family `model_type` names ("llama", the default:
    `LlamaConfig`; "mellum": `MellumConfig`).  An unknown `model_type`
    is refused by name."""
    from ..models.llama import (LlamaConfig, llama3_8b_shard_config,
                                llama_tiny_config)
    spec = dict(spec)
    preset = spec.pop("preset", None)
    model_type = spec.pop("model_type", "llama")
    if model_type == "mellum":
        from ..models.mellum import MellumConfig, mellum_tiny_config
        if preset not in (None, "tiny"):
            raise SystemExit(f"unknown mellum preset {preset!r}")
        return (mellum_tiny_config if preset == "tiny"
                else MellumConfig)(**spec)
    if model_type != "llama":
        raise SystemExit(
            f"unknown model_type {model_type!r}: run_pretrain builds "
            f"'llama' and 'mellum'")
    if preset == "llama3_8b_shard":
        return llama3_8b_shard_config(mp=8, pp=4,
                                      max_position_embeddings=seq_len,
                                      sequence_parallel=False,
                                      fuse_attention_qkv=True,
                                      fuse_attention_ffn=True, **spec)
    if preset == "tiny":
        spec.setdefault("max_position_embeddings", seq_len)
        return llama_tiny_config(**spec)
    spec.setdefault("max_position_embeddings", seq_len)
    return LlamaConfig(**spec)


def _token_stream(data_cfg: dict, vocab_size_needed: int, out_dir: str,
                  seed: int):
    """Return (tokens int32 1-D numpy, vocab_size). Three sources:
    synthetic (corpus None), pre-tokenized .npy/.npz, or a text file
    tokenized by the in-tree BPE (vocab trained once, cached)."""
    corpus = data_cfg.get("corpus")
    if corpus is None:
        rng = np.random.RandomState(seed)
        n = int(data_cfg.get("synthetic_tokens", 200_000))
        return (rng.randint(0, vocab_size_needed, n).astype(np.int32),
                vocab_size_needed)
    if corpus.endswith((".npy", ".npz")):
        arr = np.load(corpus, mmap_mode="r")
        if hasattr(arr, "files"):
            arr = arr[arr.files[0]]
        return np.asarray(arr, np.int32).reshape(-1), vocab_size_needed
    # text corpus -> BPE; only the COORDINATOR trains/writes the cached
    # vocab (atomic tmp+rename), other ranks wait for it — concurrent
    # writers would race on the shared file
    import jax
    from ..text import BPETokenizer, train_bpe
    vs = int(data_cfg.get("vocab_size", 512))
    cache = os.path.join(out_dir, "bpe_tokenizer.json")
    text = open(corpus, encoding="utf-8").read()
    if not os.path.exists(cache):
        if jax.process_index() == 0:
            vocab, merges = train_bpe([text], vocab_size=vs)
            os.makedirs(out_dir, exist_ok=True)
            with open(cache + ".tmp", "w") as f:
                json.dump({"vocab": vocab, "merges": list(merges)}, f)
            os.replace(cache + ".tmp", cache)
        else:
            deadline = time.time() + 300
            while not os.path.exists(cache):
                if time.time() > deadline:
                    raise TimeoutError(
                        "waiting for the coordinator's bpe_tokenizer.json")
                time.sleep(0.2)
    spec = json.load(open(cache))
    tok = BPETokenizer(spec["vocab"], [tuple(m) for m in spec["merges"]])
    ids = np.asarray(tok.encode(text), np.int32)
    return ids, max(vs, int(ids.max()) + 1)


class _WindowDataset:
    """Fixed-length next-token windows over the token stream."""

    def __init__(self, tokens: np.ndarray, seq_len: int):
        self.tokens = tokens
        self.seq = seq_len
        self.n = max(0, (len(tokens) - 1) // seq_len)

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        s = i * self.seq
        ids = self.tokens[s:s + self.seq]
        labels = self.tokens[s + 1:s + self.seq + 1]
        return np.asarray(ids, np.int32), np.asarray(labels, np.int32)


def _flatten_state(state) -> dict:
    """TrainState -> flat {key: array} for the sharded checkpoint; keys
    come from tree paths so they are stable across rebuilds."""
    import jax
    flat = {}
    for name, tree in (("master", state.master), ("opt", state.opt_state)):
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            key = name + "/" + "/".join(
                str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            flat[key] = leaf
    flat["step"] = state.step
    return flat


def _restore_state(state, flat: dict, param_dtype):
    """Rebuild a TrainState from the (loaded) flat dict, recomputing the
    compute params (bf16) from the master weights."""
    import jax
    from ..amp import decorate_tree
    from .pretrain import TrainState

    def refill(name, tree):
        paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
        leaves = []
        for path, _ in paths:
            key = name + "/" + "/".join(
                str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            leaves.append(flat[key])
        return jax.tree_util.tree_unflatten(treedef, leaves)

    master = refill("master", state.master)
    opt = refill("opt", state.opt_state)
    params = decorate_tree(master, param_dtype)
    return TrainState(params, master, opt, flat["step"])


def run(cfg: dict) -> int:
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from ..distributed import checkpoint as dck
    from ..distributed.mesh import global_device_put
    from ..io import DataLoader, DistributedBatchSampler
    from .pretrain import (PretrainConfig, build_llama_pretrain_step,
                           flops_per_token, make_hybrid_mesh_for,
                           record_moe_metrics)

    out_dir = cfg["output_dir"]
    os.makedirs(out_dir, exist_ok=True)
    paddle.seed(cfg["seed"])
    # multi-process (launcher-driven) runs: every process executes the
    # same SPMD program over the GLOBAL mesh; only the coordinator writes
    # the shared log/pointer files (checkpoint shards are per-process by
    # design — distributed.checkpoint tags files by rank)
    is_coord = jax.process_index() == 0

    mc = _build_model_config(cfg["model"], cfg["seq_len"])
    tokens, data_vocab = _token_stream(cfg["data"], mc.vocab_size, out_dir,
                                       cfg["seed"])
    if data_vocab > mc.vocab_size:
        # XLA's gather CLAMPS out-of-range ids, so oversized token ids
        # would train silently on wrong embeddings — refuse instead
        raise SystemExit(
            f"tokenized corpus needs vocab_size >= {data_vocab} but the "
            f"model has {mc.vocab_size}; raise model.vocab_size (or lower "
            f"data.vocab_size)")
    ds = _WindowDataset(tokens, cfg["seq_len"])
    if len(ds) == 0:
        raise SystemExit("corpus too small for one window")

    par = cfg["parallel"]
    pcfg = PretrainConfig(
        mc, global_batch=cfg["global_batch"], seq_len=cfg["seq_len"],
        n_microbatches=cfg["n_microbatches"], lr=cfg["lr"],
        weight_decay=cfg["weight_decay"], grad_clip=cfg["grad_clip"],
        dp=par.get("dp", 1), mp=par.get("mp", 1), pp=par.get("pp", 1),
        sharding=par.get("sharding", 1), sep=par.get("sep", 1),
        remat=cfg["remat"], scan_layers=cfg["scan_layers"],
        ce_chunks=cfg["ce_chunks"], pp_schedule=cfg["pp_schedule"])
    mesh = make_hybrid_mesh_for(pcfg)
    state, jstep, meta = build_llama_pretrain_step(pcfg, mesh)
    if is_coord:
        # what each layer's checkpoint keeps under remat "full", and the
        # bytes it was chosen against (null where the device says none)
        print(f"[run_pretrain] remat plan "
              f"{json.dumps(meta['remat_plan'])}", flush=True)
    fpt = flops_per_token(mc)

    # SPMD feeding contract: EVERY process draws the identical global
    # batch (num_replicas=1) and global_device_put scatters it onto the
    # dp/sharding submesh — the TPU-native replacement for per-rank NCCL
    # scatter (docs/MULTIHOST_TRAIN.json mechanism note)
    sampler = DistributedBatchSampler(ds, batch_size=cfg["global_batch"],
                                      num_replicas=1, rank=0, shuffle=True,
                                      drop_last=True)
    loader = DataLoader(ds, batch_sampler=sampler,
                        collate_fn=lambda b: (
                            np.stack([x[0] for x in b]),
                            np.stack([x[1] for x in b])))
    steps_per_epoch = len(sampler)
    if steps_per_epoch == 0:
        raise SystemExit("global_batch larger than the dataset")

    if cfg.get("hbm_budget_bytes"):
        spec = jax.ShapeDtypeStruct(
            (cfg["global_batch"], cfg["seq_len"]), jnp.int32,
            sharding=meta["data_sharding"])
        compiled = jstep.lower(state, spec, spec).compile()
        ma = compiled.memory_analysis()
        if ma is not None:
            # XLA's stats are PER-DEVICE (replicated args count at full
            # size on every device, sharded args at their shard size)
            need = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                    + ma.output_size_in_bytes)
            budget = int(cfg["hbm_budget_bytes"])
            print(f"[run_pretrain] memory estimate {need / 1e6:.1f} MB "
                  f"per device (budget {budget / 1e6:.1f} MB)", flush=True)
            if need > budget:
                raise MemoryError(
                    f"predicted per-device memory {need / 1e6:.1f} MB "
                    f"exceeds hbm_budget_bytes {budget / 1e6:.1f} MB")

    # ---- auto-resume -----------------------------------------------------
    start_step = 0
    latest = os.path.join(out_dir, "latest")
    if os.path.exists(latest):
        ck = open(latest).read().strip()
        flat = _flatten_state(state)
        dck.load_state_dict(flat, os.path.join(out_dir, ck))
        import jax.numpy as _jnp
        pdt = _jnp.bfloat16 if pcfg.param_dtype == "bfloat16" \
            else _jnp.float32
        state = _restore_state(state, flat, pdt)
        start_step = int(jax.device_get(state.step))
        print(f"[run_pretrain] resumed from {ck} at step {start_step}",
              flush=True)

    def save(step: int):
        name = f"ckpt_step{step}"
        dck.save_state_dict(_flatten_state(state),
                            os.path.join(out_dir, name))
        if jax.process_count() > 1:
            # every rank's shard files must be ON DISK before the
            # coordinator commits the pointer — a kill between one rank's
            # save and another's would otherwise publish a checkpoint
            # with missing shards
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(f"ckpt_{step}")
        if is_coord:
            with open(latest + ".tmp", "w") as f:
                f.write(name)
            os.replace(latest + ".tmp", latest)   # atomic pointer flip
            print(f"[run_pretrain] saved {name}", flush=True)

    stop = {"sig": False}
    # single-process: SIGTERM -> emergency checkpoint at the step
    # boundary. Multi-process: a signal may reach only SOME ranks; a
    # partial emergency save would hang in the pointer-flip barrier (the
    # unsignaled peers never join), so those runs exit WITHOUT an extra
    # save and recovery rides the periodic checkpoints + auto-resume —
    # the preemption-aware story of SURVEY §5.3 (the launcher's teardown
    # SIGTERMs every child anyway).
    if jax.process_count() == 1:
        signal.signal(signal.SIGTERM, lambda *_: stop.update(sig=True))

    log_path = os.path.join(out_dir, "losses.jsonl")
    logf = open(log_path, "a") if is_coord else None
    tokens_per_step = cfg["global_batch"] * cfg["seq_len"]
    # null where the device has no published peak (a CPU run): the key
    # stays so log readers need no special case, the value claims nothing
    from ..device.peaks import peak as _device_peak
    pk = _device_peak()

    def batches():
        """Deterministic step->batch mapping that survives restarts: the
        epoch seeds the shuffle, so skipping (start_step % steps_per_
        epoch) batches reproduces the uninterrupted order exactly."""
        epoch = start_step // steps_per_epoch
        skip = start_step % steps_per_epoch
        while True:
            sampler.set_epoch(epoch)
            for i, b in enumerate(loader):
                if skip:
                    skip -= 1
                    continue
                yield b
            epoch += 1

    # host spans for an operator's profiler trace and the recorder:
    # waiting for data, the step (dispatch to the loss on the host), save
    from ..observability import span
    it = batches()
    t_last = time.time()
    for step in range(start_step, cfg["max_steps"]):
        with span("trainer.data_wait", step=step + 1):
            ids_np, labels_np = next(it)
        with span("trainer.step", step=step + 1):
            ids = global_device_put(jnp.asarray(ids_np),
                                    meta["data_sharding"])
            labels = global_device_put(jnp.asarray(labels_np),
                                       meta["data_sharding"])
            state, m = jstep(state, ids, labels)
            loss = float(jax.device_get(m["loss"]))
        now = time.time()
        tok_s = tokens_per_step / max(now - t_last, 1e-9)
        t_last = now
        rec = {"step": step + 1, "loss": round(loss, 6),
               "tokens_per_s": round(tok_s, 1),
               "mfu_6N_est": (round(tok_s * fpt / pk.bf16_flops, 4)
                              if pk else None)}
        # a routed family's step says what it routed (the gauges
        # `trainer.moe.*`); a dense one adds nothing
        rec.update({k: round(v, 6) for k, v in
                    record_moe_metrics(jax.device_get(m)).items()})
        if logf is not None:
            logf.write(json.dumps(rec) + "\n")
            logf.flush()
            if (step + 1) % cfg["log_interval"] == 0:
                print(f"[run_pretrain] {json.dumps(rec)}", flush=True)
        # save_interval <= 0 disables ALL checkpoints (tuner trials)
        if cfg["save_interval"] > 0 and (
                (step + 1) % cfg["save_interval"] == 0
                or (step + 1) == cfg["max_steps"] or stop["sig"]):
            with span("trainer.save", step=step + 1):
                save(step + 1)
        if stop["sig"]:
            print("[run_pretrain] SIGTERM: emergency checkpoint done"
                  if cfg["save_interval"] > 0 else
                  "[run_pretrain] SIGTERM: exiting (checkpoints disabled "
                  "by save_interval<=0 — nothing saved)", flush=True)
            return 0
    print(f"[run_pretrain] done at step {cfg['max_steps']}", flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.trainer.run_pretrain",
        description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True,
                    help="JSON config path ('-' reads stdin)")
    ap.add_argument("--max-steps", type=int, default=None)
    ap.add_argument("--output-dir", default=None)
    ap.add_argument("--fault-spec", default=None,
                    help="deterministic fault-injection plan for chaos "
                         "runs (docs/RESILIENCE.md grammar), e.g. "
                         "'seed=3;nan_grad@step=100;preempt@step=500'")
    args = ap.parse_args(argv)
    from .._bootstrap import configure_compile_cache
    configure_compile_cache()
    cfg = _load_config(args.config)
    if args.max_steps is not None:
        cfg["max_steps"] = args.max_steps
    if args.output_dir is not None:
        cfg["output_dir"] = args.output_dir
    if args.fault_spec is not None:
        from .. import resilience as _res
        _res.set_fault_spec(args.fault_spec)
    return run(cfg)


if __name__ == "__main__":
    sys.exit(main())
