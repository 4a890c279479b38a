"""System under test: ``build_llama_pretrain_step`` exactly as
``trainer/run_pretrain.run`` builds it (same ``PretrainConfig`` fields,
same ``_WindowDataset`` / ``DataLoader`` feeding), at a configuration
file's sizes, and its comparison with the plain reference."""

from __future__ import annotations

import contextlib
import time
from typing import Mapping

import numpy as np

from ..lib import costs, reference_llama as ref
from ..lib.harness import as_run, say

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "rope_theta",
              "rms_norm_eps", "tie_word_embeddings")

#: |first-step loss - plain float32 loss| / float32 loss.  At a random
#: start the final RMSNorm fixes the hidden norm, so the batch mean is
#: ln V + a constant whatever the layers compute: this holds the head,
#: the cross-entropy and its normalisation, and NOT the layers.  The
#: rounding of a mean over n tokens falls as 1 / sqrt(n); over 32,768
#: tokens the chip read 2.5e-7 to 1.1e-6 (PR 23) and the limit is 2e-5.
LOSS_RTOL_SQRT_TOKENS = 2e-5 * 32768 ** 0.5
#: What holds the layers, forward and backward: the step's gradient of
#: the embedding rows that only sequence 0 uses (read from the first
#: Adam moment, which after one step is the clipped gradient times a
#: scalar) against the plain float32 gradient, as a relative distance
#: after the best scalar fit.  It may be at most this many times the
#: distance of the plain reference run in bfloat16 from the same
#: float32 gradient.  Rounding to 8 bits is 32 times coarser than to
#: bfloat16 and an unrelated hidden state gives a distance near 1,
#: against a bfloat16 distance of a few per cent.  The chip read 3.65 %
#: for the step against 1.88 % for the plain bfloat16 run (PR 23).
GRAD_NOISE_MULTIPLE = 4.0
EMBED_KEY = "llama.embed_tokens.weight"
#: rows compared at most: one shape for the gathers of every seed
GRAD_ROWS = 1024


def fit_distance(a, want) -> float:
    """|s a - want| / |want| at the scalar s that makes it smallest."""
    a, want = np.asarray(a, np.float64), np.asarray(want, np.float64)
    s = (a * want).sum() / max((a * a).sum(), 1e-300)
    return float(np.linalg.norm(s * a - want)
                 / max(np.linalg.norm(want), 1e-300))


def pretrain_config(src: Mapping):
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.trainer.pretrain import PretrainConfig
    t = src["trainer"]
    par = t["parallel"]
    mc = LlamaConfig(
        max_position_embeddings=t["seq_len"],
        sequence_parallel=False, fuse_attention_qkv=True,
        fuse_attention_ffn=True, fuse_pack_groups=t["fuse_pack_groups"],
        **{k: src[k] for k in MODEL_KEYS})
    # the fields run_pretrain.run passes, by the same names
    return PretrainConfig(
        mc, global_batch=t["global_batch"], seq_len=t["seq_len"],
        n_microbatches=t.get("n_microbatches", 1),
        dp=par.get("dp", 1), mp=par.get("mp", 1), pp=par.get("pp", 1),
        sharding=par.get("sharding", 1), sep=par.get("sep", 1),
        remat=t["remat"], scan_layers=t["scan_layers"],
        ce_chunks=t["ce_chunks"])


def _host_device():
    """The host as a jax device, so that the initial float32 model is
    built off the chips (``build_llama_pretrain_step`` makes it eagerly
    on the default device, where it would not fit beside the state)."""
    import jax
    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        return contextlib.nullcontext()


class System:
    def __init__(self, config: Mapping, rehearse: bool, seed: int, devices):
        import jax
        import paddle_tpu as paddle
        from paddle_tpu.io import DataLoader, DistributedBatchSampler
        from paddle_tpu.trainer import run_pretrain
        from paddle_tpu.trainer.pretrain import (build_llama_pretrain_step,
                                                 make_hybrid_mesh_for)
        src = as_run(config, rehearse)
        self.cfg = {k: src[k] for k in MODEL_KEYS}
        self.trainer = dict(src["trainer"])
        self.pcfg = pretrain_config(src)
        t0 = time.perf_counter()
        paddle.seed(seed % (2 ** 31))
        self.mesh = make_hybrid_mesh_for(self.pcfg, devices=list(devices))
        with _host_device():
            self.state, self.jstep, self.meta = build_llama_pretrain_step(
                self.pcfg, self.mesh)
        jax.block_until_ready(self.state.master)
        self.tokens_per_step = (self.trainer["global_batch"]
                                * self.trainer["seq_len"])
        self.flops_per_token = costs.train_flops_per_token(
            self.cfg, self.trainer["seq_len"])
        # the input pipeline of run_pretrain.run: synthetic stream from
        # the seed, fixed windows, the seeded sampler, the same collate
        steps = int(self.trainer.get("synthetic_steps", 64))
        tokens, _ = run_pretrain._token_stream(
            {"corpus": None,
             "synthetic_tokens": self.tokens_per_step * steps + 1},
            self.cfg["vocab_size"], "", seed % (2 ** 32))
        ds = run_pretrain._WindowDataset(tokens, self.trainer["seq_len"])
        self.sampler = DistributedBatchSampler(
            ds, batch_size=self.trainer["global_batch"], num_replicas=1,
            rank=0, shuffle=True, drop_last=True)
        self.loader = DataLoader(
            ds, batch_sampler=self.sampler,
            collate_fn=lambda b: (np.stack([x[0] for x in b]),
                                  np.stack([x[1] for x in b])))
        say(f"system: {costs.n_params(self.cfg) / 1e9:.3f} B parameters, "
            f"mesh { {k: v for k, v in self.mesh.shape.items() if v > 1} }"
            f", trainer {self.trainer}, built in "
            f"{time.perf_counter() - t0:.1f}s")

    def batches(self):
        epoch = 0
        while True:
            self.sampler.set_epoch(epoch)
            yield from self.loader
            epoch += 1

    def put(self, ids_np, labels_np):
        import jax.numpy as jnp
        from paddle_tpu.distributed.mesh import global_device_put
        return (global_device_put(jnp.asarray(ids_np),
                                  self.meta["data_sharding"]),
                global_device_put(jnp.asarray(labels_np),
                                  self.meta["data_sharding"]))

    def step(self, ids, labels) -> float:
        import jax
        self.state, m = self.jstep(self.state, ids, labels)
        return float(jax.device_get(m["loss"]))

    # ------------------------------------------------------- correctness
    def check_first_step(self, ids_np, labels_np) -> dict:
        """One step on a batch, held to the plain reference: its loss,
        its gradient through every layer, and that the optimiser moved
        the weights against that gradient (see the limits above)."""
        t0 = time.perf_counter()
        want = self.reference(ids_np, labels_np)
        rows = want["rows"]
        embed = lambda tree: np.asarray(  # noqa: E731
            tree["outer"][EMBED_KEY][rows], np.float64)
        before = embed(self.state.master)
        t1 = time.perf_counter()
        loss = self.step(*self.put(ids_np, labels_np))
        moment, after = embed(self.state.opt_state.moment1), \
            embed(self.state.master)
        loss_rel = abs(loss - want["loss"]) / abs(want["loss"])
        grad = fit_distance(moment, want["grad_f32"])
        noise = fit_distance(want["grad_bf16"], want["grad_f32"])
        # AdamW: w <- w (1 - lr wd) - lr u, where u has the sign of the
        # first moment and |u| <= 1 on the first step
        lr, wd = self.pcfg.lr, self.pcfg.weight_decay
        u = (before * (1.0 - lr * wd) - after) / lr
        moved = bool(np.isfinite(u).all() and np.abs(u).max() <= 1.001
                     and np.abs(u).mean() > 0.01
                     and (np.sign(u) == np.sign(moment))[
                         np.abs(u) > 0.01].all())
        loss_limit = LOSS_RTOL_SQRT_TOKENS / labels_np.size ** 0.5
        ok = (loss_rel <= loss_limit and moved
              and grad <= GRAD_NOISE_MULTIPLE * noise)
        return {"ok": bool(ok), "loss": loss, "loss_f32": want["loss"],
                "loss_rel": loss_rel, "loss_limit": loss_limit,
                "grad_distance": grad, "bf16_distance": noise,
                "grad_limit": GRAD_NOISE_MULTIPLE, "rows": int(len(rows)),
                "update_follows_gradient": moved,
                "mean_abs_update_over_lr": float(np.abs(u).mean()),
                "reference_s": t1 - t0}

    def reference(self, ids_np, labels_np) -> dict:
        """The plain reference on one batch at the CURRENT master
        weights, on the first chip, weights brought over a layer at a
        time: the float32 forward loss of every sequence, and for
        sequence 0 the gradient of the batch's mean loss at its embedded
        inputs, in float32 and (the yardstick) in bfloat16.  Rows of the
        embedding that no other position of the batch uses have exactly
        that gradient."""
        import jax
        import jax.numpy as jnp
        dev = self.mesh.devices.flat[0]
        m = self.state.master
        c = self.cfg
        nq, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                      c["head_dim"])
        g = self.trainer["fuse_pack_groups"]
        H, I = c["hidden_size"], c["intermediate_size"]
        st = m["stacked"]
        n_layers = c["num_hidden_layers"]
        eps = c["rms_norm_eps"]
        f32, bf16 = jnp.float32, jnp.bfloat16

        def layer_weights(i):
            per_stage = n_layers // self.mesh.shape["pp"]
            get = lambda k: jax.device_put(  # noqa: E731
                st[k][i // per_stage, i % per_stage], dev)
            qkv = get("self_attn.qkv_proj.weight").reshape(
                H, g, (nq + 2 * nkv) // g, d)
            hg, kg = nq // g, nkv // g
            gu = get("mlp.gate_up_proj.weight").reshape(H, g, 2 * I // g)
            return {"ln1": get("input_layernorm.weight"),
                    "wq": qkv[:, :, :hg].reshape(H, nq * d),
                    "wk": qkv[:, :, hg:hg + kg].reshape(H, nkv * d),
                    "wv": qkv[:, :, hg + kg:].reshape(H, nkv * d),
                    "wo": get("self_attn.o_proj.weight"),
                    "ln2": get("post_attention_layernorm.weight"),
                    "wg": gu[:, :, :I // g].reshape(H, I),
                    "wu": gu[:, :, I // g:].reshape(H, I),
                    "wd": get("mlp.down_proj.weight")}

        outer = {k: jax.device_put(v, dev) for k, v in m["outer"].items()}
        norm_w, head_w = outer["llama.norm.weight"], outer["lm_head.weight"]
        on_dev = lambda a: jax.device_put(jnp.asarray(a), dev)  # noqa: E731
        S = ids_np.shape[1]
        cos, sin = (on_dev(t) for t in ref.rope_tables(d, S,
                                                       c["rope_theta"]))
        kw = dict(nq=nq, nkv=nkv, d=d, eps=eps,
                  head_block=int(self.trainer.get("reference_head_block",
                                                  4)))
        xs = [jnp.take(outer[EMBED_KEY], on_dev(row[None]), 0)
              for row in ids_np]
        x16 = xs[0].astype(bf16)
        kept, kept16 = [xs[0]], [x16]   # the inputs of sequence 0's layers
        for i in range(n_layers):
            w = layer_weights(i)
            with ref.highest():
                xs = [ref.layer(x, w, cos, sin, dtype=f32, **kw)
                      for x in xs]
            x16 = ref.layer(x16, w, cos, sin, dtype=bf16, **kw)
            kept.append(xs[0])
            kept16.append(x16)
            del w
        with ref.highest():
            total = sum(float(ref.head_loss_sum(
                x, norm_w, head_w, on_dev(lab[None]), eps=eps, dtype=f32))
                for x, lab in zip(xs, labels_np))
            del xs
            lab0 = on_dev(labels_np[:1])
            dy = ref.head_loss_input_grad(kept.pop(), norm_w, head_w, lab0,
                                          eps=eps, dtype=f32)
        dy16 = ref.head_loss_input_grad(kept16.pop(), norm_w, head_w, lab0,
                                        eps=eps, dtype=bf16)
        for i in reversed(range(n_layers)):
            w = layer_weights(i)
            with ref.highest():
                dy = ref.layer_input_grad(kept.pop(), w, cos, sin, dy,
                                          dtype=f32, **kw)
            dy16 = ref.layer_input_grad(kept16.pop(), w, cos, sin, dy16,
                                        dtype=bf16, **kw)
            del w
        # positions of sequence 0 whose token occurs once in the batch
        ids0 = ids_np[0]
        once = np.flatnonzero(np.bincount(
            ids_np.ravel(), minlength=c["vocab_size"])[ids0] == 1)[:GRAD_ROWS]
        n = labels_np.size
        return {"loss": total / n, "rows": ids0[once],
                "grad_f32": np.asarray(dy, np.float64)[0][once] / n,
                "grad_bf16": np.asarray(dy16.astype(f32),
                                        np.float64)[0][once] / n}


def compile_for(config: Mapping, devices):
    """The step compiled for DESCRIBED devices, from shapes only (the
    off-chip rehearsal of tests/test_aot_compile.py).  The build places
    its own parameters, which a described device cannot hold; so the two
    calls that put arrays on the mesh hand back shapes with the same
    shardings instead.  Returns the compiled executable."""
    import jax
    import jax.numpy as jnp
    from unittest import mock
    from paddle_tpu.optimizer.functional import AdamWState, FunctionalAdamW
    from paddle_tpu.trainer import pretrain

    pcfg = pretrain_config(config)
    mesh = pretrain.make_hybrid_mesh_for(pcfg, devices=list(devices)[:4])

    def put(arr, sharding):
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype, sharding=sharding)

    def init(self, params):
        z = jax.tree.map(lambda p: jax.ShapeDtypeStruct(
            p.shape, jnp.dtype(self.moment_dtype), sharding=p.sharding),
            params)
        return AdamWState(moment1=z, moment2=z,
                          count=jax.ShapeDtypeStruct((), jnp.int32))

    with mock.patch.object(pretrain, "global_device_put", put), \
            mock.patch.object(FunctionalAdamW, "init", init):
        state, jstep, meta = pretrain.build_llama_pretrain_step(pcfg, mesh)
    state = state._replace(step=jax.ShapeDtypeStruct((), jnp.int32))
    t = config["trainer"]
    spec = jax.ShapeDtypeStruct((t["global_batch"], t["seq_len"]),
                                jnp.int32, sharding=meta["data_sharding"])
    return jstep.lower(state, spec, spec).compile()
