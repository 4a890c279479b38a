"""System under test: the trainer's step for a Mellum model file exactly
as ``trainer/run_pretrain.run`` builds it (``_build_model_config`` on
``model_type: "mellum"``, the same ``PretrainConfig`` fields, the same
``_WindowDataset`` / ``DataLoader`` feeding) — what ``llama_pretrain`` is
for Mistral, whose feeding, ``put`` and ``step`` it inherits — and its
comparison with the plain reference (``lib/reference_mellum.py``),
widened to what this family adds: a routed FFN, two layer kinds, a
load-balance term.
"""

from __future__ import annotations

import time
from typing import Mapping

import numpy as np

from ..lib import costs_mellum as costs, reference_mellum as ref
from ..lib.harness import as_run, say
from . import llama_pretrain as base
from .llama_pretrain import (GRAD_NOISE_MULTIPLE, GRAD_ROWS,
                             LOSS_RTOL_SQRT_TOKENS, fit_distance)

#: the model file's keys, as `run_pretrain._build_model_config` takes them
MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "max_position_embeddings",
              "rms_norm_eps", "num_experts_per_tok", "moe_intermediate_size",
              "norm_topk_prob", "sliding_window", "layer_types",
              "rope_parameters", "router_aux_loss_coef", "experts_held",
              "initializer_range", "tie_word_embeddings")
#: The limits of `check_first_step`, each with its reason and the two
#: readings it lies between ON THE FILE AS IT STANDS (the chip, PR 66:
#: the cell's own check on 16 seeds and `tools/mellum_limit.py --faults`
#: on seeds 11 and 12; PERF.md section 6 has every reading).  The lower
#: reading is the largest the timed path gave; the upper is the plain
#: reference in the nearest precision below bfloat16 (`float8`) or with
#: a fault planted, the smaller of the two seeds'.
#: (a) the LOSS: `llama_pretrain.LOSS_RTOL_SQRT_TOKENS`'s rule, the
#: accepted training cell's (the rounding of a mean over n tokens falls
#: as 1 / sqrt(n): 2.83e-5 at 16,384 tokens).  The timed path read at
#: most 3.4e-6 (8 x of room), a first-choice-only F_e 2.7e-3.  At a
#: random start the final RMSNorm fixes the hidden norm and the loss is
#: ln V + a constant whatever the layers compute, so it holds the head,
#: the cross-entropy, its normalisation and the load-balance term's
#: coefficient, NOT the layers and NOT the precision: float8 reads
#: 8.3e-6 and 1.4e-5, under it.  The gradients are what see float8.
#: (b, c, d) GRADIENTS, each read from the first Adam moment (after one
#: step the clipped gradient times a scalar) as `fit_distance` to the
#: plain float32 gradient, at most `GRAD_MULTIPLE[tensor]` x the distance
#: of the plain reference run in bfloat16 from the same float32
#: gradient — top-k flips between precisions are part of that noise and
#: are measured by it.  The timed path read 0.92-1.16 x that yardstick
#: over 48 readings (it IS a bfloat16 run).  (b) the embedding rows only
#: sequence 0 uses (through all four layers, both kinds): at most 1.004;
#: float8 13 x, full attention on the sliding layers 10.7 x; limit 4.
#: (c) layer 0's router weight (the only path into it is the choice's
#: weights and the load-balance term): at most 1.10; float8 3.6 x, full
#: attention 4.1 x, a first-choice-only F_e 17 x; limit 2.  (d) one held
#: expert's down projection in the LAST layer (the sort, the grouped
#: GEMM's backward over the stacks and the combine): at most 1.16;
#: float8 11 x, a share of [16, 32) 19 x, weights over the held choices
#: alone 10 x; limit 4.
GRAD_MULTIPLE = {"embed": GRAD_NOISE_MULTIPLE, "router": 2.0,
                 "expert_down": GRAD_NOISE_MULTIPLE}
#: (e) the load-balance term against the reference's, relative.  It is
#: a sum over 64 outputs of products of means over 16,384 tokens, which a
#: bfloat16 forward moves by the normed hidden states' rounding and the
#: few tokens whose 8th choice flips: ONE number whose error has either
#: sign, so unlike a norm it can read near 0 on any run.  The timed path
#: read at most 3.1e-6 (17 readings, root mean square 1.6e-6: the limit
#: is 5 of those); full attention on the sliding layers reads 9.8e-5
#: and 1.4e-4, a first-choice-only F_e 7.0.  float8 reads 1.85e-5 on
#: seed 11 and 7.7e-6 on seed 12 — just UNDER the limit there: what
#: float8 moves this number by is ~1e-5 with either sign, no limit
#: sees that on every seed, and (b, c, d) are what hold the precision.
AUX_RTOL = 8e-6
#: The same at the rehearsal's toy widths (512 tokens, 8 outputs, hidden
#: 64, here on the CPU), where ONE flipped choice is 1/512 of an F_e: the
#: step reads 2.2e-4, full attention 6.6e-3, a first-choice-only F_e
#: 0.99 (`benchmarks/tests/test_mellum.py`).
AUX_RTOL_REHEARSAL = 1e-3
#: (f) the optimiser's step on ALL THREE compared tensors — the first
#: `[L, E, in, out]` stacks and router through `TrainState` and AdamW:
#: |(after - before) - expected| / |expected| of the float32 master,
#: `expected` = -lr (wd before + mhat / (sqrt(vhat) + eps)) from the
#: state's own two moments.  A state left unchanged reads 1 and a step
#: the wrong way 2; the chip read 1.42e-4 on the embedding rows (the
#: float32 rounding of `w - lr u` at |w| ~ 1, lr 3e-4), 3.6e-6 on the
#: router and 2.5e-7 on the expert at most, the same on every seed: the
#: limit leaves 200 x above the reading, where fresh seeds would land,
#: and 33 x under a step not made.  And the bfloat16 copy the next
#: forward reads has to be the new master rounded, element for element:
#: a copy not refreshed keeps the old master's roundings.
UPDATE_DISTANCE_LIMIT = 0.03
#: AdamW as `pretrain.build_llama_pretrain_step` makes it
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8


def model_spec(src: Mapping) -> dict:
    """The `model` table of a run_pretrain config for this file: the
    router covers the PUBLISHED experts, `experts_held` of them live
    here."""
    spec = {k: src[k] for k in MODEL_KEYS}
    spec.update(model_type="mellum",
                num_experts=src["published"]["num_experts"])
    return spec


def pretrain_config(src: Mapping):
    from paddle_tpu.trainer.pretrain import PretrainConfig
    from paddle_tpu.trainer.run_pretrain import _build_model_config
    t = src["trainer"]
    par = t["parallel"]
    mc = _build_model_config(model_spec(src), t["seq_len"])
    # the fields run_pretrain.run passes, by the same names
    return PretrainConfig(
        mc, global_batch=t["global_batch"], seq_len=t["seq_len"],
        n_microbatches=t.get("n_microbatches", 1),
        dp=par.get("dp", 1), mp=par.get("mp", 1), pp=par.get("pp", 1),
        sharding=par.get("sharding", 1), sep=par.get("sep", 1),
        remat=t["remat"], scan_layers=t["scan_layers"],
        ce_chunks=t["ce_chunks"])


#: the reference's name of a layer weight -> the model's state-dict key
LAYER_WEIGHTS = {"ln1": "input_layernorm.weight",
                 "wq": "self_attn.q_proj.weight",
                 "wk": "self_attn.k_proj.weight",
                 "wv": "self_attn.v_proj.weight",
                 "wo": "self_attn.o_proj.weight",
                 "ln2": "post_attention_layernorm.weight",
                 "wr": "mlp.gate_weight", "wg": "mlp.w_gate",
                 "wu": "mlp.w_up", "wd": "mlp.w_down"}


def _layers(stacked, n_layers: int):
    """The reference's view of the trainer's stacked layer weights
    [1, L, ...]: a list of dicts, sliced where it is called (inside the
    reference's jit, so that no second copy is made beside the state)."""
    return [{k: stacked[name][0, i] for k, name in LAYER_WEIGHTS.items()}
            for i in range(n_layers)]


def reference_fn(ref_kw: Mapping, n_layers: int, expert: int, dtype):
    """(stacked, embed, norm, head, ids, labels) -> ((loss, aux), the
    three gradients) of the plain reference, for `jax.jit`."""
    def run(stacked, embed, norm_w, head_w, ids, labels):
        with ref.precision(dtype):
            return ref.value_and_grads(
                embed, _layers(stacked, n_layers), norm_w, head_w, ids,
                labels, expert=expert, dtype=dtype, **ref_kw)
    return run


class System(base.System):
    """`llama_pretrain.System`'s feeding, `put` and `step`; its own
    build, costs and check."""

    def __init__(self, config: Mapping, rehearse: bool, seed: int, devices):
        import jax
        import paddle_tpu as paddle
        from paddle_tpu.io import DataLoader, DistributedBatchSampler
        from paddle_tpu.trainer import run_pretrain
        from paddle_tpu.trainer.pretrain import (build_llama_pretrain_step,
                                                 make_hybrid_mesh_for)
        src = as_run(config, rehearse)
        self.aux_rtol = AUX_RTOL_REHEARSAL if rehearse else AUX_RTOL
        self.cfg = {k: src[k] for k in MODEL_KEYS + ("published",)}
        self.trainer = dict(src["trainer"])
        self.check = dict(src["check"])
        self.pcfg = pretrain_config(src)
        self.family = self.pcfg.model.pretrain_family()
        t0 = time.perf_counter()
        paddle.seed(seed % (2 ** 31))
        self.mesh = make_hybrid_mesh_for(self.pcfg, devices=list(devices))
        with base._host_device():
            self.state, self.jstep, self.meta = build_llama_pretrain_step(
                self.pcfg, self.mesh)
        jax.block_until_ready(self.state.master)
        self.tokens_per_step = (self.trainer["global_batch"]
                                * self.trainer["seq_len"])
        #: the routing counts of every step made, oldest first
        self.routing = []
        self.ref_kw = ref.model_kw(self.cfg, self.trainer["seq_len"],
                                   self.check)
        # the input pipeline of run_pretrain.run: synthetic stream from
        # the seed over the held ids, fixed windows (one document a
        # window, no packing), the seeded sampler, the same collate
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
        say(f"system: {costs.held_params(self.cfg) / 1e9:.3f} B parameters "
            f"held ({self.cfg['experts_held'][1]} of "
            f"{self.cfg['published']['num_experts']} experts a layer), "
            f"trainer {self.trainer}, remat plan "
            f"{self.meta['remat_plan']['layers']}, built in "
            f"{time.perf_counter() - t0:.1f}s")

    @property
    def flops_per_token(self) -> float:
        """`costs_mellum.train_flops_per_token` at the MEASURED held
        pairs a token a layer (the mean over the steps made); before a
        step, at a uniform router's."""
        c = self.cfg
        if self.routing:
            held = sum(r["moe_pairs_held"] for r in self.routing) \
                / len(self.routing) \
                / (self.tokens_per_step * c["num_hidden_layers"])
        else:
            held = (c["num_experts_per_tok"] * c["experts_held"][1]
                    / c["published"]["num_experts"])
        return costs.train_flops_per_token(c, self.trainer["seq_len"], held)

    def step(self, ids, labels) -> float:
        import jax
        self.state, m = self.jstep(self.state, ids, labels)
        m = {k: float(v) for k, v in jax.device_get(m).items()}
        self.routing.append(m)
        return m["loss"]

    # ------------------------------------------------------- correctness
    def check_first_step(self, ids_np, labels_np) -> dict:
        """One step on a batch, held to the plain reference at the same
        weights (the limits above)."""
        t0 = time.perf_counter()
        want = self.reference(ids_np, labels_np)
        t1 = time.perf_counter()
        got = self.first_step(ids_np, labels_np, want["rows"])
        out = self.judge(want, got, labels_np.size)
        out["reference_s"] = t1 - t0
        return out

    def _compared(self, tree, rows):
        """(embedding rows, layer 0's router, the last layer's held
        expert's down projection) of a tree shaped like the master."""
        L = self.cfg["num_hidden_layers"]
        st = tree["stacked"]
        f64 = lambda a: np.asarray(a.astype("float32"),  # noqa: E731
                                   np.float64)
        return {"embed": f64(tree["outer"][self.family.embed_key][rows]),
                "router": f64(st["mlp.gate_weight"][0, 0]),
                "expert_down": f64(st["mlp.w_down"][0, L - 1,
                                                    self.check["expert"]])}

    def first_step(self, ids_np, labels_np, rows) -> dict:
        """What the TIMED path's first step produced: its loss and
        routing numbers and, of the three compared tensors, the master
        before and after, both Adam moments and the bfloat16 copy."""
        before = self._compared(self.state.master, rows)
        loss = self.step(*self.put(ids_np, labels_np))
        opt = self.state.opt_state
        return {"loss": loss, "aux": self.routing[-1]["aux_loss"],
                "before": before,
                "after": self._compared(self.state.master, rows),
                "moments": self._compared(opt.moment1, rows),
                "moments2": self._compared(opt.moment2, rows),
                "copy": self._compared(self.state.params, rows)}

    def update_distance(self, got: Mapping, name: str) -> float:
        """|(after - before) - expected| / |expected| of one compared
        tensor's master, `expected` AdamW's first step from the state's
        own moments (limit (f))."""
        lr, wd = self.pcfg.lr, self.pcfg.weight_decay
        mhat = got["moments"][name] / (1.0 - ADAM_B1)
        vhat = got["moments2"][name] / (1.0 - ADAM_B2)
        want = -lr * (wd * got["before"][name]
                      + mhat / (np.sqrt(vhat) + ADAM_EPS))
        moved = got["after"][name] - got["before"][name]
        return float(np.linalg.norm(moved - want)
                     / max(np.linalg.norm(want), 1e-300))

    def judge(self, want: Mapping, got: Mapping, n_tokens: int) -> dict:
        """The limits above on one step; `failed` names those not met.
        A `got` with no `after` (a reference run in the trainer's place)
        is not held to (f)."""
        import jax.numpy as jnp
        loss_rel = abs(got["loss"] - want["loss"]) / abs(want["loss"])
        loss_limit = LOSS_RTOL_SQRT_TOKENS / n_tokens ** 0.5
        aux_rel = abs(got["aux"] - want["aux"]) / abs(want["aux"])
        out = {"loss": got["loss"], "loss_f32": want["loss"],
               "loss_rel": loss_rel, "loss_limit": loss_limit,
               "loss_bf16_rel": want["loss_bf16_rel"],
               "aux": got["aux"], "aux_f32": want["aux"],
               "aux_rel": aux_rel, "aux_limit": self.aux_rtol,
               "rows": int(len(want["rows"])),
               "update_limit": UPDATE_DISTANCE_LIMIT}
        failed = [n for n, bad in (("loss", not loss_rel <= loss_limit),
                                   ("aux", not aux_rel <= self.aux_rtol))
                  if bad]
        for name, moment in got["moments"].items():
            grad = fit_distance(moment, want["f32"][name])
            noise = want["noise"][name]
            out[f"{name}_grad_distance"] = grad
            out[f"{name}_bf16_distance"] = noise
            out[f"{name}_grad_limit"] = GRAD_MULTIPLE[name]
            if not grad <= GRAD_MULTIPLE[name] * noise:
                failed.append(name + "_grad")
            if "after" not in got:
                continue
            out[f"{name}_update_distance"] = d = \
                self.update_distance(got, name)
            if not d <= UPDATE_DISTANCE_LIMIT:
                failed.append(name + "_update")
            rounded = jnp.asarray(got["after"][name], jnp.float32).astype(
                self.pcfg.param_dtype).astype(jnp.float32)
            if not np.array_equal(got["copy"][name], rounded):
                failed.append(name + "_copy")
        out["failed"] = failed
        out["ok"] = not failed
        return out

    def reference(self, ids_np, labels_np,
                  precisions=("f32", "bf16")) -> dict:
        """The plain reference on one batch at the CURRENT master
        weights, on the first chip: the float32 loss and load-balance
        term, and the gradients of the loss at sequence 0's embedded
        inputs, layer 0's router and the last layer's held expert's down
        projection, in float32 and (the yardstick) in bfloat16.  Rows of
        the embedding that no other position of the batch uses have
        exactly the gradient at their embedded input."""
        import jax
        import jax.numpy as jnp
        c = self.cfg
        m = self.state.master
        outer = m["outer"]
        ids, labels = (jnp.asarray(a, jnp.int32)
                       for a in (ids_np, labels_np))
        ids0 = ids_np[0]
        once = np.flatnonzero(np.bincount(
            ids_np.ravel(), minlength=c["vocab_size"])[ids0] == 1)[:GRAD_ROWS]
        out = {"rows": ids0[once]}
        for key, dtype in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
            if key not in precisions:
                continue
            fn = jax.jit(reference_fn(self.ref_kw, c["num_hidden_layers"],
                                      self.check["expert"], dtype))
            (loss, aux), (gx, gwr, gwd) = fn(
                m["stacked"], outer[self.family.embed_key],
                outer[self.family.norm_key], outer[self.family.head_key],
                ids, labels)
            f64 = lambda a: np.asarray(  # noqa: E731
                jnp.asarray(a, jnp.float32), np.float64)
            out[key] = {"embed": f64(gx[0])[once], "router": f64(gwr),
                        "expert_down": f64(gwd)}
            out["loss" if key == "f32" else "loss_" + key] = float(loss)
            out["aux" if key == "f32" else "aux_" + key] = float(aux)
            del gx, gwr, gwd
        if "bf16" in out and "f32" in out:
            # the yardstick: what bfloat16 alone moves
            out["loss_bf16_rel"] = abs(out["loss_bf16"] - out["loss"]) \
                / abs(out["loss"])
            out["noise"] = {k: fit_distance(out["bf16"][k], out["f32"][k])
                            for k in out["f32"]}
        return out


def _described(config: Mapping, devices):
    """`pretrain.build_llama_pretrain_step` for DESCRIBED devices, from
    shapes only (as `llama_pretrain.compile_for`): the two calls that put
    arrays on the mesh hand back shapes with the same shardings."""
    import jax
    import jax.numpy as jnp
    from unittest import mock
    from paddle_tpu.optimizer.functional import AdamWState, FunctionalAdamW
    from paddle_tpu.trainer import pretrain

    pcfg = pretrain_config(config)
    mesh = pretrain.make_hybrid_mesh_for(pcfg, devices=list(devices)[:1])

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
    return pcfg, state._replace(
        step=jax.ShapeDtypeStruct((), jnp.int32)), jstep, meta


def compile_for(config: Mapping, devices):
    """The step compiled for a DESCRIBED device (the off-chip rehearsal
    of benchmarks/tests/test_mellum.py)."""
    import jax
    import jax.numpy as jnp
    _, state, jstep, meta = _described(config, devices)
    t = config["trainer"]
    spec = jax.ShapeDtypeStruct((t["global_batch"], t["seq_len"]),
                                jnp.int32, sharding=meta["data_sharding"])
    return jstep.lower(state, spec, spec).compile()


def compile_reference_for(config: Mapping, devices, dtype):
    """The plain reference's value_and_grad compiled for a described
    device at the file's sizes, its weights the trainer's master tree."""
    import jax
    import jax.numpy as jnp
    pcfg, state, _, meta = _described(config, devices)
    fam = pcfg.model.pretrain_family()
    t = config["trainer"]
    kw = ref.model_kw(dict(config), t["seq_len"], config["check"])
    fn = jax.jit(reference_fn(kw, config["num_hidden_layers"],
                              config["check"]["expert"], dtype))
    spec = jax.ShapeDtypeStruct((t["global_batch"], t["seq_len"]),
                                jnp.int32, sharding=meta["data_sharding"])
    outer = state.master["outer"]
    return fn.lower(state.master["stacked"], outer[fam.embed_key],
                    outer[fam.norm_key], outer[fam.head_key], spec,
                    spec).compile()
