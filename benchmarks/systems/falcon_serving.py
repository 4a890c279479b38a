"""System under test: ``paddle_tpu.serving.ServingEngine`` over the
Falcon-H1 decoder (`paddle_tpu.models.falcon_h1`: every layer a Mamba-2
state-space mixer AND a rotary GQA mixer on one norm, then a dense
SwiGLU FFN, fourteen muP multipliers on the path) at a configuration
file's sizes — one pipeline stage's layers and an eighth of the
vocabulary — on the programs the engine chooses itself, and its
comparison with the plain reference (`lib/reference_falcon.py`, the
recurrence token by token)."""

from __future__ import annotations

import functools
import time
from typing import Mapping, Sequence

import numpy as np

from ..lib import costs_falcon as costs, reference_falcon as ref
from ..lib.harness import as_run, say
from ..lib.weights import seed_key
from .laguna_serving import _distances, _over

#: `check()` holds the engine to THREE limits.  Two are Laguna's, on the
#: LOGITS the engine sampled each generated token from
#: (`ServingEngine.on_logits`: prompt chunks through the pages and the
#: state pool, then decode steps through both) against the plain float32
#: reference's full forward pass at the same positions
#: (`systems/laguna_serving.py` says why logits, and why these two):
#:
#: 1. TYPICAL: for each sample, the median over its positions of the
#:    root mean square over the vocabulary of (engine - float32), over
#:    the same median of (bfloat16 reference - float32).
#: 2. WORST: the largest |engine - float32| of the run over the standard
#:    deviation of the float32 logits.
#:
#: The third is on the float32 STATE itself, because logits cannot see
#: it (Nemotron's check could not tell a bfloat16 state at 1.5 k tokens:
#: PERF.md section 7):
#:
#: 3. STATE: for each sample, ||S_engine - S_float32|| / ||S_float32||
#:    over the whole [32, 128, 256] state the sample's slot holds in
#:    layer ``check.state_layer`` after its last fed token (read from
#:    the engine's pool; the reference's is its recurrence's last
#:    state), over the same distance of the bfloat16 reference (whose
#:    state is float32 too: the stream's rounding alone); the largest
#:    sample.  A state rounded to bfloat16 after every token drifts by
#:    2^-9 a step in the heads whose memory is long, many times the
#:    stream's noise, which averages out.
#:
#: Each limit is set between readings on the chip
#: (`tools/falcon_limit.py`; PERF.md section 6, PR 54, has every number
#: and its origin).  The planted faults are the float32 reference with
#: ONE of `reference_falcon.ABLATIONS` read against the ENGINE's logits
#: and state (what an engine with that fault would show), and the
#: reference with float8 matrices, the nearest precision below bfloat16.
#: TYPICAL 2.5: the engine reads 1.02-1.05 (the largest sample of a run,
#: seventeen runs; no router here, so no flipped expert and a quiet
#: yardstick, 0.0050-0.0057 by sample); the float8 reference reads
#: 10.2-10.3, a dropped `ssm_multipliers[0]` (the gate z's) 17.1-17.4,
#: `m` in another column order 22.3-23.1, no rotation 34.4-35.1,
#: interleaved rotary pairs 37.9-38.5, a dropped B multiplier 46-47, a
#: dropped `key_multiplier` 59-62, a dropped FFN-gate, embedding, state-
#: or attention-output multiplier 85-137, a dropped `lm_head_multiplier`
#: 12,700 (which the state, below the head, cannot see): 2.4 x of room
#: over the engine, 4 x under the nearest fault.
#: WORST 0.25: the engine reads 0.053-0.065 of a deviation (the
#: yardstick itself 0.050-0.061); the float8 reference 0.52-0.57, the
#: dropped z multiplier 0.88-0.96, the others 1.1-670: 3.8 x over the
#: engine, 2.1 x under float8.
#: STATE 2.0: the engine reads 0.98-1.33 (the stream's rounding alone
#: moves the state by 1.3-1.6 % of its norm, and the engine's is that
#: yardstick's size); the recurrent state rounded to bfloat16 after every
#: token reads 6.4-11.1 with 6,130 tokens behind it (2.75-3.51 at the
#: first sample of 3,060, which is why the cell's longest prompt is
#: checked; 2.1-3.3 at 1,500 and 0.9-1.2 at 250, where it cannot be
#: told) and 1.48-1.53 on TYPICAL, 0.075-0.079 on WORST, as Nemotron's
#: did: ONLY this limit sees it; float8 reads 9.5-11.1, every fault
#: above but the head's 16-78: 1.5 x over the engine's largest, 3.2 x
#: under the bfloat16 state.
#: `attention_in_multiplier` is 1 at this size: dropping it is no fault
#: and no run can show one (`tests/test_falcon_h1.py` catches it at a
#: toy value).
TYPICAL_MULTIPLE = 2.5
WORST_SHARE_OF_SD = 0.25
STATE_MULTIPLE = 2.0

#: keys of the configuration file that are not the model's
NOT_MODEL = ("name", "source", "system", "reference", "architectures",
             "torch_dtype", "published", "reduced", "reduced_notes",
             "assumed", "deployment", "engine", "engine_notes", "check",
             "check_notes", "weights", "pattern_as_run",
             "model_type", "num_logits_to_keep", "mlp_expansion_factor")

#: the draw (see `draw_weights`)
ATTENTION_GAIN = 2.0
GAIN_STD = 0.1
D_STD = 0.2
DT_RANGE = (2e-4, 0.05)
A_RANGE = (1.0, 8.0)


def draw_weights(shapes, seed: int, dtype, depth: int, cfg: Mapping):
    """One array per (name, shape) from the seed, on the device, in the
    type they are served in: Nemotron's draw (matrices Xavier by their
    last two dims, the embedding N(0, 1), what writes into the residual
    stream scaled by 1 / sqrt(2 x depth), the gain on ``q_proj``, gains
    N(1, 0.1), the convolution's weights N(0, 0.3) and bias N(0, 0.1),
    ``D`` N(1, 0.2)) with every matrix DIVIDED by the multipliers the
    program applies around it — the embedding by ``embedding_multiplier``,
    ``W_in``'s column segments by ``ssm_in_multiplier`` x their
    ``ssm_multipliers``, ``k_proj`` by ``key_multiplier``, the three
    projections of attention by ``attention_in_multiplier``, ``o_proj``,
    ``out_proj``, ``gate_proj``, ``down_proj`` and the head by theirs —
    so that with the multipliers APPLIED every product has the scale a
    model trained under them has (the multipliers are tuned for weights
    that large), neither branch vanishes beside the other, and a
    multiplier that is dropped or misplaced moves its product by its
    whole factor (3 to 128 x; `attention_in_multiplier` is 1 here).
    ``dt_bias`` is the inverse softplus of a step log-uniform in
    DT_RANGE and ``A_log`` the log of a uniform draw from A_RANGE: a
    head's memory 1 / (dt A) runs from 2.5 tokens to 5,000, so some
    heads forget inside a chunk and some carry a whole 6 k document.
    Every layer has the same shapes, so ONE jitted draw runs once a
    layer under the layer's own key."""
    import jax
    import jax.numpy as jnp
    mu = ref.multipliers(cfg)
    into_residual = ("out_proj", "o_proj", "down_proj")
    d, gn, nh = (cfg["mamba_d_ssm"],
                 cfg["mamba_n_groups"] * cfg["mamba_d_state"],
                 cfg["mamba_n_heads"])
    w_in_cols = np.concatenate([
        np.full(w, 1.0 / (mu["ssm_in"] * mu[k]), np.float32)
        for w, k in zip((d, d, gn, gn, nh),
                        ("ssm_z", "ssm_x", "ssm_B", "ssm_C", "ssm_dt"))])
    over = {"embed_tokens": mu["embedding"], "lm_head": mu["lm_head"],
            "q_proj": mu["attention_in"],
            "k_proj": mu["attention_in"] * mu["key"],
            "v_proj": mu["attention_in"], "o_proj": mu["attention_out"],
            "out_proj": mu["ssm_out"], "gate_proj": mu["mlp_gate"],
            "down_proj": mu["mlp_down"]}

    def one(key, name, shape):
        f32 = jnp.float32
        if name.endswith("dt_bias"):
            dt = jnp.exp(jax.random.uniform(
                key, shape, f32, *np.log(DT_RANGE)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if name.endswith("A_log"):
            return jnp.log(jax.random.uniform(key, shape, f32, *A_RANGE))
        normal = jax.random.normal(key, shape, f32)
        if name.endswith(".D"):
            return 1.0 + D_STD * normal
        if name.endswith("conv_bias"):
            return 0.1 * normal
        if name.endswith("conv_weight"):
            return 0.3 * normal
        if len(shape) < 2:
            return 1.0 + GAIN_STD * normal
        std = float(np.sqrt(2.0 / (shape[-2] + shape[-1])))
        if "embed_tokens" in name:
            std = 1.0
        elif any(k in name for k in into_residual):
            std /= float(np.sqrt(2.0 * depth))
        elif "q_proj" in name:
            std *= ATTENTION_GAIN
        if "in_proj" in name:
            return std * normal * w_in_cols
        for k, m in over.items():
            if k in name:
                std /= m
        return std * normal

    @functools.partial(jax.jit, static_argnums=1)
    def build(key, group):
        return {name: one(jax.random.fold_in(key, i), name,
                          shape).astype(dtype)
                for i, (name, shape) in enumerate(group)}

    groups = {}
    for name, shape in shapes:
        parts = name.split(".")
        at = parts.index("layers") + 1 if "layers" in parts else None
        which = int(parts[at]) if at else -1
        if at:
            parts[at] = "#"
        groups.setdefault(which, []).append((".".join(parts), shape))
    out = {}
    for which, group in sorted(groups.items()):
        drawn = build(jax.random.fold_in(seed_key(seed), which + 1),
                      tuple(group))
        out.update({n.replace(".#.", f".{which}."): a
                    for n, a in drawn.items()})
    return out


def model_kwargs(src: Mapping) -> dict:
    """`models.falcon_h1.falcon_h1_config` arguments from a configuration
    file as run: the published keys; ``num_hidden_layers`` and
    ``vocab_size`` are what this chip HOLDS (both under ``reduced``)."""
    return {k: v for k, v in src.items() if k not in NOT_MODEL}


class System:
    def __init__(self, config: Mapping, rehearse: bool, seed: int):
        import jax
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.models.falcon_h1 import (FalconH1ForCausalLM,
                                                 falcon_h1_config)
        from paddle_tpu.serving import ServingEngine

        src = as_run(config, rehearse)
        kw = model_kwargs(src)
        #: what `reference_falcon` and `costs_falcon` read: the published
        #: names as run
        self.cfg = dict(kw)
        self.engine_args = dict(src["engine"])
        self.check_args = dict(src.get("check", {}))
        self.dtype = jnp.bfloat16
        t0 = time.perf_counter()
        paddle.seed(seed % (2 ** 31))
        # no float32 parameter is ever made: the layers are built lazily
        # and every parameter is bound to a bfloat16 array drawn on the
        # device from the seed
        with paddle.LazyGuard():
            model = FalconH1ForCausalLM(falcon_h1_config(**kw))
        model.eval()
        named = list(model.named_parameters())
        drawn = draw_weights([(n, tuple(p._data.shape)) for n, p in named],
                             seed, self.dtype, kw["num_hidden_layers"], kw)
        for n, p in named:
            p._data = drawn[n]
        del drawn
        jax.block_until_ready([p._data for _, p in named])
        t1 = time.perf_counter()
        self.model = model
        self.engine = ServingEngine(model, **self.engine_args)
        eng = self.engine
        self.weight_bytes = sum(int(np.prod(p._data.shape)) * 2
                                for _, p in named)
        self.paths = {"ragged": eng.ragged, "megafront": eng.megafront,
                      "megadecode": eng.megadecode,
                      "front_half_launches": eng.front_half_launches,
                      "back_half_launches": eng.back_half_launches}
        acct = eng.hbm_accounting()
        say(f"system: weights {self.weight_bytes / 1e9:.3f} GB "
            f"({costs.n_params(self.cfg) / 1e9:.3f} B parameters held; "
            f"{acct['weights_bytes'] / 1e9:.3f} GB resident) in "
            f"{t1 - t0:.1f}s; engine {self.engine_args} in "
            f"{time.perf_counter() - t1:.1f}s; paths {self.paths}; state "
            f"pool {acct['state_pool_bytes'] / 1e9:.3f} GB ("
            f"{eng.max_slots} + 1 slots x {costs.state_bytes(self.cfg)} B "
            f"x {self.cfg['num_hidden_layers']} layers, stored "
            f"{list(eng._pools['ssm'][0][0].shape)}), pages "
            f"{(acct['page_pool_bytes'] - acct['state_pool_bytes']) / 1e9:.3f}"
            f" GB ({eng.num_pages} x {eng.page_size} tokens x "
            f"{costs.kv_row_bytes(self.cfg)} B x "
            f"{self.cfg['num_hidden_layers']} layers)")
        # the plain reference reads the model's own arrays, layer by layer
        self._ref_weights = {
            "embed": model.model.embed_tokens.weight._data,
            "norm": model.model.final_layernorm.weight._data,
            "head": model.lm_head.weight._data,
            "layers": model_layers(model)}
        self.vocab = kw["vocab_size"]
        self.max_total = eng.max_context
        # the logits row behind every token of the warm-up sample and the
        # slot it was served in, by request; `check()` takes the hook off
        # again, so the measured window keeps nothing
        self._rows, self._slots = {}, {}
        eng.on_logits = self._keep

    def _keep(self, req, row):
        self._rows.setdefault(req.request_id, []).append(
            np.asarray(row, np.float32))
        self._slots[req.request_id] = int(req.slot)

    # ------------------------------------------------------- correctness
    @property
    def state_layer(self) -> int:
        at = int(self.check_args.get("state_layer", -1))
        return at % self.cfg["num_hidden_layers"]

    def _reference(self, samples, dtype, ablate=frozenset(), operands=None):
        """For each sample (logits [outputs, vocabulary] at the positions
        the engine generated from, the state [H, P, N] of the checked
        layer after the last fed token), teacher-forced over prompt +
        output."""
        import jax.numpy as jnp
        blocks = {k: int(self.check_args.get(k, 0))
                  for k in ("q_block", "ffn_block")}
        w = self._ref_weights
        lm = ref._mults(ref.spec(self.cfg, ablate))["lm_head"]
        out = []
        for s in samples:
            n0, n1 = len(s["prompt"]), len(s["output"])
            fed = np.concatenate([s["prompt"], s["output"][:-1]])
            x, state = ref.hidden_states(
                jnp.asarray(fed, jnp.int32), w["embed"], w["layers"],
                self.cfg, dtype, ablate=ablate, operands=operands,
                state_of=self.state_layer, **blocks)
            rows = slice(n0 - 1, n0 - 1 + n1)
            out.append((np.asarray(ref.head_logits(
                x[rows], w["norm"], w["head"],
                eps=float(self.cfg["rms_norm_eps"]), dtype=dtype, mult=lm)),
                np.asarray(state, np.float32)))
        return out

    def engine_states(self, slots: Sequence[int]) -> list:
        """The checked layer's state [H, P, N] of each slot, as the
        engine's pool holds it (`ops.pallas_ssm`: state-minor, or
        heads-minor [P, N, H] turned)."""
        from paddle_tpu.ops.pallas_ssm import STATE_MINOR
        eng = self.engine
        pool = eng._pools["ssm"][self.state_layer][0]
        got = [np.asarray(pool[s], np.float32) for s in slots]
        if eng._state_layout != STATE_MINOR:
            got = [g.transpose(2, 0, 1) for g in got]
        return got

    def check(self, samples: Sequence[Mapping]) -> dict:
        """``samples``: {"prompt": ids, "output": the engine's tokens},
        in the order they were given to the engine.  Teacher-forces the
        plain float32 reference over prompt + output and holds the
        logits the engine sampled from to TYPICAL_MULTIPLE and
        WORST_SHARE_OF_SD and the state the sample's slot was left with
        to STATE_MULTIPLE."""
        import jax.numpy as jnp
        self.engine.on_logits = None
        ids = sorted(self._rows)
        got = [np.stack(self._rows[k]) for k in ids]
        states = self.engine_states([self._slots[k] for k in ids])
        self._rows, self._slots = {}, {}
        if len(got) != len(samples) or any(
                not np.array_equal(g.argmax(-1), s["output"])
                for g, s in zip(got, samples)):
            raise RuntimeError("the logits kept are not the samples'")
        with ref.highest():
            f32 = self._reference(samples, jnp.float32)
        bf16 = self._reference(samples, jnp.bfloat16)
        yard = _distances([b[0] for b in bf16], [f[0] for f in f32])
        yard["sd"] = float(np.concatenate([f[0] for f in f32]).std())
        yard["state"] = _state_distances([b[1] for b in bf16],
                                         [f[1] for f in f32])
        read = _over(_distances(got, [f[0] for f in f32]), yard)
        state = _state_over(_state_distances(states, [f[1] for f in f32]),
                            yard)
        checked = int(sum(len(g) for g in got))
        out = {"ok": bool(checked > 0
                          and read["typical"] <= TYPICAL_MULTIPLE
                          and read["worst"] <= WORST_SHARE_OF_SD
                          and state["state"] <= STATE_MULTIPLE),
               "checked": checked,
               "typical_over_noise": read["typical"],
               "worst_over_sd": read["worst"],
               "state_over_noise": state["state"],
               "typical_by_sample": read["by_sample"],
               "state_by_sample": state["state_by_sample"],
               "noise_typical_rms": yard["typical"],
               "noise_worst_over_sd": yard["worst"] / yard["sd"],
               "noise_state_rel": yard["state"],
               "logits_sd": yard["sd"], "state_layer": self.state_layer,
               "limits": [TYPICAL_MULTIPLE, WORST_SHARE_OF_SD,
                          STATE_MULTIPLE]}
        faults = self.check_args.get("planted_faults")
        if faults:
            # `tools/falcon_limit.py`: what has to come out as NOT correct
            with ref.highest():
                for what in faults:
                    off = self._reference(samples, jnp.float32,
                                          ablate=frozenset([what]))
                    out["fault_" + what] = dict(
                        _over(_distances(got, [o[0] for o in off]), yard),
                        **_state_over(_state_distances(
                            states, [o[1] for o in off]), yard))
            f8 = self._reference(samples, jnp.bfloat16,
                                 operands=jnp.float8_e4m3fn)
            out["float8_reference"] = dict(
                _over(_distances([o[0] for o in f8],
                                 [f[0] for f in f32]), yard),
                **_state_over(_state_distances(
                    [o[1] for o in f8], [f[1] for f in f32]), yard))
        return out


def _state_distances(got, want) -> list:
    """||got - want|| / ||want|| of each sample's state."""
    return [float(np.linalg.norm(np.asarray(g, np.float64) - w)
                  / max(np.linalg.norm(np.asarray(w, np.float64)), 1e-30))
            for g, w in zip(got, want)]


def _state_over(dist, yard) -> dict:
    by = [d / max(y, 1e-12) for d, y in zip(dist, yard["state"])]
    return {"state": max(by), "state_by_sample": by}


def model_layers(model) -> list:
    """`reference_falcon`'s weight names over the model's own arrays."""
    out = []
    for lyr in model.model.layers:
        w = {"norm1": lyr.input_layernorm.weight._data,
             "norm2": lyr.pre_ff_layernorm.weight._data}
        for part in (lyr.mamba, lyr.self_attn, lyr.feed_forward):
            w.update({k: v._data for k, v in part.weights().items()})
        out.append(w)
    return out
