"""System under test: ``paddle_tpu.serving.ServingEngine`` over the Ouro
looped decoder (`paddle_tpu.models.ouro`: 48 layers run 4 times a token
with one set of weights, a cache row for every pass of every layer) at
a configuration file's sizes — the WHOLE model on one chip — on the
programs the engine chooses itself, and its comparison with the plain
reference (`lib/reference_ouro.py`)."""

from __future__ import annotations

import functools
import time
from typing import Mapping, Sequence

import numpy as np

from ..lib import costs_ouro as costs, reference_ouro as ref
from ..lib.harness import as_run, say
from ..lib.weights import seed_key
from .laguna_serving import _distances, _over

#: `check()` is Laguna's kind (`systems/laguna_serving.py` says why in
#: full): the LOGITS the engine sampled each generated token from
#: (`ServingEngine.on_logits`) against the plain float32 reference's at
#: the same position, under two limits.
#:
#: 1. TYPICAL: for each sample, the median over its positions of the
#:    root mean square over the 49,152 logits of (engine - float32),
#:    over the same median of (bfloat16 reference - float32).
#: 2. WORST: the largest |engine - float32| of the run over the standard
#:    deviation of the float32 logits.
#:
#: Each limit is set from readings on the chip (my chip runs, PR 39:
#: `tools/ouro_limit.py` on seeds 2147493001, 12, 13, 72 positions a
#: seed, and the cell's own runs; PERF.md section 6 has every number).
#: The planted faults are the float32 reference with ONE of
#: `reference_ouro.ABLATIONS` or a cache of one slot a layer
#: (`shared_slot_states`), read against the ENGINE's logits (what an
#: engine with that fault would show), and the reference with float8
#: matrices, the nearest precision below bfloat16 (the bfloat16
#: reference IS the yardstick: it reads 1).
#:
#: TYPICAL: the engine 0.71-0.74 (its matmuls accumulate in float32 and
#: round once; the yardstick rounds every operand).  The faults, the
#: smallest of a seed's three samples: three passes 7.5-7.7, float8
#: 11.1-11.5, no norm between passes 10.9-11.1, pass u reading pass
#: u - 1's rows 14.1-14.2, one slot a layer 15.0-15.2, the output norms
#: dropped 16.3-16.6, the embedding re-injected 18.4-18.7, one pass
#: 20.2-20.6.  The limit stands at 2.0: 2.7 x the engine's largest,
#: 3.7 x under the smallest fault.
#:
#: WORST: the engine 0.199-0.218 of the logits' deviation (the
#: yardstick itself 0.28-0.29: 192 softmaxes feed each other, and no
#: routed layer flips).  The faults: three passes 2.11-2.17, no norm
#: between passes 2.84-3.24, float8 2.97-3.15, the others 3.87-6.11.
#: The limit stands at 0.7: 3.2 x the engine's largest, 3 x under the
#: smallest fault.  Every fault fails BOTH limits.
TYPICAL_MULTIPLE = 2.0
WORST_SHARE_OF_SD = 0.7

#: the published keys the model and the reference are built from
PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "max_position_embeddings", "rms_norm_eps", "rope_theta",
    "rope_scaling", "hidden_act", "tie_word_embeddings", "sliding_window",
    "total_ut_steps", "early_exit_threshold")

#: ``q_proj`` is drawn at this many times Xavier (attention scores of
#: deviation ~2): with Xavier alone a softmax over hundreds of rows is
#: near-uniform and no logit can tell which rows were there; at Laguna's
#: 4 the scores' bfloat16 rounding, taken through 192 softmaxes that
#: feed each other, parts the bfloat16 evaluation from the float32 one
#: (read here at the real widths, PR 39: the yardstick's distance 0.21
#: of the logits' deviation at 4, 0.055 at 2, 0.032 at 1, with every
#: fault 2.4-4.5 / 7.6-13.7 / 11.5-19.8 times it).  The two INPUT gains
#: a layer and the last norm's are N(1, 0.1); the exit gate's vector
#: N(0, 1 / sqrt(hidden)) over a normed state, so lambda's logit has
#: deviation ~1, and its bias N(0, 1).
ATTENTION_GAIN = 2.0
GAIN_STD = 0.1
#: a sandwich norm undoes the scale of ``o_proj`` / ``down_proj``: what
#: a sublayer writes into the residual stream is set by its OUTPUT
#: norm's gain.  Drawn N(1, 0.1) like the others, 96 unit vectors a pass
#: bury the embedding, every rounding is renormed to full size 384
#: times, and the float32 and bfloat16 evaluations part completely (read
#: on the chip, PR 39: the yardstick's distance 0.25-0.30 against logits
#: of deviation 0.28, WORST 5.2 deviations).  So the two output gains
#: carry the 1 / sqrt(2 x depth) that Laguna's draw gives the matrices:
#: N(OUTPUT_GAIN / sqrt(96), 10 % of it).
OUTPUT_GAIN = 1.0


def draw_weights(shapes, seed: int, dtype, depth: int):
    """One array per (name, shape) from the seed, on the device, in the
    type they are served in: Laguna's draw (matrices Xavier, the
    embedding N(0, 1), what writes into the residual stream scaled by
    1 / sqrt(2 x depth), the gain on ``q_proj``), and this family's
    vectors as the constants above.  Every layer has the same shapes, so
    ONE jitted draw of a layer runs once a layer under the layer's own
    key (533 arrays in one program took 330 s to compile cold)."""
    import jax
    import jax.numpy as jnp

    def mean_std(name, shape):
        if "early_exit_gate" in name:
            return 0.0, (1.0 if len(shape) < 2
                         else 1.0 / float(np.sqrt(shape[0])))
        if name.endswith("layernorm_2.weight"):
            return OUTPUT_GAIN / float(np.sqrt(2.0 * depth)), \
                OUTPUT_GAIN * GAIN_STD / float(np.sqrt(2.0 * depth))
        if len(shape) < 2:
            return 1.0, GAIN_STD
        std = float(np.sqrt(2.0 / (shape[-2] + shape[-1])))
        if "embed_tokens" in name:
            return 0.0, 1.0
        if "o_proj" in name or "down_proj" in name:
            return 0.0, std / float(np.sqrt(2.0 * depth))
        return 0.0, std * ATTENTION_GAIN if "q_proj" in name else std

    @functools.partial(jax.jit, static_argnums=1)
    def build(key, group):
        out = {}
        for i, (name, shape) in enumerate(group):
            mean, std = mean_std(name, shape)
            out[name] = (mean + std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            ).astype(dtype)
        return out

    # a layer's parameters under the layer's number taken out of their
    # names: every layer is then the same static argument, one compile
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
    """`OuroConfig` arguments from a configuration file as run."""
    kw = {k: src[k] for k in PUBLISHED_KEYS}
    kw["rope_positions"] = src["engine"]["max_context"]
    return kw


def launches_of(n_prompt: int, n_fed: int, chunk: int) -> list:
    """Rows of each launch that carries a sequence of ``n_prompt``
    prompt tokens and ``n_fed`` fed-back ones: the prompt's chunks, then
    one token at a time."""
    full, rest = divmod(n_prompt, chunk)
    return [chunk] * full + [rest] * bool(rest) + [1] * n_fed


class System:
    def __init__(self, config: Mapping, rehearse: bool, seed: int):
        import jax
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM
        from paddle_tpu.serving import ServingEngine

        src = as_run(config, rehearse)
        kw = model_kwargs(src)
        self.cfg = {k: v for k, v in kw.items() if k != "rope_positions"}
        self.engine_args = dict(src["engine"])
        self.check_args = dict(src.get("check", {}))
        self.dtype = jnp.bfloat16
        t0 = time.perf_counter()
        paddle.seed(seed % (2 ** 31))
        # no float32 parameter is ever made: the layers are built lazily
        # and every parameter is bound to a bfloat16 array drawn on the
        # device from the seed, in one jitted call
        with paddle.LazyGuard():
            model = OuroForCausalLM(OuroConfig(**kw))
        model.eval()
        named = list(model.named_parameters())
        drawn = draw_weights([(n, tuple(p._data.shape)) for n, p in named],
                             seed, self.dtype, kw["num_hidden_layers"])
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
            f"({costs.n_params(self.cfg) / 1e9:.3f} B parameters, held "
            f"once: {acct['weights_bytes'] / 1e9:.3f} GB resident) in "
            f"{t1 - t0:.1f}s; engine {self.engine_args} in "
            f"{time.perf_counter() - t1:.1f}s; paths {self.paths}; "
            f"{eng.num_pages} page ids x {eng.page_size} rows x "
            f"{costs.token_bytes(self.cfg)} B a token over "
            f"{costs.slots(self.cfg)} (pass, layer) slots = "
            f"{acct['page_pool_bytes'] / 1e9:.3f} GB of pools")
        # the plain reference reads the model's own arrays, layer by layer
        gate = model.model.early_exit_gate
        self._ref_weights = {
            "embed": model.model.embed_tokens.weight._data,
            "norm": model.model.norm.weight._data,
            "gate_w": gate.weight._data[:, 0], "gate_b": gate.bias._data[0],
            "head": model.lm_head.weight._data,
            "layers": model_layers(model)}
        self.vocab = kw["vocab_size"]
        self.max_total = eng.max_context
        # the logits row behind every token of the warm-up sample, by
        # request; `check()` takes the hook off again, so the measured
        # window keeps nothing
        self._rows = {}
        eng.on_logits = lambda req, row: self._rows.setdefault(
            req.request_id, []).append(np.asarray(row, np.float32))

    # ------------------------------------------------------- correctness
    def _reference(self, samples, dtype, ablate=frozenset(), operands=None,
                   shared_slot=False):
        """For each sample the logits [outputs, vocabulary] at the
        positions the engine generated from, teacher-forced over prompt
        + output."""
        import jax.numpy as jnp
        blocks = {k: int(self.check_args.get(k, 0))
                  for k in ("q_block", "ffn_block")}
        w = self._ref_weights
        unit = blocks["q_block"] or 1
        out = []
        for s in samples:
            n0, n1 = len(s["prompt"]), len(s["output"])
            fed = np.concatenate([s["prompt"], s["output"][:-1]])
            if shared_slot:
                h = ref.shared_slot_states(
                    jnp.asarray(fed, jnp.int32), w, self.cfg,
                    launches_of(n0, n1 - 1, self.engine.prefill_chunk),
                    dtype, ffn_block=blocks["ffn_block"])
            else:
                ids = np.zeros(-(-len(fed) // unit) * unit, np.int32)
                ids[:len(fed)] = fed
                h = ref.pass_states(jnp.asarray(ids), w, self.cfg, dtype,
                                    ablate=ablate, operands=operands,
                                    **blocks)[-1]
            out.append(np.asarray(ref.head_logits(
                h[n0 - 1:n0 - 1 + n1], w["head"], dtype=dtype)))
        return out

    def check(self, samples: Sequence[Mapping]) -> dict:
        """``samples``: {"prompt": ids, "output": the engine's tokens},
        in the order they were given to the engine.  Teacher-forces the
        plain float32 reference over prompt + output and holds the
        logits the engine sampled from to TYPICAL_MULTIPLE and
        WORST_SHARE_OF_SD."""
        import jax.numpy as jnp
        self.engine.on_logits = None
        got = [np.stack(self._rows[k]) for k in sorted(self._rows)]
        self._rows = {}
        if len(got) != len(samples) or any(
                not np.array_equal(g.argmax(-1), s["output"])
                for g, s in zip(got, samples)):
            raise RuntimeError("the logits kept are not the samples'")
        with ref.highest():
            f32 = self._reference(samples, jnp.float32)
        bf16 = self._reference(samples, jnp.bfloat16)
        yard = _distances(bf16, f32)
        yard["sd"] = float(np.concatenate(f32).std())
        read = _over(_distances(got, f32), yard)
        checked = int(sum(len(g) for g in got))
        out = {"ok": bool(checked > 0
                          and read["typical"] <= TYPICAL_MULTIPLE
                          and read["worst"] <= WORST_SHARE_OF_SD),
               "checked": checked,
               "typical_over_noise": read["typical"],
               "worst_over_sd": read["worst"],
               "typical_by_sample": read["by_sample"],
               "noise_typical_rms": yard["typical"],
               "noise_worst_over_sd": yard["worst"] / yard["sd"],
               "logits_sd": yard["sd"],
               "limits": [TYPICAL_MULTIPLE, WORST_SHARE_OF_SD]}
        if self.check_args.get("planted_faults"):
            # `tools/ouro_limit.py`: what has to come out as NOT correct
            with ref.highest():
                for what in ref.ABLATIONS:
                    off = self._reference(samples, jnp.float32,
                                          ablate=frozenset([what]))
                    out["fault_" + what] = _over(_distances(got, off), yard)
                off = self._reference(samples, jnp.float32,
                                      shared_slot=True)
                out["fault_shared_slot"] = _over(_distances(got, off), yard)
            f8 = self._reference(samples, jnp.bfloat16,
                                 operands=jnp.float8_e4m3fn)
            out["float8_reference"] = _over(_distances(f8, f32), yard)
        return out


def model_layers(model) -> list:
    """`reference_ouro`'s weight names over the model's own arrays."""
    out = []
    for lyr in model.model.layers:
        a, m = lyr.self_attn, lyr.mlp
        out.append({
            "ln1": lyr.input_layernorm.weight._data,
            "wq": a.q_proj.weight._data, "wk": a.k_proj.weight._data,
            "wv": a.v_proj.weight._data, "wo": a.o_proj.weight._data,
            "ln1_out": lyr.input_layernorm_2.weight._data,
            "ln2": lyr.post_attention_layernorm.weight._data,
            "wg": m.gate_proj.weight._data, "wu": m.up_proj.weight._data,
            "wd": m.down_proj.weight._data,
            "ln2_out": lyr.post_attention_layernorm_2.weight._data})
    return out
