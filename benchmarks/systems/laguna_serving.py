"""System under test: ``paddle_tpu.serving.ServingEngine`` over
``LagunaForCausalLM`` at a configuration file's sizes — one chip's share
of an expert-parallel deployment — with the paths the engine chooses
itself, and its comparison with the plain reference."""

from __future__ import annotations

import time
from typing import Mapping, Sequence

import numpy as np

from ..lib import costs_laguna as costs, reference_laguna as ref
from ..lib.harness import as_run, say
from ..lib.weights import seed_key

#: `check()` compares LOGITS: the row the engine sampled each generated
#: token from (`ServingEngine.on_logits`) against the plain float32
#: reference's at the same position, and holds two distances between
#: them to a limit each: one to the same distance of a correct bfloat16
#: evaluation of the reference, one to the logits' own spread.  Random
#: weights give near-flat logits, so equal tokens are
#: the wrong test (PR 21), and a token's lead cannot see a mechanism that
#: moves every logit a little (the window: REVIEW of PR 28).
#:
#: 1. TYPICAL: for each sample, the median over its positions of the
#:    root mean square over the vocabulary of (engine - float32), over
#:    the same median of (bfloat16 reference - float32).  A mechanism
#:    that is missing or wrong moves EVERY position of a sample it acts
#:    in; a routed layer's rare events do not move a median.
#: 2. WORST: the largest |engine - float32| over every position and
#:    logit of the run, over the standard deviation of the float32
#:    logits.  It catches what goes wrong at few positions, which a
#:    median does not see.  Its yardstick is NOT a bfloat16 evaluation:
#:    where two router probabilities nearly tie, a bfloat16 evaluation
#:    sends the token to another expert than float32 does
#:    (`routing_flip_share`), and the logits there move by a whole
#:    expert's worth, many times the rounding noise.  The engine and the
#:    bfloat16 reference flip at DIFFERENT positions, and whether either
#:    meets a large flip among a run's 72 positions is chance: the ratio
#:    of their largest distances swung between 0.3 and 6.5 over seeds at
#:    toy size.  The largest distance itself, in units of the logits'
#:    own spread, does not.
#:
#: Each limit lies between readings on the chip (`tools/laguna_limit.py`,
#: my chip runs, PR 28; four seeds, 72 positions each; PERF.md section
#: 6): above the engine's largest over its seeds, below the smallest of
#: the planted faults (the reference with the window, the head gate or
#: the routed scale switched off, read against the ENGINE's logits: what
#: an engine that lost the mechanism would show) and of the reference
#: with float8 operands, the nearest precision below the
#: configuration's.  TYPICAL: the engine 0.91-0.96; without the routed
#: scale 7.6-8.3, float8 9.2-9.3, without the window 22.1-23.4 (in the
#: 16 k and the 1.5 k sample, and the engine's own 0.9 in the 300-token
#: one, which never leaves the window), without the gate 34.5-35.6.
#: WORST: the engine 0.13-0.18 (the bfloat16 reference itself 0.12-0.27:
#: a flip's worth); float8 0.52-0.59, without the scale 0.63-0.78, the
#: window 1.37-1.44, the gate 1.95-2.09.  Float8 comes out as not
#: correct by TYPICAL with room; by WORST only just, which is why WORST
#: stands at twice the largest flip seen and is not the limit that
#: decides a precision.
TYPICAL_MULTIPLE = 2.0
WORST_SHARE_OF_SD = 0.5

#: the published keys the model and the reference are built from
PUBLISHED_KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "max_position_embeddings", "rms_norm_eps", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "norm_topk_prob", "mlp_only_layers", "sliding_window",
    "rope_parameters", "layer_types", "num_attention_heads_per_layer",
    "moe_routed_scaling_factor", "gating", "tie_word_embeddings")


#: ``q_proj`` is drawn at this many times Xavier.  With Xavier alone the
#: attention scores have a standard deviation of 0.9: the softmax over a
#: long context is near-uniform, its output the mean of thousands of
#: random values, ~1 / sqrt(keys) of one, and NO comparison of logits can
#: tell 512 keys from 16,000 (the reference at these widths without its
#: window moved the logits by 3 x the bfloat16 rounding noise, a token by
#: nothing: REVIEW of PR 28).  At 4 x the scores' deviation is ~3.5, a
#: query attends to a few keys as in a trained model, and the same fault
#: moves every logit of a long sample by ~18 x the noise (PERF.md, PR 28).
ATTENTION_GAIN = 4.0


def draw_weights(shapes, seed: int, dtype, depth: int):
    """One array per (name, shape) from the seed, on the device in ONE
    jitted call, in the type they are served in.  As ``lib/weights.py``
    draws them — vectors ones, matrices Xavier N(0, 2 / (fan_in +
    fan_out)), the router N(0, 0.02) — with four departures, so that
    the random model is conditioned like a trained one, every mechanism
    the check has to see carries signal, and a bfloat16 rounding or one
    flipped expert stays a small perturbation instead of growing through
    the layers (PERF.md, PR 28): expert stacks [E, in, out] take their
    fans from (in, out); the embedding is N(0, 1), a residual stream of
    unit size; every projection that writes into the residual stream
    (``o_proj``, ``down_proj``, ``w_down``, ``shared_down``) is scaled
    by 1 / sqrt(2 x depth), the usual residual scaling; ``q_proj`` by
    ATTENTION_GAIN."""
    import jax
    import jax.numpy as jnp
    into_residual = ("o_proj", "down_proj", "w_down", "shared_down")

    def build(key):
        out = {}
        for i, (name, shape) in enumerate(shapes):
            if len(shape) < 2:
                out[name] = jnp.ones(shape, dtype)
                continue
            std = float(np.sqrt(2.0 / (shape[-2] + shape[-1])))
            if "embed_tokens" in name:
                std = 1.0
            elif "gate_weight" in name:
                std = 0.02
            elif any(k in name for k in into_residual):
                std /= float(np.sqrt(2.0 * depth))
            elif "q_proj" in name:
                std *= ATTENTION_GAIN
            out[name] = (jax.random.normal(jax.random.fold_in(key, i),
                                           shape, jnp.float32)
                         * std).astype(dtype)
        return out

    return jax.jit(build)(seed_key(seed))


def model_kwargs(src: Mapping) -> dict:
    """`LagunaConfig` arguments from a configuration file as run.  The
    file's ``num_experts`` and ``vocab_size`` are what this chip HOLDS
    (both under ``reduced``); the router keeps the published width."""
    kw = {k: src[k] for k in PUBLISHED_KEYS}
    first, count = src["experts_held"]
    if count != src["num_experts"]:
        raise ValueError("experts_held and num_experts disagree")
    kw.update(vocab_size=src["vocab_size"],
              num_experts=src["published"]["num_experts"],
              experts_held=(first, count),
              rope_positions=src["engine"]["max_context"])
    return kw


def reference_config(c) -> dict:
    """What `reference_laguna` reads, from a `LagunaConfig` (or the
    `model_kwargs` of a file)."""
    c = c if isinstance(c, Mapping) else vars(c)
    keys = ("num_hidden_layers", "layer_types", "mlp_only_layers",
            "num_attention_heads_per_layer", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "sliding_window",
            "num_experts_per_tok", "norm_topk_prob",
            "moe_routed_scaling_factor", "experts_held",
            "rope_parameters")
    return {k: c[k] for k in keys}


class System:
    def __init__(self, config: Mapping, rehearse: bool, seed: int):
        import jax
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.models.laguna import LagunaConfig, LagunaForCausalLM
        from paddle_tpu.serving import ServingEngine

        src = as_run(config, rehearse)
        kw = model_kwargs(src)
        self.cfg = kw
        self.ref_cfg = reference_config(kw)
        self.engine_args = dict(src["engine"])
        self.check_args = dict(src.get("check", {}))
        self.dtype = jnp.bfloat16
        t0 = time.perf_counter()
        paddle.seed(seed % (2 ** 31))
        # no float32 parameter is ever made: the layers are built lazily
        # and every parameter is bound to a bfloat16 array drawn on the
        # device from the seed, in one jitted call
        with paddle.LazyGuard():
            model = LagunaForCausalLM(LagunaConfig(**kw))
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
        say(f"system: weights {self.weight_bytes / 1e9:.3f} GB "
            f"({costs.n_params(self.cfg) / 1e9:.3f} B parameters held) in "
            f"{t1 - t0:.1f}s; engine {self.engine_args} in "
            f"{time.perf_counter() - t1:.1f}s; paths {self.paths}; pools "
            f"{eng.num_pages} full + {eng.num_window_pages} window pages")
        # the plain reference reads the model's own arrays (not the
        # engine's permuted or concatenated copies), layer by layer
        self._ref_weights = {
            "embed": model.model.embed_tokens.weight._data,
            "norm": model.model.norm.weight._data,
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
    def _reference(self, samples, dtype, ablate=frozenset(), operands=None):
        """For each sample (logits [outputs, vocabulary] at the positions
        the engine generated from, the experts each sparse layer routed
        to there), teacher-forced over prompt + output."""
        import jax.numpy as jnp
        blocks = {k: int(self.check_args.get(k, 0))
                  for k in ("q_block", "head_block")}
        w = self._ref_weights
        out = []
        for s in samples:
            # one sample at a time at its own width (a multiple of 128,
            # so a few shapes serve every seed); the head only where
            # the engine generated
            n0, n1 = len(s["prompt"]), len(s["output"])
            ids = np.zeros(-(-(n0 + n1) // 128) * 128, np.int32)
            ids[:n0 + n1] = np.concatenate([s["prompt"], s["output"]])
            x, routed = ref.hidden_states(
                jnp.asarray(ids), w["embed"], w["layers"], self.ref_cfg,
                dtype, ablate=ablate, operands=operands, **blocks)
            rows = slice(n0 - 1, n0 - 1 + n1)
            out.append((np.asarray(ref.head_logits(
                x[rows], w["norm"], w["head"],
                eps=self.ref_cfg["rms_norm_eps"], dtype=dtype)),
                np.stack([np.sort(np.asarray(r[rows]), -1)
                          for r in routed])))
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
        yard = _distances([b[0] for b in bf16], [f[0] for f in f32])
        yard["sd"] = float(np.concatenate([f[0] for f in f32]).std())
        read = _over(_distances(got, [f[0] for f in f32]), yard)
        flips = [np.any(f[1] != b[1], axis=(0, 2))
                 for f, b in zip(f32, bf16)]
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
               "routing_flip_share": float(np.concatenate(flips).mean()),
               "limits": [TYPICAL_MULTIPLE, WORST_SHARE_OF_SD]}
        if self.check_args.get("planted_faults"):
            # `tools/laguna_limit.py`: what has to come out as NOT correct
            with ref.highest():
                for what in ("window", "gate", "scale"):
                    off = self._reference(samples, jnp.float32,
                                          ablate=frozenset([what]))
                    out["without_" + what] = _over(_distances(
                        got, [o[0] for o in off]), yard)
            f8 = self._reference(samples, jnp.bfloat16,
                                 operands=jnp.float8_e4m3fn)
            out["float8_reference"] = _over(_distances(
                [o[0] for o in f8], [f[0] for f in f32]), yard)
        return out


def _distances(got: Sequence[np.ndarray], want: Sequence[np.ndarray]) -> dict:
    """Of each sample's logits [positions, vocabulary] from the float32
    reference's: ``typical`` the median over positions of the root mean
    square over the vocabulary, by sample; ``worst`` the largest
    absolute distance of the run."""
    d = [np.abs(np.asarray(g, np.float64) - w) for g, w in zip(got, want)]
    return {"typical": [float(np.median(np.sqrt((x * x).mean(-1))))
                        for x in d],
            "worst": float(max(x.max() for x in d))}


def _over(dist: dict, yard: dict) -> dict:
    """``typical`` as multiples of the yardstick's, ``worst`` as a share
    of the float32 logits' standard deviation."""
    by = [t / max(y, 1e-12) for t, y in zip(dist["typical"],
                                            yard["typical"])]
    return {"typical": max(by), "by_sample": by,
            "worst": dist["worst"] / max(yard["sd"], 1e-12)}


def model_layers(model) -> list:
    """`reference_laguna`'s weight names over the model's own arrays."""
    out = []
    for lyr in model.model.layers:
        a, m = lyr.self_attn, lyr.mlp
        w = {"ln1": lyr.input_layernorm.weight._data,
             "wq": a.q_proj.weight._data, "wk": a.k_proj.weight._data,
             "wv": a.v_proj.weight._data, "wgate": a.g_proj.weight._data,
             "wo": a.o_proj.weight._data,
             "ln2": lyr.post_attention_layernorm.weight._data}
        if hasattr(m, "gate_weight"):
            w.update(router=m.gate_weight._data, eg=m.w_gate._data,
                     eu=m.w_up._data, ed=m.w_down._data,
                     sg=m.shared_gate.weight._data,
                     su=m.shared_up.weight._data,
                     sd=m.shared_down.weight._data)
        else:
            w.update(wg=m.gate_proj.weight._data, wu=m.up_proj.weight._data,
                     wd=m.down_proj.weight._data)
        out.append(w)
    return out
