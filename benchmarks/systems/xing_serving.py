"""System under test: ``paddle_tpu.serving.ServingEngine`` over the Xing
4.0 decoder (`paddle_tpu.models.xing`: a four-stream residual mixed by
hyper-connections around every latent-attention mixer and every FFN, a
sigmoid router with a correction bias, a shared expert) at a
configuration file's sizes — one chip's share of a pipeline stage whose
layers are shared four ways — on the programs the engine chooses
itself, and its comparison with the plain reference
(`lib/reference_xing.py`: the unabsorbed attention, the residual written
out)."""

from __future__ import annotations

import time
from typing import Mapping, Sequence

import numpy as np

from ..lib import costs_xing as costs, reference_xing as ref
from ..lib.harness import as_run, say
from ..lib.weights import seed_key
from .axk1_serving import ATTENTION_GAIN
from .laguna_serving import _distances, _over

#: `check()` is A.X-K1's kind (`systems/axk1_serving.py` and
#: `systems/laguna_serving.py` say why in full): LOGITS, the row the
#: engine sampled each generated token from, against the plain float32
#: reference's at the same position, under two limits — TYPICAL (for
#: each sample the median over its positions of the RMS over the
#: vocabulary of (engine - float32), over the same median of (bfloat16
#: reference - float32)) and WORST (the run's largest |engine - float32|
#: over the standard deviation of the float32 logits).
#:
#: Each limit is set from readings on the chip (my chip runs, PR 50:
#: `tools/xing_limit.py` on seeds 2147491021-3 for the faults, those and
#: the cell's own runs for the engine; 72 positions a seed; PERF.md
#: section 6 has every number).  The planted faults are the float32
#: reference with ONE of: a single Sinkhorn iteration instead of 20,
#: Hpost without its factor 2, the coefficients rounded to bfloat16, the
#: correction bias dropped — read against the ENGINE's logits — and the
#: reference with float8 operands, the nearest precision below bfloat16.
#:
#: TYPICAL: the engine 1.03-1.12 over seventeen seeds.  The faults: one Sinkhorn iteration
#: 3.04-4.88 (a sample 2.60 at the least), float8 13.1-14.3, the bias
#: dropped 13.7-14.4, Hpost without its 2 30.4-31.2.  The limit stands
#: at 1.9: 1.7 x the engine's largest, 1.6 x under the smallest fault
#: (A.X-K1's 2.5 would leave a fifth of room under it).  The
#: coefficients rounded to bfloat16 read 1.06-1.11, the ENGINE's own
#: reading: 24 numbers a row rounded to 8 bits move the logits by less
#: than a bfloat16 evaluation's own noise (which rounds the whole stream
#: 40 times a token), so no check of logits sees it; it is held on the
#: CPU, where the kernels meet the float32 forms at 2e-5
#: (`tests/test_mhc.py`).  Exit by the mean of the streams instead of
#: their sum is the same logits behind the last RMSNorm: held on the
#: stream itself (`tests/test_xing.py`).
#:
#: WORST: the engine 0.34-0.63 (the bfloat16 reference itself 0.37-0.62
#: on the same seeds: a token in three meets a flipped expert somewhere
#: in 18 routed layers, `routing_flip_share` 0.33-0.40, and the largest
#: of 72 positions' flips is the reading).  The limit stands at 1.0: 1.6
#: x the engine's largest; Hpost without its factor reads 1.35-1.40,
#: the dropped bias 0.80-0.94, float8 0.74-0.78, one iteration
#: 0.41-0.74 — as with A.X-K1 this limit is for what goes wrong at few
#: positions by a logit's whole spread (a stale page, a row of another
#: sequence), and precision is TYPICAL's to catch.
TYPICAL_MULTIPLE = 1.9
WORST_SHARE_OF_SD = 1.0

#: the published keys the model and the reference are built from
PUBLISHED_KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads",
    "max_position_embeddings", "rms_norm_eps", "rope_theta",
    "rope_scaling", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace",
    "moe_intermediate_size", "n_shared_experts", "num_experts_per_tok",
    "n_group", "topk_group", "scoring_func", "norm_topk_prob",
    "routed_scaling_factor", "topk_method", "moe_layer_freq",
    "tie_word_embeddings", "attention_bias", "hidden_act", "hc_mult",
    "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
    "mhc_h_res_clamp_max", "num_nextn_predict_layers")

#: the correction bias's draw: about the spacing of the sigmoid scores
#: around the 4th of 64, so that it changes some of a token's choices
#: and leaves the load balanced, as the bias it stands for does
BIAS_STD = 0.02
#: ``b`` on the residual matrix's diagonal: Hres near, not at, the
#: identity, so that the per-token part moves it
RES_DIAG = 4.0


def draw_weights(shapes, seed: int, dtype, depth: int, n: int):
    """One array per (name, shape) from the seed, on the device in ONE
    jitted call, in the type they are served in: A.X-K1's draw (vectors
    ones, matrices Xavier by their last two dims, the router N(0,
    0.02), the embedding N(0, 1), what writes into the residual stream
    scaled by 1 / sqrt(2 x depth), the gain on ``q_b_proj``) and this
    family's: the correction bias N(0, 0.02); a sublayer's mixing
    ``phi`` N(0, 1 / (n C)) (so that ``u`` has unit spread), ``a`` = 1,
    ``b`` = 0 but 4 on the residual matrix's diagonal."""
    import jax
    import jax.numpy as jnp
    into_residual = ("o_proj", "down_proj", "w_down", "shared_down")
    diag = np.zeros(n * n + 2 * n, np.float32)
    diag[2 * n:] = (RES_DIAG * np.eye(n)).reshape(-1)

    def build(key):
        out = {}
        for i, (name, shape) in enumerate(shapes):
            k = jax.random.fold_in(key, i)
            if name.endswith(".b"):
                out[name] = jnp.asarray(diag, dtype)
                continue
            if name.endswith("e_score_correction_bias"):
                out[name] = (BIAS_STD * jax.random.normal(
                    k, shape, jnp.float32)).astype(dtype)
                continue
            if len(shape) < 2:
                out[name] = jnp.ones(shape, dtype)
                continue
            std = float(np.sqrt(2.0 / (shape[-2] + shape[-1])))
            if "embed_tokens" in name:
                std = 1.0
            elif "gate_weight" in name:
                std = 0.02
            elif name.endswith(".phi"):
                std = float(shape[0]) ** -0.5
            elif any(k_ in name for k_ in into_residual):
                std /= float(np.sqrt(2.0 * depth))
            elif "q_b_proj" in name:
                std *= ATTENTION_GAIN
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * std).astype(dtype)
        return out

    return jax.jit(build)(seed_key(seed))


def model_kwargs(src: Mapping) -> dict:
    """`models.xing.xing_config` arguments from a configuration file as
    run.  The file's ``n_routed_experts`` and ``vocab_size`` are what
    this chip HOLDS (both under ``reduced``); the router keeps the
    published width."""
    kw = {k: src[k] for k in PUBLISHED_KEYS}
    first, count = src["experts_held"]
    if count != src["n_routed_experts"]:
        raise ValueError("experts_held and n_routed_experts disagree")
    kw.update(vocab_size=src["vocab_size"],
              n_routed_experts=src["published"]["n_routed_experts"],
              experts_held=(first, count),
              rope_positions=src["engine"]["max_context"])
    return kw


def reader_config(kw: Mapping) -> dict:
    """The system's ``cfg``: what `reference_xing` and `costs_xing` read
    (the published names), and the names the routed layers' readers
    written for Laguna's cell read (``num_experts`` the router's
    width)."""
    c = {k: v for k, v in kw.items() if k != "rope_positions"}
    c["num_experts"] = kw["n_routed_experts"]
    return c


class System:
    def __init__(self, config: Mapping, rehearse: bool, seed: int):
        import jax
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.models.xing import XingForCausalLM, xing_config
        from paddle_tpu.serving import ServingEngine

        src = as_run(config, rehearse)
        kw = model_kwargs(src)
        self.cfg = reader_config(kw)
        self.engine_args = dict(src["engine"])
        self.check_args = dict(src.get("check", {}))
        self.dtype = jnp.bfloat16
        t0 = time.perf_counter()
        paddle.seed(seed % (2 ** 31))
        # no float32 parameter is ever made: the layers are built lazily
        # and every parameter is bound to a bfloat16 array drawn on the
        # device from the seed, in one jitted call
        with paddle.LazyGuard():
            model = XingForCausalLM(xing_config(**kw))
        model.eval()
        named = list(model.named_parameters())
        drawn = draw_weights([(n, tuple(p._data.shape)) for n, p in named],
                             seed, self.dtype, kw["num_hidden_layers"],
                             kw["hc_mult"])
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
            f"{time.perf_counter() - t1:.1f}s; paths {self.paths}; pool "
            f"{eng.num_pages} pages of {eng.page_size} x "
            f"{eng._kv_geom[1]} columns a layer; a stream row "
            f"{eng._stream_row_bytes} B")
        # the plain reference reads the model's own arrays, layer by layer
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
                  for k in ("q_block", "head_block", "ffn_block")}
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
                jnp.asarray(ids), w["embed"], w["layers"], self.cfg,
                dtype, ablate=ablate, operands=operands, **blocks)
            rows = slice(n0 - 1, n0 - 1 + n1)
            out.append((np.asarray(ref.head_logits(
                x[rows], w["norm"], w["head"],
                eps=self.cfg["rms_norm_eps"], dtype=dtype)),
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
            # `tools/xing_limit.py`: what has to come out as NOT correct
            with ref.highest():
                for what in ref.ABLATIONS:
                    off = self._reference(samples, jnp.float32,
                                          ablate=frozenset([what]))
                    out["with_" + what] = _over(_distances(
                        got, [o[0] for o in off]), yard)
            f8 = self._reference(samples, jnp.bfloat16,
                                 operands=jnp.float8_e4m3fn)
            out["float8_reference"] = _over(_distances(
                [o[0] for o in f8], [f[0] for f in f32]), yard)
        return out


def model_layers(model) -> list:
    """`reference_xing`'s weight names over the model's own arrays."""
    out = []
    for lyr in model.model.layers:
        a, m = lyr.self_attn, lyr.mlp
        w = {"ln1": lyr.input_layernorm.weight._data,
             "wqa": a.q_a_proj.weight._data,
             "gq": a.q_a_layernorm.weight._data,
             "wqb": a.q_b_proj.weight._data,
             "wkva": a.kv_a_proj_with_mqa.weight._data,
             "gkv": a.kv_a_layernorm.weight._data,
             "wkvb": a.kv_b_proj.weight._data,
             "wo": a.o_proj.weight._data,
             "ln2": lyr.post_attention_layernorm.weight._data}
        for i, hc in ((1, lyr.hc_attn), (2, lyr.hc_ffn)):
            w.update({f"phi{i}": hc.phi._data, f"b{i}": hc.b._data,
                      f"a{i}": hc.a._data})
        if hasattr(m, "gate_weight"):
            w.update(router=m.gate_weight._data,
                     bias=m.e_score_correction_bias._data,
                     eg=m.w_gate._data, eu=m.w_up._data, ed=m.w_down._data,
                     sg=m.shared_gate.weight._data,
                     su=m.shared_up.weight._data,
                     sd=m.shared_down.weight._data)
        else:
            w.update(wg=m.gate_proj.weight._data, wu=m.up_proj.weight._data,
                     wd=m.down_proj.weight._data)
        out.append(w)
    return out
