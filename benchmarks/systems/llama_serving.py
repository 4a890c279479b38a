"""System under test: ``paddle_tpu.serving.ServingEngine`` over
``LlamaForCausalLM`` at a configuration file's sizes, with the paths the
engine chooses itself, and its comparison with the plain reference."""

from __future__ import annotations

import time
from typing import List, Mapping, Sequence

import numpy as np

from ..lib import costs, reference_llama as ref
from ..lib.harness import as_run, say
from ..lib.weights import make_weights

#: The engine's token at a generated position may trail the float32
#: reference's best token by at most this many times the distance, at
#: that position, between a correct bfloat16 evaluation and the float32
#: one (max over the vocabulary).  Random weights give near-flat logits
#: over 32k tokens, so equal tokens are the wrong test (PR 21 measured
#: 7 of 8 greedy streams flipping on near-ties); two correct bfloat16
#: evaluations can disagree by the noise of each, hence 2.  A step
#: computed in a lower precision than bfloat16 lands far outside.
NOISE_MULTIPLE = 2.0

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "max_position_embeddings",
              "rope_theta", "rms_norm_eps", "tie_word_embeddings")


class System:
    def __init__(self, config: Mapping, rehearse: bool, seed: int):
        import jax
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import ServingEngine

        src = as_run(config, rehearse)
        self.cfg = {k: src[k] for k in MODEL_KEYS}
        self.engine_args = dict(src["engine"])
        self.check_args = dict(src.get("check", {}))
        self.dtype = jnp.bfloat16
        t0 = time.perf_counter()
        paddle.seed(seed % (2 ** 31))
        # no float32 parameter is ever made: the layers are built lazily
        # and every parameter is bound to a bfloat16 array drawn on the
        # device from the seed, in one jitted call
        with paddle.LazyGuard():
            model = LlamaForCausalLM(LlamaConfig(**self.cfg))
        model.eval()
        named = list(model.named_parameters())
        drawn = make_weights([(n, tuple(p._data.shape)) for n, p in named],
                             seed, self.dtype)
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
            f"({costs.n_params(self.cfg) / 1e9:.3f} B parameters) in "
            f"{t1 - t0:.1f}s; engine {self.engine_args} in "
            f"{time.perf_counter() - t1:.1f}s; paths {self.paths}")
        # the plain reference reads the same arrays, layer by layer
        p = eng._p
        self._ref_weights = {"embed": p["embed"], "norm": p["norm"],
                             "head": p["head"], "layers": model_layers(model)}
        self.vocab = self.cfg["vocab_size"]
        self.max_total = eng.max_context

    # ------------------------------------------------------- correctness
    def check(self, samples: Sequence[Mapping]) -> dict:
        """``samples``: {"prompt": ids, "output": the engine's tokens}.
        Teacher-forces the plain float32 reference over prompt + output
        and holds every generated token to NOISE_MULTIPLE."""
        import jax.numpy as jnp
        block = int(self.check_args.get("head_block", 0))

        def logits_at_outputs(s, dtype):
            # one sample at a time at its own width (a multiple of 128,
            # so a few shapes serve every seed); the head only where
            # the engine generated
            n0, n1 = len(s["prompt"]), len(s["output"])
            ids = np.zeros((1, -(-(n0 + n1) // 128) * 128), np.int32)
            ids[0, :n0 + n1] = np.concatenate([s["prompt"], s["output"]])
            w = self._ref_weights
            x = ref.hidden_states(jnp.asarray(ids), w["embed"], w["layers"],
                                  self.cfg, dtype, head_block=block)
            return np.asarray(ref.head_logits(
                x[:, n0 - 1:n0 - 1 + n1], w["norm"], w["head"],
                eps=self.cfg["rms_norm_eps"], dtype=dtype))[0]

        with ref.highest():
            f32 = [logits_at_outputs(s, jnp.float32) for s in samples]
        bf16 = [logits_at_outputs(s, jnp.bfloat16) for s in samples]
        worst, checked, bad = 0.0, 0, []
        noises: List[float] = []
        for i, s in enumerate(samples):
            for j, tok in enumerate(s["output"]):
                row, row16 = f32[i][j], bf16[i][j]
                noise = float(np.abs(row16 - row).max())
                lead = float(row.max() - row[int(tok)])
                noises.append(noise)
                checked += 1
                ratio = lead / max(noise, 1e-9)
                worst = max(worst, ratio)
                if lead > NOISE_MULTIPLE * noise:
                    bad.append({"sample": i, "position": j,
                                "lead_f32": lead, "bf16_noise": noise})
        return {"ok": not bad and checked > 0, "checked": checked,
                "worst_lead_over_noise": worst,
                "bf16_noise_max": max(noises) if noises else None,
                "limit": NOISE_MULTIPLE, "bad": bad[:5]}


def model_layers(model) -> list:
    out = []
    for lyr in model.llama.layers:
        a, m = lyr.self_attn, lyr.mlp
        out.append({"ln1": lyr.input_layernorm.weight._data,
                    "wq": a.q_proj.weight._data,
                    "wk": a.k_proj.weight._data,
                    "wv": a.v_proj.weight._data,
                    "wo": a.o_proj.weight._data,
                    "ln2": lyr.post_attention_layernorm.weight._data,
                    "wg": m.gate_proj.weight._data,
                    "wu": m.up_proj.weight._data,
                    "wd": m.down_proj.weight._data})
    return out
