"""System under test: ``paddle_tpu.serving.ServingEngine`` over the
Ling 3.0 hybrid decoder (`paddle_tpu.models.bailing_hybrid`: KDA
linear-attention mixers with a slot of delta-rule state a sequence,
gated latent attention with pages of latent rows every sixth layer,
group-limited routed FFNs) at a configuration file's sizes — one chip's
share of an expert-parallel pipeline stage — on the programs the engine
chooses itself, and its comparison with the plain reference
(`lib/reference_ling.py`, the recurrence token by token)."""

from __future__ import annotations

import functools
import time
from typing import Mapping, Sequence

import numpy as np

from ..lib import costs_ling as costs, reference_ling as ref
from ..lib.harness import as_run, say
from ..lib.weights import seed_key
from .laguna_serving import _distances, _over

#: `check()` is Laguna's kind (`systems/laguna_serving.py` says why in
#: full): the LOGITS the engine sampled each generated token from
#: (`ServingEngine.on_logits`) against the plain float32 reference's at
#: the same position, under two limits.
#:
#: 1. TYPICAL: for each sample, the median over its positions of the
#:    root mean square over the vocabulary of (engine - float32), over
#:    the same median of (bfloat16 reference - float32).  A mechanism
#:    that is missing moves EVERY position of a sample it acts in.
#: 2. WORST: the largest |engine - float32| of the run over the standard
#:    deviation of the float32 logits: what goes wrong at few positions
#:    by a logit's whole spread (another slot's state, a stale tail, a
#:    row of another sequence).
#:
#: Each limit is set from readings on the chip (`tools/ling_limit.py` on
#: six seeds and the cell's own runs; PERF.md section 6 has every number
#: and its origin).  The planted faults are the float32 reference with
#: ONE of `reference_ling.ABLATIONS` read against the ENGINE's logits
#: (what an engine with that fault would show), and the reference with
#: float8 matrices, the nearest precision below bfloat16.
#:
#: TYPICAL 3.0: the engine reads 1.02-1.65 (the largest sample of a run,
#: thirteen runs;
#: the yardstick's own median is steady here, 0.0028-0.0053 by sample:
#: top-8 of one held group flips an expert at 28-46 % of the positions,
#: under half, so the median position has no flip); the float8 reference
#: reads 12.8-16.0, a dropped expert bias 6.1-9.3, no group limit
#: 7.7-11.0, the gate before the heads' norm 27.9-32.6, one decay a
#: head 35.0-41.8: 1.8 x of room over the engine, 2.0 x under the nearest
#: fault, 4.3 x under float8.
#: WORST 1.0: the engine reads 0.25-0.62 of a deviation (the yardstick
#: itself 0.30-0.57: one flipped expert at one position); the swapped
#: gate order reads 1.35-1.41 and one decay a head 1.68-1.85 (float8
#: 0.68-0.90 and the routing faults 0.46-0.67 fail TYPICAL, not this).
#: NOT caught, and said so: (a) the recurrent state rounded to bfloat16
#: after every token reads 1.10-1.35, what the engine reads — the delta
#: rule corrects an error along a key the next time it writes along it,
#: and each head's output is RMS-normed, so the rounding does not
#: accumulate past the bfloat16 stream's own noise even in channels that
#: remember 500 tokens; the configuration keeps the state in float32 as
#: the family's kernels do, this check cannot tell, the CPU tests (all
#: float32, where nothing else rounds) can and do; (b) a latent mixer
#: without its head gate reads 1.57-2.49: ONE block of fourteen, whose
#: output the gate halves; also held by the CPU tests.
TYPICAL_MULTIPLE = 3.0
WORST_SHARE_OF_SD = 1.0

#: keys of the configuration file that are not the model's
NOT_MODEL = ("name", "source", "system", "reference", "architectures",
             "torch_dtype", "published", "reduced", "reduced_notes",
             "assumed", "deployment", "engine", "engine_notes", "check",
             "check_notes", "pattern_as_run", "weights")

#: the draw (see `draw_weights`)
ATTENTION_GAIN = 2.0
GAIN_STD = 0.1
BIAS_STD = 0.01
#: the log decay a token a channel is drawn for: log-uniform
DECAY_MIN, DECAY_MAX = 0.002, 1.0


def draw_weights(shapes, seed: int, dtype, depth: int, cfg: Mapping):
    """One array per (name, shape) from the seed, on the device, in the
    type they are served in: Laguna's draw (matrices Xavier by their last
    two dims, the router N(0, 1 / hidden) — logits of unit spread at any
    width, Laguna's 0.02 at the published one —, the embedding N(0, 1),
    what writes
    into the residual stream scaled by 1 / sqrt(2 x depth), the gain on
    the latent mixer's ``q_proj``) and this family's vectors, none of
    them trivial (a zero bias or one decay everywhere would let an
    omission pass): gains N(1, 0.1); the router's expert bias N(0,
    0.01), about the spacing of the scores around the 8th of the 256
    that the group limit leaves (they lie in the sigmoid's upper tail):
    it changes some of a token's choices and leaves the load balanced,
    as the bias it stands for does (at a first draw's 0.002 a dropped
    bias read 1.6-2.8 x the yardstick, beside the engine's 1.0-1.25);
    the
    convolutions' weights N(0, 0.3); ``A_log`` the log of U[1, 4];
    ``dt_bias`` such that, at a zero projection, a channel's log decay a
    token is ``-r`` with ``r`` log-uniform in [0.002, 1.0] (``dt_bias =
    logit(r / -kda_lower_bound) / exp(A_log[h])``): memories from two
    tokens to five hundred, channel by channel, moved by the input
    through ``W_f`` (at the sigmoid's middle, the plain draw's, every
    channel would forget in two tokens and the state would be no
    memory).  Every block of one kind has the same shapes, so ONE jitted
    draw a kind runs once a block under the block's own key."""
    import jax
    import jax.numpy as jnp
    into_residual = ("o_proj", "down_proj", "w_down", "shared_down")
    lower = -float(cfg["kda_lower_bound"])
    H, D = cfg["num_attention_heads"], cfg["head_dim"]

    def a_log(key):
        return jnp.log(jax.random.uniform(key, (H,), jnp.float32, 1.0, 4.0))

    def one(key, name, shape, block_key):
        f32 = jnp.float32
        # (the gate's two vectors are drawn together: dt_bias reads A)
        a_key = jax.random.fold_in(block_key, 10_007)
        if name.endswith("A_log"):
            return a_log(a_key)
        if name.endswith("dt_bias"):
            r = jnp.exp(jax.random.uniform(
                key, (H, D), f32, np.log(DECAY_MIN), np.log(DECAY_MAX)))
            x = jnp.log(r / lower) - jnp.log1p(-r / lower)
            return (x / jnp.exp(a_log(a_key))[:, None]).reshape(shape)
        normal = jax.random.normal(key, shape, f32)
        if name.endswith("expert_bias"):
            return BIAS_STD * normal
        if name.endswith("_conv"):
            return 0.3 * normal
        if len(shape) < 2:
            return 1.0 + GAIN_STD * normal
        std = float(np.sqrt(2.0 / (shape[-2] + shape[-1])))
        if "embed_tokens" in name:
            std = 1.0
        elif "gate_weight" in name:
            std = float(shape[0]) ** -0.5
        elif any(k in name for k in into_residual):
            std /= float(np.sqrt(2.0 * depth))
        elif "q_proj" in name and shape[-1] != H * D:
            std *= ATTENTION_GAIN
        return std * normal

    @functools.partial(jax.jit, static_argnums=1)
    def build(key, group):
        return {name: one(jax.random.fold_in(key, i), name, shape,
                          key).astype(dtype)
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
    """`models.bailing_hybrid.bailing_hybrid_config` arguments from a
    configuration file as run.  The file's ``num_experts`` and
    ``vocab_size`` are what this chip HOLDS and ``num_hidden_layers``
    the layers it holds (all three under ``reduced``); the router keeps
    the published width and ``layers_held`` names each layer's PUBLISHED
    index, which the pattern and the limit lists read."""
    kw = {k: v for k, v in src.items() if k not in NOT_MODEL}
    first, count = src["experts_held"]
    if count != src["num_experts"]:
        raise ValueError("experts_held and num_experts disagree")
    if len(src["layers_held"]) != src["num_hidden_layers"]:
        raise ValueError("layers_held and num_hidden_layers disagree")
    kw.update(num_experts=src["published"]["num_experts"],
              num_hidden_layers=src["published"]["num_hidden_layers"],
              experts_held=(first, count))
    return kw


class System:
    def __init__(self, config: Mapping, rehearse: bool, seed: int):
        import jax
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.models.bailing_hybrid import (
            BailingHybridForCausalLM, bailing_hybrid_config)
        from paddle_tpu.serving import ServingEngine

        src = as_run(config, rehearse)
        kw = model_kwargs(src)
        # what `reference_ling` and `costs_ling` read (the published
        # names; `num_experts` the router's width, `experts_held` the
        # share: the names the routed layers' accepted readers read too)
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
            model = BailingHybridForCausalLM(bailing_hybrid_config(**kw))
        model.eval()
        named = list(model.named_parameters())
        drawn = draw_weights([(n, tuple(p._data.shape)) for n, p in named],
                             seed, self.dtype, len(kw["layers_held"]), kw)
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
            f"{eng.max_slots} + 1 slots x "
            f"{costs.state_bytes(self.cfg)} B x "
            f"{costs.kinds(self.cfg)['K']} blocks), pages "
            f"{(acct['page_pool_bytes'] - acct['state_pool_bytes']) / 1e9:.3f}"
            f" GB ({eng.num_pages} x {eng.page_size} tokens x "
            f"{costs.kv_row_bytes(self.cfg)} B)")
        # the plain reference reads the model's own arrays, block by block
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
        the engine generated from, the experts each ``E`` block routed to
        there), teacher-forced over prompt + output."""
        import jax.numpy as jnp
        blocks = {k: int(self.check_args.get(k, 0) or d)
                  for k, d in (("q_block", 0), ("expert_block", 1))}
        w = self._ref_weights
        out = []
        for s in samples:
            n0, n1 = len(s["prompt"]), len(s["output"])
            fed = np.concatenate([s["prompt"], s["output"][:-1]])
            x, routed = ref.hidden_states(
                jnp.asarray(fed, jnp.int32), w["embed"], w["layers"],
                self.cfg, dtype, ablate=ablate, operands=operands, **blocks)
            rows = slice(n0 - 1, n0 - 1 + n1)
            out.append((np.asarray(ref.head_logits(
                x[rows], w["norm"], w["head"],
                eps=float(self.cfg["rms_norm_eps"]), dtype=dtype)),
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
            # `tools/ling_limit.py`: what has to come out as NOT
            # correct
            with ref.highest():
                for what in ref.ABLATIONS:
                    off = self._reference(samples, jnp.float32,
                                          ablate=frozenset([what]))
                    out["fault_" + what] = _over(_distances(
                        got, [o[0] for o in off]), yard)
            f8 = self._reference(samples, jnp.bfloat16,
                                 operands=jnp.float8_e4m3fn)
            out["float8_reference"] = _over(_distances(
                [o[0] for o in f8], [f[0] for f in f32]), yard)
        return out


def model_layers(model) -> list:
    """`reference_ling`'s weight names over the model's own arrays."""
    out = []
    for blk in model.model.layers:
        m = blk.mixer
        w = {"norm": blk.norm.weight._data}
        if blk.kind == "E":
            w.update(router=m.gate_weight._data, bias=m.expert_bias._data,
                     eg=m.w_gate._data, eu=m.w_up._data, ed=m.w_down._data,
                     sg=m.shared_gate.weight._data,
                     su=m.shared_up.weight._data,
                     sd=m.shared_down.weight._data)
        else:
            w.update({k: v._data for k, v in m.weights().items()})
        out.append(w)
    return out
