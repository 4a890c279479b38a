"""System under test: ``paddle_tpu.serving.ServingEngine`` over
``SDARMoeForCausalLM`` at a configuration file's sizes — one stage of a
pipeline, every expert and the whole vocabulary held — generating by
diffusion over blocks, and its comparison with the plain reference."""

from __future__ import annotations

import time
from itertools import combinations
from typing import Mapping, Sequence

import numpy as np

from ..lib import costs_sdar as costs, reference_sdar as ref
from ..lib.harness import as_run, say
from ..lib.weights import seed_key
from .laguna_serving import _distances, _over

#: `check()` holds THREE things, for every denoise pass of every block of
#: the warm-up sample (`ServingEngine.on_block`: the block's tokens going
#: in, its logits rows, the block after the pass):
#:
#: (i) LOGITS.  The engine's rows for the rows still masked against the
#:    plain float32 reference's, teacher-forced with the ENGINE's ids
#:    (prompt + committed blocks + the block going in, mask ids and all),
#:    by Laguna's two distances (`systems/laguna_serving.py` says why
#:    tokens are the wrong test and why two): TYPICAL, for each sample
#:    the median over its rows of the root mean square over the
#:    vocabulary of (engine - float32), over the same median of a
#:    correct bfloat16 evaluation of the reference; WORST, the largest
#:    |engine - float32| of the run over the standard deviation of the
#:    float32 logits.
#: (ii) THE RULE, exact, on the engine's OWN logits: `reference_sdar.
#:    transfer` of the rows the engine handed over gives the block the
#:    engine fed to the next pass.  Where the confidence of the last row
#:    chosen and of the first row left differ by less than 1e-6 relative
#:    either choice passes (the device sums 151,936 exponentials in
#:    another order than numpy).
#: (iii) COMMITTED TOKENS: what a request emitted is its blocks after
#:    their last passes, past the given tokens, cut at its budget.
#:
#: The limits of (i) lie between readings on the chip
#: (`tools/sdar_limit.py`, my chip runs, PR 60; four seeds, 190 rows
#: each — the still-masked rows of 72 denoise passes; PERF.md section 6):
#: above the engine's largest over its seeds, below the smallest of the
#: planted faults (the reference with ONE fault, read against the
#: ENGINE's logits: what an engine with that fault would show) and of the
#: reference with float8 operands, the nearest precision below the
#: configuration's.  TYPICAL: the engine 0.99-1.39 (thirteen readings:
#: four seeds of the tool, nine runs of the cell; 1.39 once, in the 1 k
#: sample, 1.19 the next, in the 61-token one); an
#: engine without its commit pass 6.3-10.2 — in the 61-token sample, and
#: 1.1-2.6 in the 1 k and 3 k ones, where three keys made from the mask
#: token are lost among thousands: the short sample is there for this —
#: float8 8.1-8.5, without the top-8's renormalisation 12.5-14.3, the
#: causal mask in place of the block rule 13.1-15.5 (3.7-6.5 in the long
#: samples), without the q / k norm 28.5-30.5.  WORST: the engine
#: 0.18-0.28 (the bfloat16 reference itself 0.20-0.33: a flipped
#: expert's worth); float8 0.70-0.75, the commit 0.74-1.04, the
#: renormalisation 0.94-1.03, the mask 1.33-1.56, the norms 2.47-2.65.
#: Every fault and float8 come out as not correct by TYPICAL with room
#: (2.1 x under the smallest, 2.2 x over the engine's largest); WORST
#: stands where Laguna's does, nearer the faults (1.4 x under float8, 1.8
#: x over the engine's largest), because what it reads of an honest
#: engine is a flipped expert, which is chance.
TYPICAL_MULTIPLE = 3.0
WORST_SHARE_OF_SD = 0.5
RULE_TIE = 1e-6

#: the published keys the model and the reference are built from
PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "max_position_embeddings", "rms_norm_eps", "rope_theta", "num_experts",
    "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob",
    "tie_word_embeddings")
GENERATION_KEYS = ("block_length", "denoising_steps", "mask_token_id")

#: ``q_norm``'s gain vector is drawn at this value, not 1.  The q / k
#: norms fix the attention scores' deviation at (gain of q_norm) x (gain
#: of k_norm) whatever ``q_proj``'s own gain, so Laguna's 4 x Xavier on
#: ``q_proj`` would do nothing here: at gains of 1 the scores' deviation
#: is 1, the softmax over a long context near-uniform, and no comparison
#: of logits can tell three keys too many or too few (the block rule
#: against the causal one).  At 3.5 a query attends to a few keys as in a
#: trained model.
ATTENTION_GAIN = 3.5


def draw_weights(shapes, seed: int, dtype, depth: int):
    """One array per (name, shape) from the seed, on the device in ONE
    jitted call, in the type they are served in.  As Laguna's draw
    (`systems/laguna_serving.py::draw_weights` says why each departure
    from ``lib/weights.py``): vectors ones, matrices Xavier, expert
    stacks [E, in, out] by their last two dims, the router N(0, 0.02),
    the embedding N(0, 1), projections into the residual stream scaled
    by 1 / sqrt(2 x depth) — and the attention gain in ``q_norm``'s
    vector (ATTENTION_GAIN), not in ``q_proj``."""
    import jax
    import jax.numpy as jnp
    into_residual = ("o_proj", "w_down")

    def build(key):
        out = {}
        for i, (name, shape) in enumerate(shapes):
            if len(shape) < 2:
                out[name] = jnp.full(
                    shape, ATTENTION_GAIN if "q_norm" in name else 1.0, dtype)
                continue
            std = float(np.sqrt(2.0 / (shape[-2] + shape[-1])))
            if "embed_tokens" in name:
                std = 1.0
            elif "gate_weight" in name:
                std = 0.02
            elif any(k in name for k in into_residual):
                std /= float(np.sqrt(2.0 * depth))
            out[name] = (jax.random.normal(jax.random.fold_in(key, i),
                                           shape, jnp.float32)
                         * std).astype(dtype)
        return out

    return jax.jit(build)(seed_key(seed))


def model_kwargs(src: Mapping) -> dict:
    """`SDARMoeConfig` arguments from a configuration file as run."""
    kw = {k: src[k] for k in PUBLISHED_KEYS}
    kw.update({k: src["generation"][k] for k in GENERATION_KEYS})
    if kw.pop("tie_word_embeddings"):
        raise ValueError("the head is untied in this family")
    return kw


def reference_config(c) -> dict:
    """What `reference_sdar` reads, from an `SDARMoeConfig` (or the
    `model_kwargs` of a file)."""
    c = c if isinstance(c, Mapping) else vars(c)
    keys = ("num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
            "num_experts_per_tok", "norm_topk_prob") + GENERATION_KEYS
    return {k: c[k] for k in keys}


def model_layers(model) -> list:
    """`reference_sdar`'s weight names over the model's own arrays."""
    out = []
    for lyr in model.model.layers:
        a, m = lyr.self_attn, lyr.mlp
        out.append({
            "ln1": lyr.input_layernorm.weight._data,
            "wq": a.q_proj.weight._data, "wk": a.k_proj.weight._data,
            "wv": a.v_proj.weight._data, "q_norm": a.q_norm.weight._data,
            "k_norm": a.k_norm.weight._data, "wo": a.o_proj.weight._data,
            "ln2": lyr.post_attention_layernorm.weight._data,
            "router": m.gate_weight._data, "eg": m.w_gate._data,
            "eu": m.w_up._data, "ed": m.w_down._data})
    return out


def reference_weights(model) -> dict:
    """The plain reference reads the model's own arrays (not the
    engine's re-laid copies), layer by layer."""
    return {"embed": model.model.embed_tokens.weight._data,
            "norm": model.model.norm.weight._data,
            "head": model.lm_head.weight._data,
            "layers": model_layers(model)}


class BlockLog:
    """`ServingEngine.on_block`'s listener: every pass of every block,
    by request, in the order they retired."""

    def __init__(self):
        self.passes = {}

    def __call__(self, req, p, total, before, logits, after):
        self.passes.setdefault(req.request_id, []).append(
            dict(p=p, total=total, before=np.asarray(before),
                 logits=None if logits is None
                 else np.asarray(logits, np.float32),
                 after=np.asarray(after)))

    def blocks(self, rid):
        """[[the passes of block 0], [of block 1], ...] of a request."""
        out = []
        for rec in self.passes.get(rid, ()):
            if rec["p"] == 0:
                out.append([])
            out[-1].append(rec)
        return out


def rule_holds(rec: Mapping, mask_id: int, k: int) -> bool:
    """(ii): the reference's transfer rule on the engine's own logits
    gives the block the engine fed on; a commit pass moves nothing."""
    before, after = rec["before"], rec["after"]
    if rec["p"] == rec["total"] - 1:
        return bool(np.array_equal(before, after))
    masked = before == mask_id
    x0, chosen, c = ref.transfer(rec["logits"], masked, k)
    if np.array_equal(np.where(chosen, x0, before), after):
        return True
    # a near-tie of the last row chosen and the first left: another
    # set of as many masked rows passes if it is a top-k within RULE_TIE
    for alt in combinations(np.flatnonzero(masked), int(chosen.sum())):
        pick = np.zeros_like(masked)
        pick[list(alt)] = True
        rest = masked & ~pick
        if np.array_equal(np.where(pick, x0, before), after) and (
                not rest.any()
                or c[pick].min() >= c[rest].max() * (1 - RULE_TIE)):
            return True
    return False


class System:
    def __init__(self, config: Mapping, rehearse: bool, seed: int):
        import jax
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.models.sdar import SDARMoeConfig, SDARMoeForCausalLM
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
            model = SDARMoeForCausalLM(SDARMoeConfig(**kw))
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
            f"{time.perf_counter() - t1:.1f}s; blocks of "
            f"{kw['block_length']} in {kw['denoising_steps']} denoise "
            f"passes + a commit; paths {self.paths}; pool {eng.num_pages} "
            f"pages")
        self._ref_weights = reference_weights(model)
        self.vocab = kw["vocab_size"]
        # the traffic draws ids below the mask token's
        if kw["mask_token_id"] < self.vocab:
            self.vocab = kw["mask_token_id"]
        self.max_total = eng.max_context
        # every pass of the warm-up sample's blocks; `check()` takes the
        # hook off again, so the measured window keeps nothing
        self._log = BlockLog()
        eng.on_block = self._log

    # ------------------------------------------------------- correctness
    def _passes(self, samples):
        """[(sample index, the ids before the block, the pass's record)]
        of every DENOISE pass of the samples, in order."""
        out = []
        for i, (s, rid) in enumerate(zip(samples, sorted(self._log.passes))):
            B = self.cfg["block_length"]
            prompt = [int(t) for t in s["prompt"]]
            ctx = prompt[:len(prompt) - len(prompt) % B]
            for blk in self._log.blocks(rid):
                for rec in blk[:-1]:
                    out.append((i, list(ctx), rec))
                ctx += [int(t) for t in blk[-1]["after"]]
        return out

    def _reference(self, passes, dtype, ablate=frozenset(), operands=None,
                   uncommitted=None):
        """For each denoise pass the reference's logits [B, V] of the
        block's rows, teacher-forced with the engine's ids; the width of
        a forward is a multiple of 128, so a few shapes serve every
        seed.  `uncommitted` (a sample: [positions] bool, the rows a
        block's LAST denoise pass unmasked) plants the fault of an engine
        without its commit pass: every EARLIER block's such rows are fed
        as the mask token (their K/V was written from it)."""
        import jax.numpy as jnp
        B, mask = self.cfg["block_length"], self.cfg["mask_token_id"]
        blocks = {k: int(self.check_args.get(k, 0))
                  for k in ("q_block", "expert_block")}
        w, out = self._ref_weights, []
        for i, ctx, rec in passes:
            n = len(ctx)
            ids = np.zeros(-(-(n + B) // 128) * 128, np.int32)
            ids[:n], ids[n:n + B] = ctx, rec["before"]
            if uncommitted is not None:
                ids[:n] = np.where(uncommitted[i][:n], mask, ids[:n])
            x, _ = ref.hidden_states(
                jnp.asarray(ids), w["embed"], w["layers"], self.ref_cfg,
                dtype, ablate=ablate, operands=operands, **blocks)
            out.append(np.asarray(ref.head_logits(
                x[n:n + B], w["norm"], w["head"],
                eps=self.ref_cfg["rms_norm_eps"], dtype=dtype,
                vocab_block=int(self.check_args.get("vocab_block", 0)))))
        return out

    def check(self, samples: Sequence[Mapping]) -> dict:
        """``samples``: {"prompt": ids, "output": the engine's tokens},
        in the order they were given to the engine."""
        import jax.numpy as jnp
        self.engine.on_block = None
        B, S = self.cfg["block_length"], self.cfg["denoising_steps"]
        mask = self.cfg["mask_token_id"]
        rids = sorted(self._log.passes)
        if len(rids) != len(samples):
            raise RuntimeError("the passes kept are not the samples'")
        # (iii) committed tokens = the blocks after their last passes
        committed_ok, rule_ok, n_rule = True, True, 0
        last_unmasked = []
        for s, rid in zip(samples, rids):
            g = len(s["prompt"]) % B
            blocks = self._log.blocks(rid)
            toks = [int(t) for k, blk in enumerate(blocks)
                    for t in blk[-1]["after"][g if k == 0 else 0:]]
            committed_ok &= toks[:len(s["output"])] == \
                [int(t) for t in s["output"]] and all(
                    len(blk) == blk[0]["total"] == ref.block_passes(
                        B, S, g if k == 0 else 0)
                    for k, blk in enumerate(blocks))
            for blk in blocks:
                for rec in blk:
                    n_rule += 1
                    rule_ok &= rule_holds(rec, mask, B // S)
            # which positions were unmasked by a block's LAST denoise pass
            last = np.zeros(len(s["prompt"]) - g + B * len(blocks), bool)
            at = len(s["prompt"]) - g
            for blk in blocks:
                if len(blk) > 1:
                    last[at:at + B] = blk[-2]["before"] == mask
                at += B
            last_unmasked.append(last)
        passes = self._passes(samples)
        # the rows the rule read: still masked going in
        rows = [rec["before"] == mask for _, _, rec in passes]

        def by_sample(vals):
            return [np.concatenate([v[m] for (i, _, _), v, m
                                    in zip(passes, vals, rows) if i == k])
                    for k in range(len(samples))]

        got = by_sample([rec["logits"] for _, _, rec in passes])
        with ref.highest():
            f32 = by_sample(self._reference(passes, jnp.float32))
        bf16 = by_sample(self._reference(passes, jnp.bfloat16))
        yard = _distances(bf16, f32)
        yard["sd"] = float(np.concatenate(f32).std())
        read = _over(_distances(got, f32), yard)
        checked = int(sum(len(g) for g in got))
        out = {"ok": bool(checked > 0 and committed_ok and rule_ok
                          and read["typical"] <= TYPICAL_MULTIPLE
                          and read["worst"] <= WORST_SHARE_OF_SD),
               "checked": checked, "passes": n_rule,
               "rule_exact": bool(rule_ok),
               "committed_equal": bool(committed_ok),
               "typical_over_noise": read["typical"],
               "worst_over_sd": read["worst"],
               "typical_by_sample": read["by_sample"],
               "noise_typical_rms": yard["typical"],
               "noise_worst_over_sd": yard["worst"] / yard["sd"],
               "logits_sd": yard["sd"],
               "limits": [TYPICAL_MULTIPLE, WORST_SHARE_OF_SD]}
        if self.check_args.get("planted_faults"):
            # `tools/sdar_limit.py`: what has to come out as NOT correct,
            # read against the ENGINE's logits (what an engine that lost
            # the mechanism would show)
            with ref.highest():
                for what in ("causal", "qk_norm", "renorm"):
                    off = by_sample(self._reference(
                        passes, jnp.float32, ablate=frozenset([what])))
                    out["without_" + what] = _over(_distances(got, off),
                                                   yard)
                off = by_sample(self._reference(
                    passes, jnp.float32, uncommitted=last_unmasked))
                out["without_commit"] = _over(_distances(got, off), yard)
            f8 = by_sample(self._reference(passes, jnp.bfloat16,
                                           operands=jnp.float8_e4m3fn))
            out["float8_reference"] = _over(_distances(f8, f32), yard)
        self._log = BlockLog()
        return out
