"""System under test: ``paddle_tpu.serving.ServingEngine`` over
``Lfm2MoeForCausalLM`` at a configuration file's sizes — the first stage
of a four-stage pipeline, every expert and the whole vocabulary held —
WITH the prefix cache on (a snapshot of the convolutions' tails a page),
and its comparison with the plain reference."""

from __future__ import annotations

import functools
import time
from typing import Mapping, Sequence

import numpy as np

from ..lib import costs_lfm2 as costs, reference_lfm2 as ref
from ..lib.harness import as_run, say
from ..lib.serving import run_requests
from ..lib.traffic import Req
from ..lib.weights import seed_key
from .laguna_serving import _distances, _over

#: `check()` holds THREE things; the first two by the same two limits
#: (Laguna's kind: `systems/laguna_serving.py` says why logits and why two
#: distances), the third by one of its own:
#:
#: (i) THE SAMPLE.  The logits rows the engine sampled each token of the
#:    warm-up sample from (`ServingEngine.on_logits`) against the plain
#:    float32 reference's full forward at the same position,
#:    teacher-forced over prompt + output.
#: (ii) AN ADOPTION, made here, outside the window, through the same
#:    engine: a prompt ``X[:P] + b`` with b of a few tokens is served
#:    whole — a MISS: its pages go into the trie with the tails at their
#:    last rows —, then the same prompt again and ``X[:P] + b'`` with b'
#:    of some hundred tokens, side by side.  Both must have ADOPTED the P
#:    tokens (``shared_tokens == P``: asserted), and their logits are held
#:    to the reference's FULL forward.
#: (iii) THE BORDER.  The short adopter's FIRST generated row — position
#:    P + 1, which reads row P - 1 of u from the snapshot in every conv
#:    block, and K / V of rows P and P + 1 made from it — as a HIT against
#:    the same row of the same prompt as a MISS, which reads no snapshot
#:    and is held to the reference by (ii)'s twin (`miss_typical_over_noise`).
#:    (i) and (ii) cannot see a lost snapshot on the chip: TYPICAL is a
#:    median over 24 positions of which one reads it, WORST a maximum
#:    that a flipped expert sets.
#:
#: TYPICAL: for each sample the median over its positions of the root
#: mean square over the vocabulary of (engine - float32), over the same
#: median of a correct bfloat16 evaluation of the reference.  WORST: the
#: largest |engine - float32| of the run over the standard deviation of
#: the float32 logits.  BORDER: the root mean square over the vocabulary
#: of (hit - miss) at that row, over the bfloat16 evaluation's distance
#: from float32 at the same row.
#:
#: The limits lie between readings on the chip (`tools/lfm2_limit.py` on
#: eight seeds and the cell's runs, my chip runs, PR 64; PERF.md section
#: 6): above the engine's largest over its seeds, below the planted faults
#: (the reference with ONE fault, read against the ENGINE's logits: what
#: an engine with that fault would show) and the reference with float8
#: operands, the nearest precision below the configuration's.
#: TYPICAL 6.5: the engine reads 1.01-1.47 and 3.05 ONCE (the short
#: adopter of one seed, its other samples 1.01-1.06: with a query that
#: attends to a few keys, one key row made under a flipped expert moves
#: all 24 neighbouring positions together — the hit's border row IS the
#: miss's bit for bit on every seed, so adoption cannot make it);
#: float8 12.2-14.8, no q / k norm 15.9-20.3, a silu on the convolution
#: 17.9-20.5, the B gate left out 44.1-47.8, no renormalisation
#: 51.3-55.3: 2.1 x over the engine's largest, 1.9 x under the smallest
#: fault.
#: WORST 0.8: the engine 0.33-0.62 — the bfloat16 reference ITSELF
#: 0.35-0.60: what both read is one flipped expert at one position, which
#: is chance —; the snapshot of the page BEFORE 0.90-0.98, float8
#: 0.72-0.80, the silu 0.93-1.08, the norms 1.05-1.14, the gate 2.1-2.4,
#: the renormalisation 6.6-7.1.
#: BORDER 4.0: the engine 0.0 EXACTLY in every set read (the same
#: program, shapes and page bytes under other page ids), and 1.00-1.12 if
#: the row were held to the reference and not to the miss; zeros in place
#: of the snapshot 17.6-19.4, the snapshot of the page BEFORE 24.1-26.0:
#: 4 x of room on both sides of one bfloat16 noise.
#: NOT caught on the chip, and held by the float32 CPU tests
#: (`tests/test_lfm2.py`): the bias weighing (1.2-2.6 / 0.35-0.52:
#: renormalised, it moves a routed layer's weights by a few percent).
TYPICAL_MULTIPLE = 6.5
WORST_SHARE_OF_SD = 0.8
BORDER_MULTIPLE = 4.0

#: the published keys the model and the reference are built from
PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "layer_types", "num_attention_heads", "num_key_value_heads",
    "max_position_embeddings", "moe_intermediate_size", "norm_eps",
    "norm_topk_prob", "num_dense_layers", "num_experts",
    "num_experts_per_tok", "rope_parameters", "routed_scaling_factor",
    "use_expert_bias", "conv_L_cache", "conv_bias")

#: ``q_layernorm``'s gain vector is drawn at this value, not 1 (SDAR's
#: file says why: the q / k norms fix the scores' deviation at the
#: product of their gains, so the attention gain goes here)
ATTENTION_GAIN = 3.5
#: the three taps of the convolution, of like size: a lost or stale tail
#: then moves the next two rows by as much as their own row does
CONV_STD = 0.6
#: the router's bias: about one spacing of the sigmoid scores around the
#: fourth of 64 (0.02-0.03), so that it flips a choice at a share of the
#: tokens and leaves the load balanced (Nemotron's file says why not more)
BIAS_STD = 0.02


def draw_weights(shapes, seed: int, dtype, depth: int):
    """One array per (name, shape) from the seed, on the device, in the
    type they are served in.  Laguna's draw (vectors ones, matrices
    Xavier, expert stacks [E, in, out] by their last two dims, the router
    N(0, 0.02), the embedding N(0, 1), projections into the residual
    stream scaled by 1 / sqrt(2 x depth)) and this family's own: the
    attention gain in ``q_layernorm``'s vector, the convolution's taps
    N(0, CONV_STD), the expert bias N(0, BIAS_STD).  Every block of one
    kind has the same shapes, so ONE jitted draw a kind runs once a block
    under the block's own key."""
    import jax
    import jax.numpy as jnp
    into_residual = ("out_proj", "down_proj", "w_down")

    def one(key, name, shape):
        f32 = jnp.float32
        if "conv_weight" in name:
            return CONV_STD * jax.random.normal(key, shape, f32)
        if "e_score_correction_bias" in name:
            return BIAS_STD * jax.random.normal(key, shape, f32)
        if len(shape) < 2:
            return jnp.full(shape, ATTENTION_GAIN
                            if "q_layernorm" in name else 1.0, f32)
        std = float(np.sqrt(2.0 / (shape[-2] + shape[-1])))
        if "embed_tokens" in name:
            std = 1.0
        elif "gate_weight" in name:
            std = 0.02
        elif any(k in name for k in into_residual):
            std /= float(np.sqrt(2.0 * depth))
        return std * jax.random.normal(key, shape, f32)

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
    """`Lfm2MoeConfig` arguments from a configuration file as run."""
    kw = {k: src[k] for k in PUBLISHED_KEYS}
    if "layers_held" in src:
        kw["layers_held"] = list(src["layers_held"])
        # the model counts published layers; the file's count is the cut
        kw["num_hidden_layers"] = len(src["layer_types"])
    return kw


def reader_config(kw: Mapping) -> dict:
    """The system's ``cfg``: what `reference_lfm2` and `costs_lfm2` read
    (the published names; ``num_hidden_layers`` the layers HELD and
    ``layers_held`` their published indices)."""
    c = dict(kw)
    held = list(kw.get("layers_held") or range(kw["num_hidden_layers"]))
    c.update(layers_held=held, num_hidden_layers=len(held))
    return c


def model_layers(model) -> list:
    """`reference_lfm2`'s weight names over the model's own arrays: one
    dict a published LAYER (two blocks of the model)."""
    out = []
    blocks = list(model.model.layers)
    for mix, ffn in zip(blocks[0::2], blocks[1::2]):
        w = {"operator_norm": mix.norm.weight._data,
             "ffn_norm": ffn.norm.weight._data}
        w.update({k: v._data for k, v in mix.mixer.weights().items()})
        m = ffn.mixer
        if ffn.kind == "D":
            w.update(w1=m.gate_proj.weight._data, w3=m.up_proj.weight._data,
                     w2=m.down_proj.weight._data)
        else:
            w.update(router=m.gate_weight._data,
                     bias=m.e_score_correction_bias._data,
                     e1=m.w_gate._data, e3=m.w_up._data, e2=m.w_down._data)
        out.append(w)
    return out


def reference_weights(model) -> dict:
    """The plain reference reads the model's own arrays (not the
    engine's re-laid copies), layer by layer; the head is the
    embedding."""
    return {"embed": model.model.embed_tokens.weight._data,
            "norm": model.model.embedding_norm.weight._data,
            "layers": model_layers(model)}


def _rms(x) -> float:
    """Root mean square of a logits row's distance."""
    return float(np.sqrt(np.square(np.asarray(x, np.float64)).mean()))


class System:
    def __init__(self, config: Mapping, rehearse: bool, seed: int):
        import jax
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.models.lfm2 import (Lfm2MoeForCausalLM,
                                            lfm2_moe_config)
        from paddle_tpu.serving import ServingEngine

        src = as_run(config, rehearse)
        kw = model_kwargs(src)
        self.cfg = reader_config(kw)
        self.engine_args = dict(src["engine"])
        self.check_args = dict(src.get("check", {}))
        self.dtype = jnp.bfloat16
        self.seed = seed
        t0 = time.perf_counter()
        paddle.seed(seed % (2 ** 31))
        # no float32 parameter is ever made: the layers are built lazily
        # and every parameter is bound to a bfloat16 array drawn on the
        # device from the seed
        with paddle.LazyGuard():
            model = Lfm2MoeForCausalLM(lfm2_moe_config(**kw))
        model.eval()
        named = list(model.named_parameters())
        drawn = draw_weights([(n, tuple(p._data.shape)) for n, p in named],
                             seed, self.dtype,
                             self.cfg["num_hidden_layers"])
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
        k = costs.kinds(self.cfg)
        say(f"system: weights {self.weight_bytes / 1e9:.3f} GB "
            f"({costs.n_params(self.cfg) / 1e9:.3f} B parameters held, "
            f"{costs.n_params(self.cfg, active=True) / 1e9:.3f} B a token) "
            f"in {t1 - t0:.1f}s; engine {self.engine_args} in "
            f"{time.perf_counter() - t1:.1f}s; paths {self.paths}; "
            f"{k['conv']} conv + {k['attn']} attention mixers; pools "
            f"{acct['page_pool_bytes'] / 1e9:.3f} GB: {eng.num_pages} pages "
            f"x {eng.page_size} tokens x {costs.kv_row_bytes(self.cfg)} B "
            f"x {k['attn']} layers, tails {eng.max_slots} + 1 slots x "
            f"{costs.tail_bytes(self.cfg)} B x {k['conv']} blocks, "
            f"snapshots {acct.get('tail_snapshot_bytes', 0) / 1e9:.3f} GB; "
            f"prefix cache {'on' if eng.prefix_cache is not None else 'off'}")
        self._ref_weights = reference_weights(model)
        self.vocab = kw["vocab_size"]
        self.max_total = eng.max_context
        # the logits row behind every token of the warm-up sample and of
        # the adoption probe, by request; `check()` takes the hook off
        # again, so the measured window keeps nothing
        self._rows = {}
        eng.on_logits = lambda req, row: self._rows.setdefault(
            req.request_id, []).append(np.asarray(row, np.float32))

    # ------------------------------------------------------- correctness
    def _reference(self, samples, dtype, ablate=frozenset(), operands=None,
                   cut: int = 0):
        """For each sample the logits [outputs, vocabulary] at the
        positions the engine generated from, teacher-forced over prompt +
        output; the width of a forward is a multiple of 128, so a few
        shapes serve every seed (the rows past the sample are causal
        successors: they move nothing before them)."""
        import jax.numpy as jnp
        blocks = {k: int(self.check_args.get(k, 0))
                  for k in ("q_block", "expert_block")}
        w, out = self._ref_weights, []
        for s in samples:
            n0, n1 = len(s["prompt"]), len(s["output"])
            fed = np.concatenate([s["prompt"], s["output"][:-1]])
            ids = np.zeros(-(-len(fed) // 128) * 128, np.int32)
            ids[:len(fed)] = fed
            x, _ = ref.hidden_states(
                jnp.asarray(ids), w["embed"], w["layers"], self.cfg, dtype,
                ablate=ablate, operands=operands, cut=cut,
                page=self.engine.page_size, **blocks)
            out.append(np.asarray(ref.head_logits(
                x[n0 - 1:n0 - 1 + n1], w["norm"], w["embed"],
                eps=float(self.cfg["norm_eps"]), dtype=dtype,
                vocab_block=int(self.check_args.get("vocab_block", 0)))))
        return out

    def _adoption_probe(self):
        """(ii) and (iii): serve ``X[:P] + b`` with b of a few tokens
        whole — a MISS, its logits kept —, then the same prompt again and
        ``X[:P] + b'`` with b' of some hundred tokens side by side, both
        HITS; -> the hits' samples ({"prompt", "output"}), the tokens
        each adopted, P, and the miss's (tokens, logits rows)."""
        eng, args = self.engine, self.check_args
        P = int(args.get("adopt_prefix", 16 * eng.page_size))
        tails = [int(t) for t in args.get("adopt_tails", (2, 700))]
        n_out = int(args.get("adopt_output_len", 24))
        rng = np.random.default_rng(self.seed + 2)

        def ids(n):
            return rng.integers(0, self.vocab, n, dtype=np.int32)

        X = ids(P)
        prompts = [np.concatenate([X, ids(t)]) for t in tails]
        self._rows = {}
        tokens, = run_requests(eng, [Req(0.0, prompts[0], n_out)])
        (rows,) = self._rows.values()
        self._rows = {}
        reqs = [Req(0.0, p, n_out) for p in prompts]
        handles = [eng.add_request(r.prompt, max_new_tokens=r.max_new)
                   for r in reqs]
        while eng.has_work():
            eng.step()
        eng.collect()
        return ([{"prompt": r.prompt,
                  "output": np.asarray(h.tokens, np.int32)}
                 for r, h in zip(reqs, handles)],
                [int(h.shared_tokens) for h in handles], P,
                (tokens, np.stack(rows)))

    def check(self, samples: Sequence[Mapping]) -> dict:
        """``samples``: {"prompt": ids, "output": the engine's tokens},
        in the order they were given to the engine."""
        import jax.numpy as jnp
        got = [np.stack(self._rows[k]) for k in sorted(self._rows)]
        if len(got) != len(samples) or any(
                not np.array_equal(g.argmax(-1), s["output"])
                for g, s in zip(got, samples)):
            raise RuntimeError("the logits kept are not the samples'")
        probe, adopted, P, (miss_tokens, miss) = self._adoption_probe()
        self.engine.on_logits = None
        got += [np.stack(self._rows[k]) for k in sorted(self._rows)]
        self._rows = {}
        n = len(probe)
        samples = list(samples) + probe
        adopted_ok = all(a == P for a in adopted)
        with ref.highest():
            f32 = self._reference(samples, jnp.float32)
        bf16 = self._reference(samples, jnp.bfloat16)
        yard = _distances(bf16, f32)
        yard["sd"] = float(np.concatenate(f32).std())
        read = _over(_distances(got, f32), yard)
        # (iii): the hit's border row from the miss's, in the yardstick's
        # distance at that row; and the miss held to the reference over
        # the rows whose inputs the two share (all, where they agree)
        hit = -n
        noise0 = max(_rms(bf16[hit][0] - f32[hit][0]), 1e-12)
        border = _rms(got[hit][0] - miss[0]) / noise0
        same = int(np.cumprod(np.r_[True, (
            miss_tokens == probe[0]["output"])[:-1]]).sum())
        twin = _over(_distances([miss[:same]], [f32[hit][:same]]),
                     dict(yard, typical=_distances(
                         [bf16[hit][:same]], [f32[hit][:same]])["typical"]))
        checked = int(sum(len(g) for g in got))
        out = {"ok": bool(checked > 0 and adopted_ok
                          and read["typical"] <= TYPICAL_MULTIPLE
                          and read["worst"] <= WORST_SHARE_OF_SD
                          and border <= BORDER_MULTIPLE),
               "checked": checked, "adopted_tokens": adopted,
               "typical_over_noise": read["typical"],
               "worst_over_sd": read["worst"],
               "border_over_noise": border,
               "typical_by_sample": read["by_sample"],
               "miss_typical_over_noise": twin["typical"],
               "miss_rows_compared": same,
               "noise_typical_rms": yard["typical"],
               "noise_worst_over_sd": yard["worst"] / yard["sd"],
               "noise_border_rms": noise0,
               "logits_sd": yard["sd"],
               "limits": [TYPICAL_MULTIPLE, WORST_SHARE_OF_SD,
                          BORDER_MULTIPLE]}
        if self.check_args.get("planted_faults"):
            # `tools/lfm2_limit.py`: what has to come out as NOT correct,
            # read against the ENGINE's logits (what an engine that lost
            # the mechanism would show); the two faults of an adoption
            # over the probe's samples, the only ones that adopted
            pyard = dict(yard, typical=yard["typical"][-n:])
            # (the border row also from the reference's own: what (iii)
            # would read if it were held to the reference and not to the
            # miss)
            out["border_by_reference"] = _rms(
                got[hit][0] - f32[hit][0]) / noise0
            with ref.highest():
                for what in ref.ABLATIONS:
                    off = self._reference(samples, jnp.float32,
                                          ablate=frozenset([what]))
                    out["fault_" + what] = _over(_distances(got, off), yard)
                for what in ref.ADOPTION_ABLATIONS:
                    off = self._reference(probe, jnp.float32,
                                          ablate=frozenset([what]), cut=P)
                    out["fault_" + what] = dict(
                        _over(_distances(got[-n:], off), pyard),
                        border=_rms(off[0][0] - miss[0]) / noise0,
                        border_by_reference=_rms(
                            got[hit][0] - off[0][0]) / noise0)
            f8 = self._reference(samples, jnp.bfloat16,
                                 operands=jnp.float8_e4m3fn)
            out["float8_reference"] = _over(_distances(f8, f32), yard)
        return out
