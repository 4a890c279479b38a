"""Pretrain step — the flagship hybrid-parallel training program
(ref: PaddleNLP llm/run_pretrain.py over fleet 4D; SURVEY §3.5), for a
dense Llama and for any decoder FAMILY that says what it is
(`DecoderFamily`: Mellum's sliding / full layers with routed experts).

One jitted SPMD program composes every axis:
  pp  — compiled microbatch pipeline (distributed.pipeline)
  dp  — batch dim sharded (grad psum by GSPMD)
  sharding — ZeRO: params+opt-state sharded on a parameter dim (each
        layer's weights split, all-gathered once a step; the weight
        gradients reduce-scattered)
  sep — sequence dim sharded (context parallelism via GSPMD resharding
        around attention; ring-attention kernel lands at L6)
  mp  — Megatron TP (weight specs) + vocab-parallel CE
Optimizer is the framework's own AdamW (optimizer.functional.FunctionalAdamW
— the same adamw_kernel the eager optimizer.AdamW.step() runs) with
ClipGradByGlobalNorm semantics; bf16 compute params via amp.decorate_tree
(functional O2) over f32 master weights (multi_precision parity).
"""

from __future__ import annotations

import functools
import itertools
from typing import Any, Callable, Dict, List, Mapping, NamedTuple, \
    Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import observability as _obs
from ..amp import decorate_tree
from ..core.tensor import Tensor
from ..device import memory_stats
from ..distributed.mesh import (build_hybrid_mesh, global_device_put,
                                mesh_context)
from ..distributed.parallel_layers import seq_sharded_on
from ..observability.attribution import (RESIDUALS, compile_named, keeping,
                                         scope as _scope)
from ..ops.on_mesh import kernel_mesh
from ..distributed.pipeline import (PP_AXIS, spmd_pipeline,
                                    spmd_pipeline_interleaved,
                                    stack_layer_params,
                                    stack_layer_params_interleaved)
from ..distributed.sharding import compose_sharding_spec
from ..models.llama import (LlamaConfig, LlamaDecoderLayer, LlamaForCausalLM,
                            precompute_rope)
from ..optimizer.functional import FunctionalAdamW
from ..jit import _StateSwap, bind_state, extract_state

__all__ = ["PretrainConfig", "build_llama_pretrain_step",
           "make_hybrid_mesh_for", "flops_per_token", "flops_per_token_hw",
           "choose_remat_plan", "remat_order", "DecoderFamily",
           "decoder_family", "MOE_METRICS", "record_moe_metrics"]

_G_SAVED_BYTES = _obs.registry().gauge(
    "trainer.remat.saved_bytes",
    "bytes a chip of residuals the layers' checkpoints keep (remat full)")
_G_SAVED_LAYERS = _obs.registry().gauge(
    "trainer.remat.saved_layers",
    "layers whose checkpoint keeps the named residual", labels=("name",))
_G_SEQ_SHARDED = _obs.registry().gauge(
    "trainer.mp.seq_sharded",
    "1 where the built step holds its [B, S, H] activations [B, S/mp, H] a "
    "chip between a row-parallel and the next column-parallel product, "
    "else 0")

#: what a routed family's step says of its routing beside the loss
#: (`incubate.moe.routing_stats`, combined over the layers as the serving
#: engine's step counts are: sums, the fullest expert, the mean), and the
#: gauges `run_pretrain.run` keeps of them
MOE_METRICS = ("moe_pairs_routed", "moe_pairs_held", "moe_expert_rows_max",
               "moe_expert_rows_mean", "aux_loss", "moe_pair_rows_moved")
_G_MOE = {name: _obs.registry().gauge(
    "trainer.moe." + name.removeprefix("moe_"), text)
    for name, text in zip(MOE_METRICS, (
        "(token, expert) pairs the last logged step routed, summed over "
        "the routed layers",
        "of those, the pairs that met an expert this program holds",
        "rows of the fullest held expert in any layer of that step",
        "mean rows a held expert, mean over the layers",
        "sum over the layers of the load-balance term (before its "
        "coefficient)",
        "pair rows the dispatch's forward visited, summed over the routed "
        "layers (`ops.grouped_gemm.pair_rows_visited`)"))}


def record_moe_metrics(metrics: Mapping[str, Any]) -> Dict[str, float]:
    """The routing numbers of one step's `metrics` as floats, kept as
    the gauges `trainer.moe.*`; {} for a dense family's step."""
    out = {k: float(metrics[k]) for k in MOE_METRICS if k in metrics}
    for k, v in out.items():
        _G_MOE[k].set(v)
    return out


#: What `remat` "full" leaves free on a chip BESIDE the step program when
#: it chooses the residuals to keep: a program's `memory_analysis()` is
#: the compiler's own peak, and beside it live the batch, whatever the
#: caller still holds on the first chip (a reference check's programs)
#: and the allocator's fragments.
REMAT_MARGIN_BYTES = 1 << 30


class PretrainConfig:
    def __init__(self, model: LlamaConfig, global_batch=8, seq_len=512,
                 n_microbatches=1, lr=3e-4, weight_decay=0.1,
                 param_dtype="bfloat16", grad_clip=1.0,
                 dp=1, mp=1, pp=1, sharding=1, sep=1, vpp=1,
                 scan_layers: bool = True, remat: str = "full",
                 ce_chunks: int = 4, pp_schedule: str = "compiled",
                 moment_dtype: str = "float32"):
        self.model = model
        self.global_batch = global_batch
        self.seq_len = seq_len
        self.n_microbatches = n_microbatches
        self.lr = lr
        self.weight_decay = weight_decay
        self.param_dtype = param_dtype
        self.grad_clip = grad_clip
        self.dp, self.mp, self.pp = dp, mp, pp
        self.sharding, self.sep = sharding, sep
        # vpp > 1 = interleaved virtual-pipeline schedule (ref:
        # virtual_pp_degree / PipelineParallelWithInterleave)
        self.vpp = vpp
        # scan_layers=False unrolls the per-stage layer loop. On this
        # device generation each while-loop iteration costs ~2ms of host
        # round-trip, so unrolling 16 layers saves ~60ms/step fwd+bwd at
        # the price of longer compiles (ref parity: CINN-style tradeoff).
        self.scan_layers = scan_layers
        # remat: "full" puts every layer under a checkpoint (fleet
        # recompute parity) that keeps what the device has room for:
        # named residuals chosen by bytes against the memory the device
        # reports free beyond the program that keeps nothing
        # (`choose_remat_plan`; nothing where it reports no limit),
        # "dots" saves matmul outputs (recompute only elementwise),
        # "none" stores all residuals.
        if remat not in ("full", "dots", "none"):
            raise ValueError(f"remat must be full|dots|none, got {remat!r}")
        self.remat = remat
        # sequence chunks for the softmax-CE loss: bounds peak logits
        # memory at B*S/ce_chunks*vocab f32 (per-chunk remat)
        if ce_chunks < 1:
            raise ValueError(f"ce_chunks must be >= 1, got {ce_chunks}")
        self.ce_chunks = ce_chunks
        # pipeline execution strategy (ref: fleet pipeline_scheduler_pass):
        #   "compiled" — scan+ppermute program, autodiff'd (GPipe-class
        #                memory; + interleaved when vpp>1);
        #   "1F1B" / "ZBH1" / "FThenB" — the pp_schedule timetable run by
        #                the distributed.pp_exec executor (1F1B bounds
        #                live activations by stage depth, ZBH1 also fills
        #                bubbles with deferred weight-grads). Timetable
        #                modes imply stage-level remat and require vpp=1.
        if pp_schedule not in ("compiled", "1F1B", "ZBH1", "FThenB", "VPP"):
            raise ValueError(f"unknown pp_schedule {pp_schedule!r}")
        if pp_schedule == "VPP" and vpp <= 1:
            raise ValueError("pp_schedule='VPP' needs vpp>1 virtual "
                             "chunks per stage")
        if vpp > 1 and pp_schedule not in ("compiled", "VPP", "1F1B"):
            raise ValueError(f"pp_schedule={pp_schedule!r} does not "
                             f"support vpp>1 (use 'VPP' for the "
                             f"interleaved timetable executor)")
        if pp_schedule != "compiled" and pp <= 1:
            raise ValueError(f"pp_schedule={pp_schedule!r} requires "
                             f"pp>1 (got pp={pp}); a single stage has "
                             f"no pipeline to schedule")
        self.pp_schedule = pp_schedule
        # "bfloat16" halves Adam-state HBM (update math stays f32) —
        # the knob that admits a larger per-chip batch when optimizer
        # state crowds out activations
        if moment_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"moment_dtype must be float32|bfloat16, "
                             f"got {moment_dtype!r}")
        self.moment_dtype = moment_dtype


def make_hybrid_mesh_for(cfg: PretrainConfig, devices=None) -> Mesh:
    return build_hybrid_mesh(dp_degree=cfg.dp, mp_degree=cfg.mp,
                             pp_degree=cfg.pp, sharding_degree=cfg.sharding,
                             sep_degree=cfg.sep, devices=devices)


def _ffn_params(c: LlamaConfig, pairs_held: Optional[float]) -> float:
    """Parameters of one layer's FFN that a token multiplies: the dense
    gate / up / down; for a routed layer (a config with
    `num_experts_per_tok`) the router plus `pairs_held` experts — the
    (token, expert) pairs a token that meet an expert HELD here, which
    is the routing's to say; None reckons a uniform router, top-k times
    the share of the experts held."""
    k = getattr(c, "num_experts_per_tok", None)
    if k is None:
        return 3 * c.hidden_size * c.intermediate_size
    held = getattr(c, "experts_held", None)
    if pairs_held is None:
        pairs_held = k * (held[1] / c.num_experts if held else 1.0)
    return (c.hidden_size * c.num_experts
            + pairs_held * 3 * c.hidden_size * c.moe_intermediate_size)


def _n_params(c: LlamaConfig, pairs_held: Optional[float] = None) -> float:
    return (c.vocab_size * c.hidden_size * (1 if c.tie_word_embeddings else 2)
            + c.num_hidden_layers * (
                c.hidden_size * c.head_dim
                * (c.num_attention_heads + 2 * c.num_key_value_heads)
                + c.num_attention_heads * c.head_dim * c.hidden_size
                + _ffn_params(c, pairs_held)
                + 2 * c.hidden_size)
            + c.hidden_size)


def flops_per_token(c: LlamaConfig,
                    pairs_held: Optional[float] = None) -> float:
    """6*N FLOPs/token — weight FLOPs only, NO attention term.  For a
    routed family N is the parameters a token MULTIPLIES (`_ffn_params`).

    This is the *model*-FLOPs MFU denominator (the conservative convention:
    attention score/value FLOPs the hardware actually performs are not
    credited, so MFU reported against this is a lower bound). For the
    hardware-FLOPs variant that adds the 12*L*h*s attention term, use
    `flops_per_token_hw`; both are reported in docs/FLAGSHIP.md.
    """
    return 6.0 * _n_params(c, pairs_held)


def flops_per_token_hw(c: LlamaConfig, seq_len: int,
                       pairs_held: Optional[float] = None) -> float:
    """6*N + attention FLOPs/token: the hardware-FLOPs MFU denominator.

    Attention adds 2 matmuls (QK^T and PV) per head per layer, each
    s*head_dim MACs = 2*s*head_dim FLOPs per token in the forward pass ->
    4*s*head_dim*n_heads*L forward FLOPs/token; the backward costs 2x the
    forward, so fwd+bwd = 3x -> 12 * L * n_heads * head_dim * seq_len per
    token (causal masking halves the realized work, but the dense
    convention is standard for MFU).  A config with `layer_types` (a
    family of sliding and full layers) is counted by the keys VISIBLE to
    a query instead: the causal mean (seq + 1) / 2 on a full layer, the
    band's mean on a sliding one.
    """
    kinds = getattr(c, "layer_types", None)
    if kinds is None:
        keys = float(c.num_hidden_layers * seq_len)
    else:
        def visible(w):
            w = seq_len if w is None else min(w, seq_len)
            # query i sees min(i + 1, w) keys
            return (w * (w + 1) / 2 + (seq_len - w) * w) / seq_len
        keys = sum(visible(c.window_of(kind)) for kind in kinds)
    attn = 12.0 * c.num_attention_heads * c.head_dim * keys
    return 6.0 * _n_params(c, pairs_held) + attn


#: state-dict key prefix of a decoder layer's parameter -> the step scope
#: (`observability.attribution.SCOPES`) of the part that uses it
_WEIGHT_SCOPES = (("input_layernorm", "attn_norm"),
                  ("post_attention_layernorm", "ffn_norm"),
                  ("mlp.gate_weight", "routed_ffn"),
                  ("mlp.w_", "routed_ffn"),
                  ("mlp.", "ffn"),
                  ("self_attn.o_proj", "attn_out"),
                  ("self_attn.", "qkv_proj"))


def _weight_scope(key: str) -> str:
    return next(s for prefix, s in _WEIGHT_SCOPES if key.startswith(prefix))


def remat_order(mp: int, routed: bool = False
                ) -> Tuple[Tuple[str, ...], ...]:
    """The residuals a layer's checkpoint may keep
    (`observability.attribution.RESIDUALS`), most recomputation saved a
    byte first.  That follows from shapes: the flash kernel is quadratic
    in the sequence for an output linear in it (both of its residuals or
    neither: its backward reads the two); a matmul's output saves its
    contraction length in FLOPs a byte, and that is the same hidden size
    for the three products a chip — the row product `attn_out` contracts
    hidden / mp columns but, held `[B, S/mp, H]` under the sequence
    layout, is 1 / mp of the bytes too.  What parts them under tensor
    parallelism is the collective a recomputation repeats: `attn_out`'s
    is the row product's own reduce-scatter (an all-reduce where the
    layout does not engage), which stands alone in the compiled step with
    nothing beside it, while the gathers into `qkv` and `gate_up` are
    made again whatever is kept, for their weight gradients read the
    gathered norm outputs (and they ride inside the backward's matmuls;
    the step compiled for a v5e 2x2, `PERF.md` section 6, PR 65).  So
    there `attn_out` goes before the other two; without `mp` it ranks
    with them, behind the smaller `qkv`.  A `routed` layer's FFN keeps
    `moe_gate_up` where a dense one keeps `gate_up`: the sorted pair
    rows' gate and up products, the same contraction a byte."""
    flash = ("flash_o", "flash_lse")
    ffn = ("moe_gate_up",) if routed else ("gate_up",)
    if mp > 1:
        return (flash, ("attn_out",), ("qkv",), ffn)
    return (flash, ("qkv",), ("attn_out",), ffn)


def choose_remat_plan(nbytes: Mapping[str, int], n_layers: int,
                      headroom: int, unrolled: bool,
                      order: Sequence[Tuple[str, ...]]
                      ) -> List[Tuple[str, ...]]:
    """The names each layer's checkpoint keeps: `order` walked while the
    bytes fit.  `nbytes` is a name's bytes a layer a chip and `headroom`
    what the chip has free beyond the program that keeps nothing.  An
    entry is taken for ALL layers while it fits; the first that does not
    fit whole is taken for the first layers it fits in where the layers
    are unrolled (under `lax.scan` one set serves every layer: for
    none), and the walk ends there."""
    plan: List[Tuple[str, ...]] = [()] * n_layers
    left = headroom
    for names in order:
        each = sum(nbytes[n] for n in names)
        fit = min(n_layers, max(left, 0) // each)
        if fit < n_layers and not unrolled:
            break
        plan = [kept + names if i < fit else kept
                for i, kept in enumerate(plan)]
        left -= fit * each
        if fit < n_layers:
            break
    return plan


def _take_back(plan: List[Tuple[str, ...]],
               order: Sequence[Tuple[str, ...]], nbytes: Mapping[str, int],
               over: Optional[int], unrolled: bool
               ) -> List[Tuple[str, ...]]:
    """`plan` with less of the entry of `order` it took last, after the
    compiled program needed `over` bytes more than fit (None: the
    compiler refused it outright).  Where the layers are unrolled, the
    LAST layers that keep the entry let go of it, as many as `over` is
    worth by `nbytes` and at least one (a program's need is not its
    floor's plus the bytes by shapes to the byte: the compiler places
    what it keeps); under `lax.scan`, or with nothing to reckon from,
    the whole entry."""
    taken = {n for kept in plan for n in kept}
    last = next(names for names in reversed(order) if taken & set(names))
    holders = [i for i, kept in enumerate(plan) if set(last) & set(kept)]
    n = len(holders)
    if unrolled and over is not None:
        n = min(n, max(1, -(-over // sum(nbytes[k] for k in last))))
    return [tuple(k for k in kept if k not in last)
            if i in holders[-n:] else kept for i, kept in enumerate(plan)]


def _bytes_limit(mesh: Mesh) -> Optional[int]:
    """The least `bytes_limit` this process's devices of the mesh report,
    None where one reports none (the CPU; a described device)."""
    limits = [memory_stats(d).get("bytes_limit")
              for d in mesh.devices.flat
              if d.process_index == jax.process_index()]
    return int(min(limits)) if limits and all(limits) else None


def _program_need(compiled) -> int:
    """What a compiled program holds a device at its peak, by the
    compiler's own account: arguments, outputs and scratch, less the
    donated arguments the outputs take over."""
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


class DecoderFamily(NamedTuple):
    """What the step builder reads of a decoder family.  A model config
    with a `pretrain_family()` method gives its own (`models/mellum.py`);
    one without is a dense Llama."""
    build: Callable         # config -> the causal LM (every parameter)
    layer: Callable         # the built LM -> the layers' template
    layer_prefix: str       # state-dict prefix of the decoder layers
    embed_key: str
    norm_key: str
    head_key: str
    #: a static kind a layer, handed to the template's call after the
    #: rotary tables; () where the layers are all alike
    kinds: Tuple[str, ...] = ()
    #: seq_len -> (cos, sin) as the template reads them; None: Llama's
    rope: Optional[Callable] = None
    #: not None: the template's `mlp` leaves a load-balance term and
    #: `routing_stats` (`l_aux`, `l_stats`) after each call, and the
    #: loss adds this coefficient times the layers' sum
    aux_coef: Optional[float] = None


_LLAMA_FAMILY = DecoderFamily(
    build=LlamaForCausalLM, layer=lambda lm: LlamaDecoderLayer(lm.config),
    layer_prefix="llama.layers.", embed_key="llama.embed_tokens.weight",
    norm_key="llama.norm.weight", head_key="lm_head.weight")


def decoder_family(mc) -> DecoderFamily:
    own = getattr(mc, "pretrain_family", None)
    return own() if own is not None else _LLAMA_FAMILY


def _period(kinds: Sequence[str]) -> int:
    """The shortest p with kinds[i] == kinds[i % p] for every layer."""
    return next((p for p in range(1, len(kinds) + 1)
                 if all(k == kinds[i % p] for i, k in enumerate(kinds))), 1)


class TrainState(NamedTuple):
    params: Any          # bf16 compute params
    master: Any          # f32 master weights
    opt_state: Any
    step: jnp.ndarray


def build_llama_pretrain_step(cfg: PretrainConfig, mesh: Mesh):
    """Returns (state, train_step, meta). train_step(state, batch_ids,
    labels) -> (state, metrics) — one fully-sharded jitted step.

    The build is the span ``trainer.build`` of the set-up ledger
    (`observability.tracing.recorder().setup()`), its sections disjoint
    children: ``.model`` (the eager float32 model), ``.state`` (the
    stacked and placed parameters, the optimizer state), ``.step`` (the
    rope tables and the step's closures) and ``.plan`` (the remat plan:
    ``.plan.floor`` and one ``.plan.try`` a compiled try). The chosen
    program has no compile of its own here: it is the plan's last try,
    or the `jax.jit` call's at the first step."""
    with _obs.sections("trainer.build") as section:
        return _build_step(section, cfg, mesh)


def _build_step(section, cfg: PretrainConfig, mesh: Mesh):
    mc = cfg.model
    family = decoder_family(mc)
    routed = family.aux_coef is not None
    section("model")
    with mesh_context(mesh):
        model = family.build(mc)
    param_dtype = jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32

    full_state = extract_state(model)

    section("state")
    # split decoder-layer params (pipelined & stacked) from outer params
    layer_prefix = family.layer_prefix
    per_layer: list = [dict() for _ in range(mc.num_hidden_layers)]
    outer: Dict[str, jnp.ndarray] = {}
    for k, v in full_state.items():
        if k.startswith(layer_prefix):
            rest = k[len(layer_prefix):]
            idx, sub = rest.split(".", 1)
            per_layer[int(idx)][sub] = v
        else:
            outer[k] = v

    n_stages = mesh.shape[PP_AXIS]
    if routed and (n_stages > 1 or cfg.vpp > 1):
        raise NotImplementedError(
            "a routed family's load-balance term does not cross the "
            "pipeline's stages yet: pp and vpp must be 1")
    if cfg.vpp > 1:
        stacked = stack_layer_params_interleaved(per_layer, n_stages, cfg.vpp)
    else:
        stacked = stack_layer_params(per_layer, n_stages)

    # sharding specs
    tmpl = family.layer(model)
    tmpl_sd = tmpl.state_dict()
    stacked_specs = {}
    n_lead = 3 if cfg.vpp > 1 else 2  # [S, (v,) L/stage, ...param dims]
    for k in stacked:
        base = getattr(tmpl_sd[k], "_sharding_spec", None) or P()
        entries = [PP_AXIS] + [None] * (n_lead - 1) + list(base) \
            + [None] * (stacked[k].ndim - n_lead - len(base))
        spec = P(*entries)
        stacked_specs[k] = spec
    model_sd = model.state_dict()
    outer_specs = {k: (getattr(model_sd[k], "_sharding_spec", None) or P())
                   for k in outer}

    # ZeRO composition on the sharding axis: inside each layer's weights,
    # never on the stage / chunk / layer-count dims (whole layers on one
    # rank would be broadcast to the others, not gathered from shards)
    zdeg = mesh.shape.get("sharding", 1)
    gathered_specs = stacked_specs
    stacked_specs = {k: compose_sharding_spec(
        stacked_specs[k], stacked[k].shape, "sharding", zdeg, n_lead)
        for k in stacked}
    outer_specs = {k: compose_sharding_spec(
        outer_specs[k], outer[k].shape, "sharding", zdeg) for k in outer}

    params = {"stacked": stacked, "outer": outer}
    specs = {"stacked": stacked_specs, "outer": outer_specs}

    def place(tree, specs_tree, dtype=None):
        out = {}
        for k, v in tree.items():
            arr = v.astype(dtype) if dtype is not None and \
                jnp.issubdtype(v.dtype, jnp.floating) else v
            out[k] = global_device_put(arr, NamedSharding(mesh, specs_tree[k]))
        return out

    master = {g: place(params[g], specs[g]) for g in params}
    compute = {g: place(params[g], specs[g], param_dtype) for g in params}

    tx = FunctionalAdamW(cfg.lr, beta1=0.9, beta2=0.95, epsilon=1e-8,
                         weight_decay=cfg.weight_decay,
                         clip_norm=cfg.grad_clip,
                         moment_dtype=cfg.moment_dtype)
    opt_state = tx.init(master)

    section("step")
    cos, sin = family.rope(cfg.seq_len) if family.rope is not None \
        else precompute_rope(mc.head_dim, cfg.seq_len, mc.rope_theta)
    # layers of one parameter shape and several static kinds: the unit
    # of the layer scan is a PERIOD, its kinds applied in order
    kinds = tuple(family.kinds)
    period = _period(kinds)

    def zero_gather(stacked_bf16):
        """Each layer's weights brought together over the sharding axis
        ONCE a step: an all-gather of shards on the bf16 copy, before the
        pipeline (outside its `shard_map`, whose stage body runs every
        tick) and outside the rematerialised layer bodies, whose saved
        inputs the gathered weights are, so that neither a recomputed
        forward nor the backward gathers them again.  The cotangent is
        constrained back to the shards: that is what lets the partitioner
        reduce-scatter a weight's gradient where it would otherwise
        all-reduce it whole."""
        def gather(k):
            def to(spec):
                return lambda v: jax.lax.with_sharding_constraint(
                    v, NamedSharding(mesh, spec))
            whole, shard = to(gathered_specs[k]), to(stacked_specs[k])
            f = jax.custom_vjp(whole)
            f.defvjp(lambda v: (whole(v), None),
                     lambda _, ct: (shard(ct),))
            return f
        out = {}
        for k, v in stacked_bf16.items():
            with _scope(_weight_scope(k)):    # the part that uses it
                out[k] = gather(k)(v)
        return out

    # stage body: apply L/S decoder layers via scan over the local slice;
    # per-layer remat (ref: fleet recompute intervals) keeps scan residuals
    # at O(hidden) instead of O(attention-scores) per layer
    def remat_wrap(kept: Tuple[str, ...]):
        if cfg.remat == "dots":
            return functools.partial(
                jax.checkpoint, policy=jax.checkpoint_policies.dots_saveable)
        if cfg.remat == "none":
            return lambda f: f
        if not kept:
            return jax.checkpoint
        return functools.partial(
            jax.checkpoint,
            policy=jax.checkpoint_policies.save_only_these_names(*kept))

    def stage_body(saved):
        """The stage function whose layer i keeps `saved[i]` under its
        checkpoint (`saved` empty: every layer keeps nothing).  A routed
        family's returns (h, {"aux": [layers], "stats": [layers, 5]})."""
        def stage_fn(params_slice, x, cos_, sin_):
            def body(kept, kind, h, layer_params):
                with _StateSwap([tmpl]), keeping(kept):
                    bind_state(tmpl, layer_params)
                    from ..core import autograd as ag
                    with ag.no_grad():
                        out = tmpl(Tensor(h), cos_, sin_, *kind)
                if not routed:
                    return out._data, None
                raw = lambda t: getattr(t, "_data", t)  # noqa: E731
                return out._data, {"aux": raw(tmpl.mlp.l_aux),
                                   "stats": raw(tmpl.mlp.l_stats)}

            def layer_fn(kept, j):
                """Layer j of a period under its own checkpoint."""
                return remat_wrap(kept)(functools.partial(
                    body, kept, (kinds[j],) if kinds else ()))

            def period_body(layers, h, period_params):
                ys = []
                for j, layer in enumerate(layers):
                    h, y = layer(
                        h, {k: v[j] for k, v in period_params.items()})
                    ys.append(y)
                return h, jax.tree.map(lambda *a: jnp.stack(a), *ys)
            n_local = jax.tree.leaves(params_slice)[0].shape[0]
            if n_local % period:
                raise ValueError(
                    f"{n_local} layers a stage do not hold whole periods "
                    f"of {period} layer kinds")
            # one scan a run of periods that keep the same: ONE where the
            # plan is one set for all (the empty one included)
            plan_ = list(saved) or [()] * n_local
            units = [tuple(plan_[i:i + period])
                     for i in range(0, n_local, period)]
            runs = [(kepts, len(list(g))) for kepts, g in
                    itertools.groupby(units)]
            sizes = [n * period for _, n in runs]
            assert sum(sizes) == n_local, (saved, n_local)
            split = {k: jax.lax.split(p, sizes)
                     for k, p in params_slice.items()} if len(runs) > 1 \
                else {k: [p] for k, p in params_slice.items()}
            h, extras = x, []
            for i, (kepts, n) in enumerate(runs):
                xs = {k: parts[i] for k, parts in split.items()}
                if period == 1:
                    fn = layer_fn(kepts[0], 0)
                else:
                    fn = functools.partial(period_body, [
                        layer_fn(kept, j) for j, kept in enumerate(kepts)])
                    xs = {k: v.reshape((n, period) + v.shape[1:])
                          for k, v in xs.items()}
                h, ys = jax.lax.scan(fn, h, xs,
                                     unroll=1 if cfg.scan_layers else n)
                extras.append(ys)
            if not routed:
                return h
            lead = 1 if period == 1 else 2      # [n(, period), ...]
            return h, jax.tree.map(
                lambda *a: jnp.concatenate(
                    [v.reshape((-1,) + v.shape[lead:]) for v in a]), *extras)
        return stage_fn

    embed_key, norm_key, head_key = (family.embed_key, family.norm_key,
                                     family.head_key)

    M = cfg.n_microbatches
    B, S = cfg.global_batch, cfg.seq_len
    assert B % M == 0

    use_timetable = cfg.pp_schedule != "compiled" and n_stages > 1
    if use_timetable:
        from ..distributed.pp_exec import scheduled_pipeline_loss
        from ..distributed.pp_schedule import generate_schedule
        # vpp>1 with a timetable mode runs the interleaved (VPP)
        # schedule through the chunked executor
        if cfg.vpp > 1:
            pp_timetable = generate_schedule("VPP", n_stages, M,
                                             n_chunks=cfg.vpp)
        else:
            pp_timetable = generate_schedule(cfg.pp_schedule, n_stages, M)
        pp_timetable.validate()
    # the sequence layout, as the layers traced on this mesh choose it
    # (`parallel_layers.seq_sharded_on`; the timetable executor suppresses
    # it in its branches): the embedding and the head follow the layers
    seq_on_mp = not use_timetable and seq_sharded_on(mesh, S)
    mp = mesh.shape.get("mp", 1)

    @_scope("head_loss")
    def _rms_head_loss(norm_w, w_head, h, labels_h, constrain=False,
                       onehot_pick=False):
        """final RMSNorm + chunked-CE SUM over h [.., S, H]. constrain
        adds the logits sharding hint (outer-graph path only — inside the
        timetable executor's shard_map the pp axis is manual).
        onehot_pick replaces the label-pick gather with a one-hot
        contraction: under the executor's partial-manual sharding a
        take_along_axis on sep-sharded logits trips the SPMD
        partitioner's device-group factorization CHECK
        (spmd_partitioner_util.cc:495); the contraction partitions
        cleanly (and rides the MXU)."""
        h32 = h.astype(jnp.float32)
        hn = (h32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(h32), -1, keepdims=True) + mc.rms_norm_eps)
        ).astype(h.dtype) * norm_w
        # under the sequence layout `h` arrives on S/mp rows a chip: the
        # norm runs on those, and a chunk is the SAME rows of every chip's
        # share ([.., mp, S/mp, H], cut along S/mp), so that a chunk's
        # gather into the vocabulary-parallel product and the
        # reduce-scatter of its cotangent stay inside the chunk (a sum
        # over tokens does not care which chunk holds which)
        ways = mp if constrain and seq_on_mp else 1
        span = S // ways            # the rows the chunks are cut along
        if ways > 1:
            rows = NamedSharding(mesh, P(("dp", "sharding"), "mp", None, None))
            hn = jax.lax.with_sharding_constraint(
                hn.reshape(hn.shape[:-2] + (ways, span, -1)), rows)
            labels_h = labels_h.reshape(labels_h.shape[:-1] + (ways, span))

        @jax.checkpoint
        def chunk_loss(h_c, labels_c):
            logits = h_c @ w_head
            if constrain:
                logits = jax.lax.with_sharding_constraint(
                    logits,
                    NamedSharding(mesh, P(("dp", "sharding"), None, "mp")))
            logits32 = logits.astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(logits32, axis=-1)
            if onehot_pick:
                oh = jax.nn.one_hot(labels_c, logits32.shape[-1],
                                    dtype=logits32.dtype)
                picked = (logits32 * oh).sum(-1)
            else:
                picked = jnp.take_along_axis(
                    logits32, labels_c[..., None], axis=-1)[..., 0]
            return (lse - picked).sum()

        n_chunks = min(cfg.ce_chunks, span)
        bounds = [i * span // n_chunks for i in range(n_chunks)] + [span]
        total = jnp.zeros((), jnp.float32)
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            h_c, labels_c = hn[..., lo:hi, :], labels_h[..., lo:hi]
            if ways > 1:
                h_c = h_c.reshape(h_c.shape[:-3] + (-1, h_c.shape[-1]))
                labels_c = labels_c.reshape(labels_c.shape[:-2] + (-1,))
            total = total + chunk_loss(h_c, labels_c)
        return total

    def loss_fn(saved, compute_params, ids, labels):
        stage_fn = stage_body(saved)
        if zdeg > 1:
            compute_params = dict(compute_params, stacked=zero_gather(
                compute_params["stacked"]))
        emb = compute_params["outer"][embed_key]
        with _scope("embed"):
            if mp > 1:
                # vocab-parallel lookup as a one-hot CONTRACTION: a
                # gather over the vocab-sharded table forces GSPMD into
                # involuntary full rematerialization (replicate the
                # table, then reshard — the r2-flagged SPMD warnings);
                # the contraction partitions cleanly (batch-sharded
                # one-hot x vocab-sharded table = local matmul + psum
                # over mp, the GSPMD analog of Megatron's range-mask +
                # allreduce) and rides the MXU
                oh = jax.nn.one_hot(ids, emb.shape[0], dtype=emb.dtype)
                if seq_on_mp:
                    # every row, this chip's vocabulary: with the output
                    # on S/mp rows the partitioner would otherwise gather
                    # the TABLE to every chip and all-reduce its gradient
                    oh = jax.lax.with_sharding_constraint(
                        oh, NamedSharding(mesh, P(("dp", "sharding"),
                                                  None, "mp")))
                x = oh @ emb                # [B,S,H]
            else:
                x = jnp.take(emb, ids, axis=0)  # [B,S,H]
            # under the sequence layout the vocabulary-parallel product
            # leaves as a reduce-scatter onto S/mp rows, as a layer's does
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(("dp", "sharding"),
                                         "mp" if seq_on_mp else "sep",
                                         None)))
        if use_timetable:
            # 1F1B/ZBH1/FThenB: the loss head runs ON the last stage
            # inside the executor (the cotangent seeds the interleaved
            # backward); embedding still differentiates through d_mbs.
            # The sep axis is GATHERED at this boundary: seq-sharded
            # operands inside the executor's switch branches deadlock
            # (see pp_exec composition-limit note); in-executor seq
            # parallelism rides mp (Megatron SP), ring context
            # parallelism composes with the compiled path instead.
            x_pp = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(("dp", "sharding"), None, None)))
            mbs = x_pp.reshape((M, B // M) + x_pp.shape[1:])
            if head_key in compute_params["outer"]:
                w_head = compute_params["outer"][head_key]
            else:
                w_head = emb.T
            hp = {"norm": compute_params["outer"][norm_key],
                  "head": w_head}
            labels_mb = labels.reshape((M, B // M, S))
            # one-hot label pick only where it's needed (sep axis in the
            # mesh): it dodges the partitioner CHECK on the gather but
            # costs an O(tokens x vocab) one-hot per CE chunk
            use_onehot = mesh.shape.get("sep", 1) > 1
            total = scheduled_pipeline_loss(
                pp_timetable, stage_fn,
                lambda hp_, y, lab: _rms_head_loss(hp_["norm"],
                                                   hp_["head"], y, lab,
                                                   onehot_pick=use_onehot),
                mesh, compute_params["stacked"], hp, mbs, labels_mb,
                extra_args=(cos.astype(x.dtype), sin.astype(x.dtype)),
                mb_auto_spec=P(("dp", "sharding"), None, None))
            return total / (B * S)
        mbs = x.reshape((M, B // M) + x.shape[1:])
        # remat="full" keeps the stage-level checkpoint (per-tick
        # residual = stage input only, GPipe footprint); for "dots"/"none"
        # the stage body owns the policy — an outer checkpoint would
        # discard what dots_saveable deliberately saved
        if cfg.vpp > 1:
            outs = spmd_pipeline_interleaved(
                stage_fn, compute_params["stacked"], mbs, mesh, M, cfg.vpp,
                extra_args=(cos.astype(x.dtype), sin.astype(x.dtype)),
                remat=(cfg.remat == "full"))
        else:
            outs = spmd_pipeline(stage_fn, compute_params["stacked"], mbs,
                                 mesh, M,
                                 extra_args=(cos.astype(x.dtype),
                                             sin.astype(x.dtype)),
                                 remat=(cfg.remat == "full"))
        extras = None
        if routed:          # (h, the layers' terms) of each microbatch
            outs, extras = outs
        h = outs.reshape((B, S, -1))
        if head_key in compute_params["outer"]:
            w_head = compute_params["outer"][head_key]
        else:
            w_head = emb.T
        # Chunked softmax cross-entropy (in _rms_head_loss): never
        # materializes the full [B, S, vocab] f32 logits (the reference's
        # c_softmax_with_cross_entropy solves the same memory blow-up for
        # TP; here the lever is chunking + per-chunk remat — bwd
        # recomputes each chunk's logits instead of keeping 4·B·S·V
        # bytes live). Uneven ceil-division chunk boundaries keep the
        # bound for every S with ≤2 compiled chunk variants.
        total = _rms_head_loss(compute_params["outer"][norm_key], w_head,
                               h, labels, constrain=True)
        loss = total / (B * S)
        if not routed:
            return loss
        # the load-balance term is a statistic of a microbatch's tokens:
        # the layers' sum, mean over the microbatches; the routing
        # numbers combine as the serving engine's step counts do
        aux = extras["aux"].sum(-1).mean()
        st = jax.lax.stop_gradient(extras["stats"])        # [M, L, 6]
        said = {"moe_pairs_routed": st[..., 0].sum(),
                "moe_pairs_held": st[..., 1].sum(),
                "moe_expert_rows_max": st[..., 2].max(),
                "moe_expert_rows_mean": st[..., 3].mean(),
                "aux_loss": aux,
                "moe_pair_rows_moved": st[..., 5].sum()}
        return loss + family.aux_coef * aux, said

    def step_with(saved, state: TrainState, ids, labels):
        def cast_loss(master_params):
            return loss_fn(saved, decorate_tree(master_params, param_dtype),
                           ids, labels)
        # Pallas kernels in the step run per-shard on this mesh
        said = {}
        with kernel_mesh(mesh):
            if routed:
                (loss, said), grads = jax.value_and_grad(
                    cast_loss, has_aux=True)(state.master)
            else:
                loss, grads = jax.value_and_grad(cast_loss)(state.master)
        # gradient clip, AdamW and the cast back, on each rank's shards
        with _scope("update"):
            new_master, new_opt, gnorm = tx.update(grads, state.opt_state,
                                                   state.master)
            new_params = decorate_tree(new_master, param_dtype)
        return TrainState(new_params, new_master, new_opt,
                          state.step + 1), {"loss": loss,
                                            "grad_norm": gnorm, **said}

    state = TrainState(compute, master, opt_state, jnp.zeros((), jnp.int32))

    data_spec = NamedSharding(mesh, P(("dp", "sharding"), None))
    batch_shape = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=data_spec)

    def step_for(saved):
        """The jitted step whose layer i keeps `saved[i]`: a function
        (and so a trace) of its own for each plan."""
        saved = tuple(saved)

        def train_step(state: TrainState, ids, labels):
            return step_with(saved, state, ids, labels)
        return jax.jit(train_step, donate_argnums=(0,))

    def step_args(state: TrainState):
        """The step's arguments as shapes with `state`'s shardings (an
        uncommitted leaf, the step count before the first step, goes
        where the program puts it, as in the `jax.jit` call)."""
        def shape(a):
            placed = getattr(a, "committed", True)
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=a.sharding if placed else None)
        return jax.tree.map(shape, state), batch_shape, batch_shape

    # remat "full": what each layer's checkpoint keeps, chosen HERE from
    # what can be observed — the residuals' bytes a chip at these shapes
    # on this mesh against what the device reports free beyond the
    # program that keeps nothing (the FLOOR: today's program, and what a
    # device that reports no limit gets).  Two compiles before the first
    # step where it engages: the floor's, for its need, and the chosen
    # program's, whose own need is held to the same limit (what is too
    # much is taken back, `_take_back`, at a compile a try, and the floor
    # always remains); the `jax.jit` call
    # and `compiled_programs` find the second in the compile cache.
    # Under pp > 1 (either pipeline, the timetable executor too) a
    # stage-level checkpoint around the layers discards whatever a layer
    # kept, so the plan is empty there; with FLAGS_flash_impl "bundled"
    # the kernel's own custom_vjp carries no names, the rest applies.
    section("plan")
    order = remat_order(mp, routed)
    item = jnp.dtype(param_dtype).itemsize
    rows = -(-B // (mesh.shape.get("dp", 1) * zdeg)) \
        * -(-S // mesh.shape.get("sep", 1))      # tokens a chip
    heads = -(-mc.num_attention_heads // mp)
    nbytes = {
        "flash_o": rows * heads * mc.head_dim * item,
        "flash_lse": rows * heads * 4,
        # the row product is kept as it is held: S/mp rows a chip under
        # the sequence layout, every row without it
        "attn_out": rows * mc.hidden_size * item // (mp if seq_on_mp else 1),
        "qkv": rows * -(-(mc.num_attention_heads
                         + 2 * mc.num_key_value_heads) * mc.head_dim
                        // mp) * item}
    if routed:
        # the grouped GEMM's operand is ALL k pair rows a token, the
        # absent experts' sorted behind the held ones (ROADMAP S12)
        nbytes["moe_gate_up"] = (rows * mc.num_experts_per_tok
                                 * 2 * mc.moe_intermediate_size * item)
    else:
        nbytes["gate_up"] = rows * -(-2 * mc.intermediate_size // mp) * item
    plan: List[Tuple[str, ...]] = [()] * mc.num_hidden_layers
    jstep = step_for(())
    limit = _bytes_limit(mesh) \
        if cfg.remat == "full" and n_stages == 1 else None
    floor_need = chosen_need = None
    if limit is not None:
        fits = limit - REMAT_MARGIN_BYTES
        with _obs.span("trainer.build.plan.floor"):
            floor_need = chosen_need = _program_need(
                jstep.lower(*step_args(state)).compile())
        plan = choose_remat_plan(nbytes, len(plan), fits - floor_need,
                                 not cfg.scan_layers, order)
        tries = 0
        while any(plan):
            chosen, tries = step_for(plan), tries + 1
            try:
                with _obs.span("trainer.build.plan.try", n=tries):
                    need = _program_need(
                        chosen.lower(*step_args(state)).compile())
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                need = None         # the compiler itself refuses it
            if need is not None and need <= fits:
                jstep, chosen_need = chosen, need
                break
            plan = _take_back(plan, order, nbytes,
                              None if need is None else need - fits,
                              not cfg.scan_layers)
    section()
    record = {"layers": plan, "seq_sharded": seq_on_mp,
              "nbytes": dict(nbytes),
              "saved_bytes": sum(nbytes[n] for kept in plan for n in kept),
              "limit": limit, "margin": REMAT_MARGIN_BYTES,
              "headroom": None if limit is None else fits - floor_need,
              "floor_need": floor_need, "need": chosen_need}
    _G_SEQ_SHARDED.set(int(seq_on_mp))
    _G_SAVED_BYTES.set(record["saved_bytes"])
    for n in RESIDUALS:     # every name: 0 for one this family has not
        _G_SAVED_LAYERS.labels(name=n).set(sum(n in kept for kept in plan))

    # the init model is NOT kept: its f32 parameters are a second copy
    # of the state on the device (1.3 GiB at the 8B shard), stale after
    # the first step, and at the flagship recipe they were what made a
    # resumed run miss the step program's scratch reservation
    def compiled_programs(state: TrainState) -> Dict[str, Any]:
        """{"train_step": the step compiled for `state`'s shapes and
        shardings}, for whoever reads a trace of it afterwards
        (`observability.attribution.op_scopes`).  Lowered from shapes
        and answered by this process's own compile of the step or by
        the compile cache (`attribution.compile_named`); nothing runs
        on the devices and nothing here is called by the step."""
        with _obs.span("trainer.compiled_programs"):
            return {"train_step": compile_named(
                jstep, step_args(state), lambda: step_for(plan))}

    meta = {"mesh": mesh, "data_sharding": data_spec,
            "flops_per_token": flops_per_token(mc),
            "compiled_programs": compiled_programs,
            "remat_plan": record}
    return state, jstep, meta
