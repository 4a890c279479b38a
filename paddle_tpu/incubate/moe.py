"""Mixture-of-Experts with expert parallelism (SURVEY §2.3 P7).

Reference capability: python/paddle/incubate/distributed/models/moe/
moe_layer.py — gate (GShard top-2 w/ aux loss + capacity, Switch top-1,
naive) → global_scatter/global_gather collective ops (capacity-bucketed
all-to-all, paddle/fluid/operators/collective/global_scatter_op.*) →
parallel experts → combine.

TPU-native rework — no hand-written all-to-all ops:
- Experts live as STACKED weights [E, ...] whose expert dim carries a
  sharding spec on the expert mesh axis.
- Dispatch/combine are einsums against a capacity-bucketed one-hot dispatch
  tensor (the GShard formulation). When the expert dim is sharded, GSPMD
  lowers those einsums to exactly the all-to-all the reference codes by
  hand — riding ICI, overlapped by XLA's scheduler.
- A dropless path (megablocks pattern) sorts tokens by expert and runs ONE
  `lax.ragged_dot` grouped GEMM over all experts (paddle_tpu.ops.grouped_gemm).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.dispatch import apply
from ..core.tensor import Tensor
from .. import nn
from ..nn import initializer as I
from ..distributed.mesh import get_mesh
from ..observability.attribution import residual as _residual
from ..ops.grouped_gemm import (combine_pair_rows, dispatch_pair_rows,
                                grouped_gemm, pair_rows_visited)

__all__ = ["top_k_gating", "load_balance_loss",
           "load_balance_loss_all_choices", "router_z_loss",
           "MoELayer", "SwitchMoELayer", "global_scatter", "global_gather",
           "ClipGradForMOEByGlobalNorm"]


# ---------------------------------------------------------------------------
# gating
# ---------------------------------------------------------------------------

def top_k_gating(gates, k: int, capacity: int, *, renormalize: bool = True):
    """GShard-style top-k dispatch planner (pure function, jit-safe).

    gates: [T, E] softmax router probabilities.
    Returns (dispatch [T, E, C] 0/1, combine [T, E, C], aux_loss scalar).
    Priority is choice-major (all 1st choices claim capacity before any 2nd
    choice), matching the reference gate's capacity semantics.
    """
    T, E = gates.shape
    topv, topi = jax.lax.top_k(gates, k)                    # [T, k]
    mask = jax.nn.one_hot(topi, E, dtype=gates.dtype)       # [T, k, E]

    # position of each (token, choice) within its expert's queue, choice-major
    mask_km = jnp.swapaxes(mask, 0, 1).reshape(k * T, E)
    pos_km = jnp.cumsum(mask_km, axis=0) - mask_km
    pos = jnp.swapaxes(pos_km.reshape(k, T, E), 0, 1)       # [T, k, E]

    keep = mask * (pos < capacity)
    loc = jnp.sum(pos * keep, axis=-1).astype(jnp.int32)    # [T, k]
    kept_any = jnp.sum(keep, axis=-1)                       # [T, k] 0/1

    # aux load-balance loss on FIRST choices (GShard eq. 13)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask[:, 0, :], axis=0)
    aux = E * jnp.sum(me * ce)

    gv = topv * kept_any
    if renormalize:
        gv = gv / jnp.maximum(jnp.sum(gv, axis=-1, keepdims=True), 1e-9)
    oh_loc = jax.nn.one_hot(loc, capacity, dtype=gates.dtype) * \
        kept_any[..., None]
    dispatch = jnp.einsum("tke,tkc->tec", keep, oh_loc)
    combine = jnp.einsum("tk,tke,tkc->tec", gv, keep, oh_loc)
    return dispatch, combine, aux


def load_balance_loss(gates, expert_mask):
    """Switch-Transformer aux loss: E * sum_e mean(prob_e) * mean(frac_e)."""
    E = gates.shape[-1]
    return E * jnp.sum(jnp.mean(gates, axis=0) * jnp.mean(expert_mask, axis=0))


def router_z_loss(logits):
    """ST-MoE z-loss: mean(logsumexp(logits)^2) — keeps router logits small."""
    return jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)


# ---------------------------------------------------------------------------
# expert-parallel collectives parity (ref: global_scatter/global_gather ops)
# ---------------------------------------------------------------------------

def _expert_axis_or_none(axis: Optional[str]):
    m = get_mesh()
    if m is None:
        return None
    if axis is not None:
        return axis if (axis in m.axis_names and m.shape[axis] > 1) else None
    for cand in ("ep", "mp", "sharding", "dp"):
        if cand in m.axis_names and m.shape[cand] > 1:
            return cand
    return None


def _constrain_expert_dim(x, axis: Optional[str]):
    """Shard dim 0 (experts) of x on the expert mesh axis."""
    m = get_mesh()
    if m is None or axis is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(m, P(axis, *([None] * (x.ndim - 1)))))


def global_scatter(x, dispatch, expert_axis: Optional[str] = None):
    """Capacity-bucketed dispatch (ref: global_scatter_op). x [T, H],
    dispatch [T, E, C] → [E, C, H] with the expert dim sharded (GSPMD emits
    the all-to-all)."""
    xa = x._data if isinstance(x, Tensor) else jnp.asarray(x)
    out = jnp.einsum("tec,th->ech", dispatch, xa)
    return _constrain_expert_dim(out, _expert_axis_or_none(expert_axis))


def global_gather(expert_out, combine, expert_axis: Optional[str] = None):
    """Inverse of global_scatter (ref: global_gather_op): [E, C, H] +
    combine [T, E, C] → [T, H]."""
    ea = expert_out._data if isinstance(expert_out, Tensor) else \
        jnp.asarray(expert_out)
    ea = _constrain_expert_dim(ea, _expert_axis_or_none(expert_axis))
    return jnp.einsum("tec,ech->th", combine, ea)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def _held_ids(topi, held):
    """(mask of the pairs whose expert lies in `held = (first, count)`,
    their ids local to the held stack — `count`, one past the held
    groups, for the others)."""
    first, count = held
    mine = (topi >= first) & (topi < first + count)
    return mine, jnp.where(mine, topi - first, count)


def _group_limited(gates, n_group: int, topk_group: int):
    """`gates` [T, E] with every expert outside the `topk_group` best
    of the `n_group` groups of E / n_group CONSECUTIVE experts set
    below any score (scores are probabilities or sigmoids, >= 0). A
    group's score is the sum of its two largest gates (DeepSeek-V3's
    rule, the one `n_group` / `topk_group` name)."""
    T, E = gates.shape
    per = E // n_group
    best2 = jax.lax.top_k(gates.reshape(T, n_group, per), min(2, per))[0]
    keep = jax.lax.top_k(jnp.sum(best2, -1), topk_group)[1]  # [T, groups]
    kept = jnp.any(keep[..., None] == jnp.arange(n_group), 1)
    return jnp.where(jnp.repeat(kept, per, axis=1), gates, -1.0)


def _route(gates, top_k: int, renormalize: bool, held, scale: float,
           group=None, bias=None):
    """Top-k routing over ALL experts of `gates` [T, E]: (weights [T, k],
    expert ids [T, k], local ids, held mask). `held = (first, count)`
    names the contiguous experts whose weights this program holds: a
    pair whose expert lies outside gets weight 0 and the local id
    `count` (one past the held groups), so it sorts behind them and
    meets no expert. `scale` is the routed scaling factor. `group =
    (n_group, topk_group)` limits the choice to the best groups
    (`_group_limited`); the weights are still the gates' own. `bias`
    [E] is a per-expert correction added to the scores for the CHOICE
    (the groups' and the top-k's): it picks, it does not weigh — the
    weights are the chosen experts' own gates. With `held=None`, scale
    1, no group and no bias local ids are the ids and the mask is None:
    the numerics of the uncut layer, bit for bit."""
    scores = gates
    if bias is not None:
        gates = gates + bias
    if group is not None:
        n_group, topk_group = group
        if gates.shape[-1] % n_group or \
                topk_group * (gates.shape[-1] // n_group) < top_k:
            raise ValueError(
                f"{topk_group} of {n_group} groups over "
                f"{gates.shape[-1]} experts cannot give top-{top_k}")
        gates = _group_limited(gates, n_group, topk_group)
    topv, topi = jax.lax.top_k(gates, top_k)
    gv = topv if bias is None else jnp.take_along_axis(scores, topi, -1)
    if renormalize:
        gv = gv / jnp.maximum(jnp.sum(gv, -1, keepdims=True), 1e-9)
    if scale != 1.0:
        gv = gv * scale
    if held is None:
        return gv, topi, topi, None
    mine, local = _held_ids(topi, held)
    return jnp.where(mine, gv, 0.0), topi, local, mine


def routing_stats(topi, held, num_experts: int, live=None):
    """[5] float32 of one routed layer: pairs routed, pairs that met a
    held expert, the most rows any held expert received, the mean rows
    a held expert, held experts that received a row (the engine's
    `moe_*` step counts). `topi` [T, k] is
    the routing over all `num_experts`; `held = (first, count)` or None
    for all. `live` [T] bool names the rows a request owns: the padding
    rows of a fixed-shape launch are routed and computed like any
    other, but count for nothing here."""
    held = held if held is not None else (0, num_experts)
    count = held[1]
    mine, local = _held_ids(topi, held)
    n_routed = jnp.asarray(topi.size, jnp.float32)
    if live is not None:
        mine = mine & live[:, None]
        local = jnp.where(live[:, None], local, count)
        n_routed = jnp.sum(live).astype(jnp.float32) * topi.shape[1]
    sizes = jnp.bincount(local.reshape(-1), length=count + 1)[:count]
    n_held = jnp.sum(mine).astype(jnp.float32)
    return jnp.stack([n_routed, n_held,
                      jnp.max(sizes).astype(jnp.float32), n_held / count,
                      jnp.sum(sizes > 0).astype(jnp.float32)])


def load_balance_loss_all_choices(gates, topi):
    """The Qwen-MoE / Mixtral families' `load_balancing_loss_func` on
    one layer: E * sum_e P_e F_e with P_e the mean gate of expert e and
    F_e its share of the tokens summed over ALL k choices (so a uniform
    router reads k, not 1)."""
    E = gates.shape[-1]
    chosen = jnp.sum(jax.nn.one_hot(topi, E, dtype=gates.dtype), axis=1)
    return E * jnp.sum(jnp.mean(gates, axis=0) * jnp.mean(chosen, axis=0))


def _expert_act(activation: str, up, gate):
    """The experts' nonlinearity on the up-projection `up`: `swiglu`
    gates it with `gate()` (the third matrix's product, made only
    here), `relu2` squares its positive part (two matrices an expert),
    anything else is gelu."""
    if activation == "swiglu":
        return jax.nn.silu(gate()) * up
    if activation == "relu2":
        return jnp.square(jax.nn.relu(up))
    return jax.nn.gelu(up)


def dense_expert_ffn(xt, gates, wg, wu, wd, *, top_k: int,
                     renormalize: bool, activation: str = "swiglu",
                     held=None, scale: float = 1.0, group=None,
                     bias=None):
    """Decode-sized routed FFN: run EVERY (held) expert on every token
    and weighted-select. At serving token counts (T <= ~32) this beats
    the sort+grouped-GEMM path, whose per-expert tiles pad to 128 rows —
    and it is bitwise-identical to it (same per-row matmuls, same
    combine), so the cached-decode exact-match contract is preserved.
    `held` / `scale` / `group` / `bias`: see `dropless_expert_ffn`."""
    gv, topi, local, mine = _route(gates, top_k, renormalize, held, scale,
                                   group, bias)
    up = jnp.einsum("th,ehi->eti", xt, wu)
    act = _expert_act(activation, up,
                      lambda: jnp.einsum("th,ehi->eti", xt, wg))
    down = jnp.einsum("eti,eih->eth", act, wd)          # [E, T, H]
    # combine EXACTLY like the grouped path: gather the k selected expert
    # outputs per token and reduce over k in rank order (a different
    # summation order would argmax-flip near-tied logits vs the
    # buffer/grouped path and break the exact-match contract)
    T = xt.shape[0]
    if mine is not None:
        local = jnp.minimum(local, wu.shape[0] - 1)     # weight 0 there
    sel = down[local, jnp.arange(T)[:, None]]           # [T, k, H]
    y = jnp.einsum("tk,tkh->th", gv.astype(sel.dtype), sel)
    return y, topi


def dropless_expert_ffn(xt, gates, wg, wu, wd, *, top_k: int,
                        renormalize: bool, activation: str = "swiglu",
                        held=None, scale: float = 1.0, group=None,
                        bias=None):
    """Per-token top-k routed expert FFN, dropless (megablocks pattern:
    flatten (token, choice) rows, sort by expert, one ragged grouped GEMM,
    unsort, weighted-combine). SINGLE SOURCE OF TRUTH for the routing
    numerics — MoELayer's training forward and the cached-decode serving
    path (generation._ffn_apply) both call this, so the serving exact-match
    contract cannot drift. Returns (y [T, H], topi [T, k]).

    One chip's share of an expert-parallel layer: `gates` [T, E] covers
    ALL experts, `wg` / `wu` / `wd` stack only the `held = (first,
    count)` contiguous ones. Routing (top-k, renormalise, `scale`) is
    that of the whole layer; the pairs of other chips' experts sort
    BEHIND the held groups, where the grouped GEMM owns no row of them
    (rows past the last group cost no tile; on the chip they come back
    as whatever the kernel found there, forward and backward), and
    weigh nothing in the combine. The result is this chip's addend of
    the layer's routed sum. `gates` are whatever scores the router
    gives (a softmax, or sigmoids); `group` limits the choice to the
    best groups of experts and `bias` corrects it (`_route`).
    `activation` "relu2" is the non-gated expert of two matrices
    (`wg` None): relu(x U)^2 V.

    The choice runs under the scope `moe_route`, the sort and gather
    under `moe_dispatch` and the unsort and weighted sum under
    `moe_combine` (names only), so that a trace tells them from the
    grouped GEMMs, which stay directly under the caller's scope
    (`observability.attribution.SCOPE_ALIASES`); the sorted rows' gate /
    up products are the residual `moe_gate_up` where a checkpoint around
    the caller keeps it.  Dispatch and combine carry their own
    backward (`ops.grouped_gemm.dispatch_pair_rows` /
    `combine_pair_rows`): gathers alone, the combine's in sorted space,
    and neither the value nor the cotangent of a row no held expert
    owns is used.  A call with `held` and more pair rows than
    `PAIR_ROW_CHUNK` visits the owned prefix of the sorted rows only;
    every other call's forward is one plain gather each way."""
    E = wu.shape[0]
    with jax.named_scope("moe_route"):
        gv, topi, local, mine = _route(gates, top_k, renormalize, held,
                                       scale, group, bias)
    with jax.named_scope("moe_dispatch"):
        srt, sizes, order, inv, n_owned = dispatch_pair_rows(
            xt, local, mine, E)
    up = _residual(grouped_gemm(srt, wu, sizes), "moe_gate_up")
    act = _expert_act(activation, up, lambda: _residual(
        grouped_gemm(srt, wg, sizes), "moe_gate_up"))
    down = grouped_gemm(act, wd, sizes)
    with jax.named_scope("moe_combine"):
        y = combine_pair_rows(down, gv, order, inv, mine, n_owned)
    return y, topi


class MoELayer(nn.Layer):
    """Top-k routed MoE FFN (GShard/Qwen2-MoE pattern).

    Capacity mode (default): GShard dispatch einsums (drops overflow tokens).
    Dropless mode: sort-by-expert + grouped GEMM (`lax.ragged_dot`) — no
    drops, megablocks-style; single-program, EP via sharded expert weights.
    After forward, ``self.l_aux`` holds the aux loss (Tensor, differentiable).
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 top_k: int = 2, capacity_factor: float = 1.25,
                 activation: str = "swiglu", dropless: bool = False,
                 renormalize: bool = True, expert_axis: Optional[str] = None,
                 shared_expert_hidden: int = 0, z_loss_weight: float = 0.0,
                 name=None, experts_held=None, routed_scale: float = 1.0,
                 score: str = "softmax", n_group: int = 1,
                 topk_group: int = 1, correction_bias: bool = False,
                 aux_choices: str = "first"):
        super().__init__()
        # which choices the load-balance term counts (`_dropless`)
        if aux_choices not in ("first", "all"):
            raise ValueError(f"aux_choices must be first|all, got "
                             f"{aux_choices!r}")
        if aux_choices == "all" and not dropless:
            raise NotImplementedError("aux_choices='all' needs "
                                      "dropless=True")
        self.aux_choices = aux_choices
        if activation not in ("swiglu", "gelu"):
            raise ValueError(f"unsupported activation: {activation}")
        # the router's scores (a softmax over the experts, or one
        # sigmoid an expert) and the group limit of its top-k (`_route`)
        if score not in ("softmax", "sigmoid"):
            raise ValueError(f"unsupported router score: {score}")
        if (score != "softmax" or n_group > 1 or correction_bias) \
                and not dropless:
            raise NotImplementedError(
                "sigmoid scores, group-limited routing and a correction "
                "bias need dropless=True")
        self.score = score
        self.route_group = (int(n_group), int(topk_group)) \
            if n_group > 1 else None
        # one chip's share of an expert-parallel layer: the router
        # covers all `num_experts`, the stacks hold `experts_held =
        # (first, count)` of them (dropless_expert_ffn)
        if experts_held is not None:
            first, count = (int(v) for v in experts_held)
            if not (0 <= first and count >= 1
                    and first + count <= num_experts):
                raise ValueError(
                    f"experts_held {experts_held} outside 0..{num_experts}")
            if not dropless:
                raise NotImplementedError(
                    "experts_held needs dropless=True: the capacity "
                    "dispatch einsums run over every expert")
            experts_held = (first, count)
        if routed_scale != 1.0 and not dropless:
            raise NotImplementedError("routed_scale needs dropless=True")
        self.experts_held = experts_held
        self.routed_scale = float(routed_scale)
        self.d_model, self.d_hidden = d_model, d_hidden
        self.num_experts, self.top_k = num_experts, top_k
        self.capacity_factor = capacity_factor
        self.activation = activation
        self.dropless = dropless
        self.renormalize = renormalize
        self.expert_axis = expert_axis
        self.z_loss_weight = z_loss_weight
        self.l_aux = None
        # the last forward's `routing_stats` and the pair rows its
        # dispatch visited (dropless only; an array of the trace it was
        # made in, as `l_aux` is)
        self.l_stats = None

        H, Iw = d_model, d_hidden
        Eg = num_experts                # the router's outputs
        E = experts_held[1] if experts_held else num_experts
        init = I.XavierNormal()
        espec = lambda *rest: P("ep" if expert_axis is None else expert_axis,
                                *rest)  # noqa: E731
        self.gate_weight = self.create_parameter(
            [H, Eg], default_initializer=I.Normal(0.0, 0.02))
        # a per-expert correction of the CHOICE (`_route`: it picks, it
        # does not weigh), over all the router's outputs
        self.e_score_correction_bias = self.create_parameter(
            [Eg], default_initializer=I.Constant(0.0)) \
            if correction_bias else None
        self.w_up = self.create_parameter([E, H, Iw], default_initializer=init)
        self.w_up._sharding_spec = espec(None, None)
        if activation == "swiglu":
            self.w_gate = self.create_parameter(
                [E, H, Iw], default_initializer=init)
            self.w_gate._sharding_spec = espec(None, None)
        else:
            self.w_gate = None
        self.w_down = self.create_parameter([E, Iw, H],
                                            default_initializer=init)
        self.w_down._sharding_spec = espec(None, None)
        if shared_expert_hidden:
            self.shared_up = nn.Linear(H, shared_expert_hidden,
                                       bias_attr=False)
            self.shared_gate = nn.Linear(H, shared_expert_hidden,
                                         bias_attr=False)
            self.shared_down = nn.Linear(shared_expert_hidden, H,
                                         bias_attr=False)
        else:
            self.shared_up = None

    # -- expert FFN on dispatched tokens [E, C, H] -> [E, C, H]
    def _expert_ffn(self, disp, w_gate, w_up, w_down):
        up = jnp.einsum("ech,ehi->eci", disp, w_up)
        if self.activation == "swiglu":
            g = jnp.einsum("ech,ehi->eci", disp, w_gate)
            act = jax.nn.silu(g) * up
        else:
            act = jax.nn.gelu(up)
        return jnp.einsum("eci,eih->ech", act, w_down)

    def _capacity(self, T: int) -> int:
        c = int(self.capacity_factor * self.top_k * T / self.num_experts)
        return max(c, self.top_k)

    def forward(self, x):
        eaxis = _expert_axis_or_none(self.expert_axis)
        shape = x.shape
        T = 1
        for d in shape[:-1]:
            T *= d
        cap = self._capacity(T)
        k, E = self.top_k, self.num_experts

        inputs = [x, self.gate_weight, self.w_up, self.w_down]
        if self.w_gate is not None:
            inputs.append(self.w_gate)
        if self.e_score_correction_bias is not None:
            inputs.append(self.e_score_correction_bias)

        def impl(xa, gw, wu, wd, *rest):
            rest = list(rest)
            bias = rest.pop().astype(jnp.float32) \
                if self.e_score_correction_bias is not None else None
            wg = rest[0] if rest else None
            xt = xa.reshape(T, shape[-1])
            with jax.named_scope("moe_route"):
                logits = (xt.astype(jnp.float32)
                          @ gw.astype(jnp.float32))       # [T, E] f32 router
                gates = jax.nn.sigmoid(logits) if self.score == "sigmoid" \
                    else jax.nn.softmax(logits, axis=-1)
            stats = jnp.zeros((5,), jnp.float32)
            if self.dropless:
                y, aux, stats = self._dropless(xt, logits, gates, wg, wu,
                                               wd, bias)
            else:
                dispatch, combine, aux = top_k_gating(
                    gates, k, cap, renormalize=self.renormalize)
                dispatch = dispatch.astype(xa.dtype)
                combine = combine.astype(xa.dtype)
                disp = jnp.einsum("tec,th->ech", dispatch, xt)
                disp = _constrain_expert_dim(disp, eaxis)
                eout = self._expert_ffn(disp, wg, wu, wd)
                eout = _constrain_expert_dim(eout, eaxis)
                y = jnp.einsum("tec,ech->th", combine, eout)
            if self.z_loss_weight:
                aux = aux + self.z_loss_weight * router_z_loss(logits)
            return (y.reshape(shape).astype(xa.dtype),
                    aux.astype(jnp.float32), jax.lax.stop_gradient(stats))

        out, aux, stats = apply("moe_layer", impl, inputs)
        self.l_aux = aux
        self.l_stats = stats
        if self.shared_up is not None:
            from ..nn import functional as F
            s = F.silu(self.shared_gate(x)) * self.shared_up(x)
            out = out + self.shared_down(s)
        return out

    def _dropless(self, xt, logits, gates, wg, wu, wd, bias=None):
        """Megablocks pattern: flatten (token, choice) rows, sort by expert,
        one ragged grouped GEMM, unsort, weighted-combine.  Returns (y,
        the load-balance term, `routing_stats` with the pair rows the
        dispatch's forward visited behind them: [6]).

        The load-balance term, E * sum_e P_e F_e over ALL the router's
        outputs (P_e the mean gate), takes its F_e by family
        (`aux_choices`): "first" — the share of tokens whose FIRST
        choice is e (GShard eq. 13 / Switch; the capacity path's
        `top_k_gating`, `moe_llm.MoEDecoderLayer`, Laguna, and every
        family before ISSUE 66); "all" — that share summed over the k
        choices (HF `load_balancing_loss_func`: Mixtral, Qwen-MoE,
        Mellum2)."""
        k, E = self.top_k, self.num_experts
        y, topi = dropless_expert_ffn(xt, gates, wg, wu, wd, top_k=k,
                                      renormalize=self.renormalize,
                                      activation=self.activation,
                                      held=self.experts_held,
                                      scale=self.routed_scale,
                                      group=self.route_group, bias=bias)
        with jax.named_scope("moe_route"):
            if self.aux_choices == "all":
                aux = load_balance_loss_all_choices(gates, topi)
            else:
                aux = load_balance_loss(
                    gates, jax.nn.one_hot(topi[:, 0], E, dtype=gates.dtype))
            stats = routing_stats(topi, self.experts_held, E)
            held = self.experts_held
            moved = pair_rows_visited(topi.size, None if held is None else
                                      jnp.sum(_held_ids(topi, held)[0]))
        return y, aux, jnp.append(stats, moved.astype(stats.dtype))


class SwitchMoELayer(MoELayer):
    """Switch Transformer: top-1 routing, capacity_factor ~1.0-2.0."""

    def __init__(self, d_model, d_hidden, num_experts,
                 capacity_factor: float = 2.0, **kw):
        kw.setdefault("activation", "gelu")
        super().__init__(d_model, d_hidden, num_experts, top_k=1,
                         capacity_factor=capacity_factor, **kw)


class ClipGradForMOEByGlobalNorm:
    """MoE-aware global-norm clip (ref: ClipGradForMOEByGlobalNorm [M]):
    expert-parallel grads are summed into the norm once per expert shard;
    under GSPMD the sharded weights already hold distinct shards per device,
    so a plain global norm over all (param, grad) pairs is correct — this
    class exists for API parity and for marking moe params."""

    def __init__(self, clip_norm: float, is_expert_param_fn=None,
                 moe_group=None):
        self.clip_norm = float(clip_norm)
        self.is_expert_param_fn = is_expert_param_fn

    def __call__(self, params_grads):
        from ..nn.clip import clip_grad_norm_
        params = [p for p, g in params_grads]
        clip_grad_norm_(params, self.clip_norm)
        return params_grads
