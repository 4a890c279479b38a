"""Continuous-batching serving engine over the paged KV cache.

`ServingEngine.add_request/step/collect` drives FIXED-SHAPE jitted
device programs (static `max_slots` batch, per-slot active masking
through the page tables) with the per-family math of the generation.py
cached step bodies. Requests join mid-decode (chunked prefill),
leave the instant they hit EOS/max-tokens (their pages return to the
pool immediately), and never retrace — one compile per (model-config,
slot-count) pair, checked by the PT002-gated tests.

The engine has ONE step program, the unified ragged step: ONE launch
per step. Every decode slot's rows — one token a decode row; 1 + k with
speculative drafts; a BLOCK of `block_length` rows where the model
generates by diffusion over blocks — and the oldest prefill request's
chunk ride a single flat token buffer through ONE per-layer chain, the
same at every width and on every backend: norm -> q / k / v
projections -> `fused_rope_append` (MLA: `fused_append_rows`) ->
`ragged_paged_attention` -> o-proj -> norm -> `_ffn_apply`
(`serving.engine.launches` counts the launches). Per-sequence row
tables (seq_start / num_tokens / kv_lengths / page table) make joins
and leaves pure data changes. The step is built from ONE description a
family (`_chain_of` -> `_Chain`: the blocks in order — a norm feeding
its mixers, or an FFN — the residual, the rotary rows, the family's pool
pytree) by ONE builder, `ServingEngine._chain_unified_body`; chunk-summary
attention and the looped decoder have builders of their own beside it
(`_make_unified_body`). Every family takes it on a TPU at
published widths: llama / MoE / Laguna / GPT heads of 64 or a multiple
of 128, and latent attention (MLA) whose latent rank is a multiple of
128 — its cache row (latent | rope key) is stored padded to whole
128-lane registers (`_latent_row_width`: 512 + 64 -> 640), K is the
row, V its first `kv_lora_rank` columns, one page fetch for both.
Chunk-summary (EVA) attention: its sequence is one page table of pooled
rows followed by the current window's exact rows
(`ChunkSummaryAllocator`, `_eva_unified_body`). A shape the ragged
kernel cannot tile on a TPU (`_ragged_step_eligible`: a head width that
is neither 64 nor a multiple of 128) is refused at construction with a
`ValueError` that names it; no constructor argument selects a program.

A launch computes the rows it carries. The body is compiled at two row
counts: `max_slots x R + prefill_chunk` flat rows (R = `_slot_rows`: 1 +
spec_k, or the block length), and the same tables' prefix of `max_slots
x R` rows with no chunk part (`_step_programs`; a block family's have
the riding commits' region between the slots' rows and the chunk's,
`_launch_rows`). `_unified_step` launches the second whenever no
prompt is being dispatched in the launch it builds — which the host
knows a launch ahead, like every other table — so a decode-only launch
pays for no idle chunk row: at a few hundred rows the layers' matmuls
are bound by the rows computed, not by the weights' bytes.

Inactive slots point their whole page table at the allocator's trash
page 0 with num_tokens 0: the step writes their (garbage) K/V into the
trash page and their logits are ignored on the host.

The unified step keeps ONE launch queued ahead (`_unified_step`):
call k of `step()` builds and dispatches launch k BEFORE it reads the
result of launch k-1, so the device finds its next program queued the
moment the last one ends and the host's admit / build / launch /
sample / account run beside a device step instead of between two. The
greedy token of every logits row is taken on the device and a decode
row of launch k is fed from there (the `feed` programs); the host reads launch
k-1's tokens after it has queued launch k. What `step()` returns and
what a `Request` shows (`tokens`, `prefill_pos`) is always RETIRED
work: results the host holds.

Greedy decoding only: the exactness contract (engine tokens ==
solo `generate_cached` tokens per request, the acceptance test) is a
greedy property; sampling strategies belong to the batch APIs.

Generation by diffusion over blocks (a model whose config has a
`block_length`: `models.sdar`; no constructor argument selects it). A
decode slot owns a block of B flat rows that see each other and
everything before them (the ragged kernel's `block` rule); the prompt's
whole blocks are prefilled under the same rule and its remainder opens
the first block as GIVEN tokens. A block is fed for `denoising_steps`
denoise passes — every pass writes the block's K/V again; on the device
(`_unmask`, scope `unmask`) the B / S still-masked rows of the largest
confidence take their argmax, and the block after the pass is the next
launch's input through the same `feed` programs, so the one launch
queued ahead survives — and ONE commit pass with the final tokens, after
which its K/V is final and its tokens are emitted: `Request.tokens`
grows by up to B at a commit, and only then. The schedule is static
(`models.sdar.block_passes`), so the host knows a launch ahead which
pass each slot is in; an EOS is seen a launch late, like any other.

A commit pass needs no launch of its own. Where the request has a next
block, the commit RIDES in the launch of that block's first denoise
pass: the block's B final rows go in as a block-sized prefill of the
slot's own sequence — a sequence entry of their own (the slot's page
table, the block's positions, a `kv_lengths` that ends at the block) in
a fixed region behind the block rows, fed from the launch in flight by
the same `feed` programs — written, attended, never sampled (no logits
are formed for them); the slot's own rows open the next block, and read
the riding block's FINAL K/V from the pages, because every layer appends
all rows before any row attends. So a block costs `denoising_steps`
launches, not one more, with the same passes, rule and tokens. The
region holds `_ride_slots` blocks (derived from `max_slots` and
`denoising_steps`; no argument sets it): a slot that finds it full and
a request's last block commit alone, as before. A listener (`on_block`)
and the step counts see a riding commit as the last PASS of its block,
before the next block's first.

Its exactness contract is the reference rule's (`benchmarks/lib/
reference_sdar.py`): every pass's logits, the rule on them, the tokens
committed. Drafting, the prefix cache, live-donor sharing, hand-off and
preemption are refused at construction by name.
"""

from __future__ import annotations

import functools
from contextlib import nullcontext
from typing import (Callable, Dict, List, NamedTuple, Optional,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from .. import resilience as _res
from ..observability import costmodel as _costmodel
from ..observability.attribution import compile_named, scope as _scope
from ..observability import tracing as _tracing
from ..generation import (_decode_params, _ffn_apply, _kvb_heads,
                          _llama_weights, _mm_heads, _mm_w)
from ..ops.fused import (append_run_count, append_run_table,
                         append_slot_run_table, append_tile,
                         fused_append_rows, fused_chunk_pool,
                         fused_layer_norm, fused_rms_norm,
                         fused_rope_append)
from ..models.bailing_hybrid import kda_gated_norm, kda_operands
from ..models.phi4flash import diff_combine, pair_queries, ssm1_operands
from ..models.sdar import block_passes
from ..models.nemotron_h import (ssm_conv, ssm_gated_norm, ssm_operands,
                                 ssm_split)
from ..models.ouro import exit_distribution as _exit_distribution
from ..ops.pallas_kda import (kda_chunk_scan, kda_state_update,
                              kda_tileable)
from ..ops.pallas_mhc import (mhc_enter, mhc_exit, mhc_post, mhc_pre,
                              mhc_tileable)
from ..ops.pallas_ssm import (HEADS_MINOR, ssm1_chunk_scan,
                              ssm1_state_update, ssm_chunk_scan,
                              ssm_state_put, ssm_state_update, state_layout,
                              state_pool_shape)
from ..ops.pallas_ragged import (ragged_head_block,
                                 ragged_kernel_eligible,
                                 ragged_narrow_rows, ragged_paged_attention,
                                 ragged_tile_block, ragged_tile_tokens,
                                 ragged_visit_counts)
from .block_allocator import ChunkSummaryAllocator, PageBlockAllocator
from .handoff import (HANDOFF_BYTES, HANDOFF_PAGES, HANDOFFS,
                      KVPageHandoff)
from .prefix_cache import PrefixCache
from .scheduler import DECODE, PREFILL, Request, Scheduler
from .spec_decode import accept_length, ngram_draft, record_verify

__all__ = ["ServingEngine"]

_REQS = _obs.registry().counter(
    "serving.engine.requests", "engine requests by outcome",
    labels=("outcome",))
_STEPS = _obs.registry().counter(
    "serving.engine.steps", "device steps launched", labels=("phase",))
_LAUNCHES = _obs.registry().counter(
    "serving.engine.launches", "device program launches by dispatch path",
    labels=("path",))
_TOKENS = _obs.registry().counter(
    "serving.engine.tokens", "tokens processed", labels=("phase",))
_ACTIVE = _obs.registry().gauge(
    "serving.engine.active_slots", "slots holding an in-flight request")
_WAITING = _obs.registry().gauge(
    "serving.engine.waiting", "requests queued for admission")
_REBUILDS = _obs.registry().counter(
    "serving.controller.rebuilds",
    "jit program rebuilds triggered by chunk/spec-k actuation "
    "(ServingEngine.reconfigure)", labels=("replica",))
_DIFF_PASSES = _obs.registry().counter(
    "serving.engine.diffusion_passes",
    "passes of a block of a model that generates by diffusion over "
    "blocks, a slot a launch: denoise (the rule reads its logits) or "
    "commit (it writes the block's final K/V); fused: the denoise "
    "passes whose launch also carried the commit of the block before",
    labels=("kind",))
_PREEMPTIONS = _obs.registry().counter(
    "serving.engine.preemptions",
    "low-priority decodes re-queued (pages intact) for a higher-"
    "priority arrival")
_G_HBM_WEIGHTS = _obs.registry().gauge(
    "serving.engine.hbm_weights_bytes",
    "resident decode weight-tree bytes (costmodel.tree_bytes)")
_G_HBM_POOL = _obs.registry().gauge(
    "serving.engine.hbm_page_pool_bytes",
    "resident KV page-pool bytes: layers x planes x kv_heads x "
    "num_pages x page_size x head_dim x itemsize")
_G_HBM_DRAFT = _obs.registry().gauge(
    "serving.engine.hbm_draft_bytes",
    "spec-decode draft state staged this step: draft + verify token "
    "ids for every extra row of the unified launch")
_G_BPT_MODEL = _obs.registry().gauge(
    "serving.engine.bytes_per_token_model",
    "cumulative costmodel.decode_step_budget bytes (evaluated at each "
    "step's batch and mean live context) / tokens processed")
_G_BPT_MEASURED = _obs.registry().gauge(
    "serving.engine.bytes_per_token_measured",
    "cumulative launch ledger / tokens processed: weight tree once "
    "per device launch + page-granular cache reads at actual lengths")
_TRACE = _tracing.recorder()

#: gauges sampled onto the chrome-trace counter tracks after each step
_COUNTER_GAUGES = (
    "serving.engine.active_slots", "serving.engine.waiting",
    "serving.engine.pages_used", "serving.engine.pages_free",
    "serving.engine.page_utilization",
    "serving.engine.page_fragmentation",
    "serving.engine.hbm_weights_bytes",
    "serving.engine.hbm_page_pool_bytes",
    "serving.engine.hbm_draft_bytes",
    "serving.engine.bytes_per_token_model",
    "serving.engine.bytes_per_token_measured",
)


def _lcp(a: np.ndarray, b: np.ndarray) -> int:
    n = min(a.size, b.size)
    if n == 0:
        return 0
    neq = np.nonzero(a[:n] != b[:n])[0]
    return int(neq[0]) if neq.size else n


def _ragged_step_eligible(heads, kv: int, d: int, page_size: int) -> bool:
    """The ONE question `ServingEngine` asks, once, at construction:
    does the ragged kernel tile every layer's head count here? On a TPU
    that is `ragged_kernel_eligible`; anywhere else the kernel runs
    interpreted and has no tiling constraint. A `False` is refused by
    name; a test that needs that answer patches THIS function."""
    return (jax.default_backend() != "tpu"
            or all(ragged_kernel_eligible(h, kv, d, page_size)
                   for h in heads))


def _kda_step_eligible(heads: int, d: int, chunk: int, sub: int) -> bool:
    """As `_ragged_step_eligible`, for a KDA block's two kernels: on a
    TPU the state update tiles whole registers and the prefill chunk is
    whole sub-chunks of the scan; interpreted, anything goes."""
    return (jax.default_backend() != "tpu"
            or (kda_tileable(heads, d, d) and chunk % sub == 0))


def _mhc_step_eligible(rows: int, n: int, C: int) -> bool:
    """As `_ragged_step_eligible`, for the two hyper-connection kernels:
    on a TPU the launch's flat rows are whole row blocks and a stream is
    whole registers (`mhc_tileable`); interpreted, anything goes. The
    kernels have no other path: the plain forms are the oracle."""
    return jax.default_backend() != "tpu" or mhc_tileable(rows, n, C)


def _refuse_shared_cache(why: str, enable_prefix_cache, spec_decode: int,
                         role: str) -> None:
    """What a cache whose pages are released mid-sequence cannot serve,
    refused at construction; `why` says which cache."""
    if enable_prefix_cache:
        raise ValueError(why + "a cached prefix cannot be adopted; "
                         "enable_prefix_cache must be off")
    if spec_decode:
        raise ValueError(why + "a rejected draft cannot roll the cache "
                         "back; spec_decode must be 0")
    if role != "colocated":
        raise ValueError(why + "export_request / import_request are not "
                         "supported; role must be 'colocated'")


def _latent_row_width(r: int, dr: int) -> int:
    """Columns of latent attention's cache row AS STORED: the latent
    [r] and the shared rope key [dr], then zeros up to whole 128-lane
    registers where the latent itself is lane-aligned (the published
    512 + 64 -> 640). A 576-wide minor dimension has no row-major
    tiled layout without padding: XLA lays such a pool out transposed
    and a Mosaic call then copies the WHOLE pool to relayout it, every
    layer. Padding once, in the shape, keeps the pool in place, every
    DMA and lane slice aligned, and costs 64 dead columns a token
    (`latent_row_bytes` says what is stored). Toy ranks (r % 128 != 0)
    run interpreted only and keep r + dr."""
    w = r + dr
    return w if r % 128 else -(-w // 128) * 128


def _pad_lanes(x, width: int):
    """x [..., w] with zero columns up to `width`."""
    pad = width - x.shape[-1]
    if not pad:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


def _pairs_heads(p) -> bool:
    """Whether the pool's KV head is TWO published heads side by side
    (`_pack_head_pairs`), read from the decode parameters' shapes, never
    a switch: a published head of half a 128-lane register, an even
    count of them, in a family whose attention is `_gqa_mixer` over
    separate q / k / v projections (differential heads are paired
    already). The constraint is the chip's (`_pack_head_pairs`: a 64-lane
    row has no tiled layout there), but the pairing is the POOL's layout,
    so it is decided from the model alone and holds on every backend: the
    program the CPU tests and the pins read is the one the chip runs, and
    the pools, the copy-on-write and hand-off programs and the off-chip
    compile see one shape a model. What it costs where nothing tiles —
    the zero half of a query's lanes in the scores and in PV — is
    arithmetic in a kernel that the page reads bound."""
    cfg = p["cfg"]
    return (p["family"] in ("llama", "moe", "laguna", "hybrid")
            and not p.get("diff") and getattr(cfg, "head_dim", 0) == 64
            and cfg.num_key_value_heads % 2 == 0)


def _pack_head_pairs(q, k, v, kv: int):
    """TWO published heads of width w side by side in one stored row of 2
    w lanes: q [T, H, w], k / v [T, 2 kv, w] (None: a mixer that appends
    nothing) -> q [T, H, 2 w], k / v [T, kv, 2 w]. A 64-wide row has no
    tiled layout on the chip but a padded one (the pool would hold, and
    every page fetch move, twice its bytes, and Mosaic refuses the
    64-lane slice of it), so heads 2 p and 2 p + 1 share row p. K's lanes
    are [first halves of a | of b | second halves of a | of b]: the
    append kernel's half-split rotary turn pairs lane i with lane i + w,
    each head's own two halves, under the row's angles tiled twice
    (`_pair_angles`). V's lanes are [a | b]. A query head of side s
    holds zeros in the other side's lanes, so its 2 w-wide score IS its
    own head's w-wide score; of its 2 w-wide output its side's w lanes
    are its own (`_unpack_head_pairs`)."""
    T, H, w = q.shape
    if k is not None:
        k = k.reshape(T, kv, 2, 2, w // 2).swapaxes(2, 3).reshape(
            T, kv, 2 * w)
        v = v.reshape(T, kv, 2 * w)
    side = jnp.arange(2)
    own = side[:, None, None, None, None] == side[None, None, None, :, None]
    q = jnp.where(own, q.reshape(T, kv, 2, H // (2 * kv), 2, 1, w // 2), 0)
    return q.reshape(T, H, 2 * w), k, v


def _pair_angles(cos, sin):
    """The rows' angles [T, w / 2] for rows of two heads (`_pack_head_
    pairs`): each head's, side by side."""
    return (jnp.concatenate([cos, cos], -1),
            None if sin is None else jnp.concatenate([sin, sin], -1))


def _unpack_head_pairs(o, kv: int):
    """o [T, H, 2 w] over paired rows -> [T, H, w]: each query head's
    own side of V's lanes."""
    T, H, w2 = o.shape
    o = o.reshape(T, kv, 2, H // (2 * kv), 2, w2 // 2)
    return jnp.stack([o[:, :, 0, :, 0], o[:, :, 1, :, 1]], 2).reshape(
        T, H, w2 // 2)


@functools.lru_cache(maxsize=None)
def _kernel_jit(fn, scope: str, static):
    def run(*args):
        with jax.named_scope(scope):
            return fn(*args, **dict(static))
    return jax.jit(run)


def _once(fn, scope: str, *args, **static):
    """``fn(*args, **static)`` under the name `scope`, inside a jitted
    copy of ``fn`` that every layer of a step — and every step program
    of the process — shares: a kernel is traced and lowered ONCE for
    equal shapes, not once a layer, and that is most of what a step
    program's first launch costs when the compile cache answers
    (`ragged_paged_attention` and `fused_append_rows` do the same
    themselves). XLA inlines the call: the compiled step is the one the
    plain call gives (`tests/test_tpu_aot_compile.py` compiles both).
    The name is opened here, around the call, and again inside the
    shared copy (static, innermost): the instructions answer to `scope`
    whichever layer's call was lowered first. `static` are ``fn``'s
    keyword arguments that are not arrays."""
    with jax.named_scope(scope):
        return _kernel_jit(fn, scope, tuple(sorted(static.items())))(*args)


# -- the step's entry and exit, shared by the jitted bodies ------------
def _seq_starts(B: int, R: int, riders: int = 0):
    """[B + riders + 1] baked row starts of the unified step: decode
    slot s owns rows [s*R, (s+1)*R), riding commit j (generation by
    diffusion over blocks, `_ride_slots`) the R rows from B*R + j*R, the
    prefill chunk the rows from (B + riders)*R on. R == 1 reduces to
    arange(B + 1)."""
    if riders:
        return jnp.arange(B + riders + 1, dtype=jnp.int32) * R
    return jnp.concatenate(
        [jnp.arange(B, dtype=jnp.int32) * R,
         jnp.asarray([B * R], jnp.int32)])


def _ride_slots(max_slots: int, steps: int) -> int:
    """Commits ONE launch of a model that generates by diffusion over
    blocks can carry riding behind the block rows: the slots that stand
    at the end of a block in a launch when the `steps` launches a fused
    block takes are spread evenly over the slots. A launch that finds
    more takes their commits alone, which moves those slots a launch on
    and evens the spread out; a longer region would cost EVERY launch
    its rows (idle rows of the flat buffer are routed and computed)."""
    return -(-max_slots // steps)


def _logit_rows(x, seq_start, num_tokens, K: int):
    """The rows of the unified step's x [1, T, H] whose logits go back
    to the host: each sequence's LAST flat row (idle slots, num_tokens
    0, index garbage the host ignores); with spec decoding every row —
    each drafted position is a verify point."""
    if K:
        return x[0]
    return x[0, jnp.clip(seq_start + num_tokens - 1, 0, x.shape[1] - 1)]


def _owned_rows(T: int, seq_start, num_tokens):
    """[T] bool: the rows of the flat buffer that a sequence owns this
    step. The routed layers' counts leave the idle rows out."""
    row = jnp.arange(T, dtype=jnp.int32)[:, None]
    return jnp.any((row >= seq_start) & (row < seq_start + num_tokens), -1)


def _moe_step_counts(moe_stats):
    """One [5] array beside the logits (STEP_COUNTS_MOE) from the
    routed layers' `routing_stats`: pairs routed and held summed over
    the layers, the fullest expert's rows, the mean rows a held
    expert, experts hit summed."""
    ms = jnp.stack(moe_stats)
    return jnp.stack([ms[:, 0].sum(), ms[:, 1].sum(), ms[:, 2].max(),
                      ms[:, 3].mean(), ms[:, 4].sum()])


def _head_logits(w, last):
    """Logits of the rows `last`: the quantized head through `_mm_w`,
    else the model's head or its tied embedding."""
    if "head_q" in w or "head_q4" in w:
        return _mm_w(last, w, "head")
    return last @ (w["head"] if w["head"] is not None else w["embed"].T)


def _greedy(logits):
    """[rows] int32: the greedy token of each logits row, taken on the
    device from the rows the host would take it from (the first index
    on ties, as `np.argmax`)."""
    return jnp.argmax(logits, -1).astype(jnp.int32)


def _unmask(block, logits, take, mask_id: int):
    """The transfer rule of generation by diffusion over blocks
    (``low_confidence_static``), on the device: `block` [M, B] the
    slots' blocks as this launch was fed them (`mask_id` where a row is
    still masked), `logits` [M * B, V] float32 of their rows, `take` [M]
    how many rows a slot's pass unmasks (0: a commit pass, or an idle
    slot). ``x0 = argmax(logits)``, ``c = max softmax(logits)``; among
    a slot's masked rows the `take` with the largest c — all that are
    left, if fewer; ties to the lower position — take their x0; no other
    row moves. -> the blocks after the pass [M, B]: the next pass's
    input, which never leaves the device (the `feed` programs)."""
    M, B = block.shape
    x0 = _greedy(logits).reshape(M, B)
    top = jnp.max(logits, -1, keepdims=True)
    conf = (1.0 / jnp.sum(jnp.exp(logits - top), -1)).reshape(M, B)
    masked = block == mask_id
    score = jnp.where(masked, conf, -1.0)       # c > 0 where masked
    at = jnp.arange(B)
    # rows that go before row i: a larger c, or the same at a lower place
    before = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None]) & (at[None, :] < at[:, None]))
    chosen = masked & (before.sum(-1) < take[:, None])
    return jnp.where(chosen, x0, block)


def _halves_rope(c, s):
    """The rotary turn of t [1, T, h, dr] by the rows' angles c, s [T,
    dr / 2], pairing (j, j + dr / 2): it runs on the split q_pe / k_pe
    shapes (not D-halved cache rows), so the append is the row-scatter
    kernel."""
    def rope(t):
        d2 = t.shape[-1] // 2
        t1, t2 = t[..., :d2], t[..., d2:]
        cc = c[None, :, None, :].astype(t.dtype)
        ss = s[None, :, None, :].astype(t.dtype)
        return jnp.concatenate(
            [t1 * cc - t2 * ss, t2 * cc + t1 * ss], -1)
    return rope


def _latent_mixer(L, h, rope, pool, seq_start, num_tokens, kv_lengths,
                  tables, runs, *, nh: int, dn: int, dr: int, dv: int,
                  r: int, width: int, eps: float, scale: float):
    """Latent attention in the ABSORBED form, on the normed rows h [1,
    T, H] of a sublayer's input: the cache row is (RMSNorm(latent) |
    RoPE(k_pe) | pad), the query of head a is (q_nope_a W_kvb^K_a |
    RoPE(q_pe_a) | 0), the kernel's output the weighted sum of the
    rows' latent columns, and W_kvb^V_a comes after; where the layer has
    a head gate (``wgate``), each head's output times its sigmoid
    before the out-projection. The prefill chunk rides the same form as
    the decode rows; `runs` is the step's one append work list
    (`ServingEngine._run_table`). -> (the mixer's output y [1, T, H],
    the pool): the caller's residual takes y (`_Residual.leave`). The chain's
    ``L`` mixer: the mla family's layers and a hybrid's ``L`` blocks."""
    T = h.shape[1]
    wkb = _kvb_heads(L, nh, h.dtype)
    w_k, w_v = wkb[:, :dn], wkb[:, dn:]
    with jax.named_scope("mla_q"):
        if "wqa" in L or "wqa_q" in L or "wqa_q4" in L:
            q = _mm_heads(_once(fused_rms_norm, "mla_q",
                                _mm_w(h, L, "wqa"), L["gq"], eps=eps),
                          L, "wqb")
        else:
            q = _mm_heads(h, L, "wq")
        q = q.reshape(1, T, nh, dn + dr)
        q_nope, q_pe = q[..., :dn], q[..., dn:]
        q_pe = rope(q_pe)
        q_eff = jnp.einsum("bsnd,ndr->bsnr", q_nope, w_k)
        q_cat = _pad_lanes(
            jnp.concatenate([q_eff, q_pe], -1)[0], width)
    with jax.named_scope("mla_kv"):
        kv_a = _mm_w(h, L, "wkva")           # [1, T, r+dr]
        lat = _once(fused_rms_norm, "mla_kv", kv_a[..., :r], L["gkv"],
                    eps=eps)
        k_pe = rope(kv_a[..., r:][:, :, None, :])[:, :, 0]
        rows = _pad_lanes(
            jnp.concatenate([lat, k_pe], -1)[0], width)
        with _scope("cache_write"):
            pool = fused_append_rows(pool, rows[:, None], runs,
                                     scope="cache_write")
    with jax.named_scope("mla_attention"):
        # K is the row, V its latent columns: one page
        # fetch serves both matmuls
        o_lat = ragged_paged_attention(
            q_cat, pool, None, seq_start, num_tokens,
            kv_lengths, tables, scale=scale, v_dim=r,
            scope="mla_attention")
    with jax.named_scope("mla_out"):
        o = jnp.einsum("tnr,nvr->tnv", o_lat, w_v)
        if "wgate" in L:
            o = o * jax.nn.sigmoid(
                (h[0] @ L["wgate"]).astype(jnp.float32))[
                    ..., None].astype(o.dtype)
        y = _mm_w(o.reshape(1, T, nh * dv), L, "wo")
    return y, pool


def _gqa_mixer(L, h, rope, pools, seq_start, num_tokens, kv_lengths, tables,
               runs, *, heads: int, kv: int, d: int, mults=None,
               window=None, diff=None, borrowed: bool = False,
               shared: bool = False, eps: float = 1e-5, block=None,
               ride=None, pack: int = 1):
    """Grouped-query attention on the normed rows h [1, T, H] of a
    block's input, over the pages `pools` = (K, V): q / k / v (+ their
    biases, where the layer has them; ONE fused ``wqkv`` + ``bqkv``
    split in three where the checkpoint ships that: gpt) ->
    `fused_rope_append` (rotary on
    all `d` dims, half-split pairs, by the rows' angles `rope` = (cos,
    sin) [T, d / 2]; with ``None``, or `_no_turn`'s pair, the model has
    NO rotary embedding and the kernel appends under the identity turn)
    -> `ragged_paged_attention` -> where the layer has a head gate
    (``wgate``, Laguna) each head's output times one sigmoid scalar of
    the normed input -> o-proj through `_mm_w` (the weight-only int8 /
    int4 layouts) + its bias. Where the layer has ``q_norm`` / ``k_norm``
    leaves (SDAR) each head's q and k are RMS-normalised over the head's
    `d` dims, one gain vector for all heads, BEFORE the rotary turn
    (`qk_norm`). `block`: the launch is block-causal — a row sees the
    keys up to the end of its block of `block` positions (generation by
    diffusion over blocks). `ride` (static, with it): the flat rows [r0,
    r1) hold riding commits — the final rows of blocks whose slots' own
    rows, in this launch, are the NEXT block of the same sequence — and
    `runs` is a pair, (the other rows' work list, theirs): two
    neighbouring blocks lie in one cache tile more often than not, and
    a run of the append owns its tile, so the riding rows are appended
    by a call of their own, before any row attends. `mults` (Falcon-H1): the
    input's, the key's and the output's static multipliers. `window`:
    the layer's sliding window (its `tables` and `runs` are then the
    window kind's). -> (the mixer's output y [1, T, H], the pools).

    `diff` (a layer index; Phi-4-flash) is DIFFERENTIAL attention in the
    PAIR layout: a KV head of the pool is two published heads side by
    side (``kv``, ``d`` are the pool's: half the heads, twice the
    width), each query head is projected d / 2 wide and padded with
    zeros on the side of the K head it does not use
    (`models.phi4flash.pair_queries`), the scale is the published
    head's, and the kernel's head 2i / 2i + 1 are the two softmaxes of
    differential head i over the pair's two V heads; `diff_combine`
    (``a1 - lambda a2``, RMSNorm, ``1 - lambda_init``) follows the
    launch. `borrowed`: the mixer owns NO memory — no K / V projection,
    no append; `pools` are another block's, after that block's append of
    this launch's rows. `shared`: the pool is read by SEVERAL blocks of
    the launch (its owner and its borrowers); such a launch is named
    ``shared_attention``, one over a pool only its block reads
    ``attention``.

    The chain's ``*`` mixer — a llama / MoE / Laguna / gpt layer's,
    alone in its block (Nemotron-H, Phi-4-flash) or beside a state-space
    mixer on the same norm (Falcon-H1) — and its ``X`` mixer.

    `pack` 2 (a published head of 64 lanes: LFM2): the pool's KV head is
    TWO published heads side by side, as the differential pair is, with
    no combine — ``kv``, ``d`` are the pool's, q / k / v are projected
    and normalised at the published width and packed before the append
    (`_pack_head_pairs`), and each head's output is its side of the
    row's."""
    T = h.shape[1]
    kp, vp = pools
    cos, sin = rope or _no_turn(T, d, h.dtype)
    dq = d // 2 if diff is not None or pack > 1 else d
    with _scope("qkv_proj"):
        if mults:
            h = h * mults["attention_in"]
        if "wqkv" in L:
            q, k, v = jnp.split(h @ L["wqkv"] + L["bqkv"], 3, axis=-1)
        else:
            q, k, v = (None if borrowed and w != "wq"
                       else _mm_heads(h, L, w) for w in ("wq", "wk", "wv"))
        if mults:
            k = k * mults["key"]
        if "bq" in L:
            q = q + L["bq"]
            if not borrowed:
                k, v = k + L["bk"], v + L["bv"]
        q = q.reshape(T, heads, dq)
        if diff is not None:
            q = pair_queries(q)
    if "q_norm" in L:
        with _scope("qk_norm"):
            q = _once(fused_rms_norm, "qk_norm", q, L["q_norm"], eps=eps)
            k = _once(fused_rms_norm, "qk_norm",
                      k.reshape(T, kv * pack, d // pack), L["k_norm"],
                      eps=eps)
    if pack > 1:
        with _scope("cache_write"):
            q, k, v = _pack_head_pairs(
                q, *((None, None) if borrowed else (
                    k.reshape(T, kv * pack, dq), v.reshape(T, kv * pack,
                                                           dq))), kv)
            cos, sin = _pair_angles(cos, sin)
    if not borrowed:
        with _scope("cache_write"):
            if ride:
                runs, rider_runs = runs
                at = slice(*ride)
                # (their q is turned with the launch's, below)
                _, kp, vp = _once(
                    fused_rope_append, "cache_write", q[at],
                    k.reshape(T, kv, d)[at], v.reshape(T, kv, d)[at],
                    cos[at], jnp.zeros_like(cos[at]) if sin is None
                    else sin[at], kp, vp, rider_runs)
            q, kp, vp = _once(
                fused_rope_append, "cache_write", q,
                k.reshape(T, kv, d), v.reshape(T, kv, d), cos,
                jnp.zeros_like(cos) if sin is None else sin, kp, vp, runs)
    name = "shared_attention" if borrowed or shared else "attention"
    with jax.named_scope(name):
        o = ragged_paged_attention(
            q, kp, vp, seq_start, num_tokens, kv_lengths, tables,
            scale=dq ** -0.5, window=window, scope=name,
            **({"block": block} if block else {}))
    if diff is not None:
        with jax.named_scope("diff_combine"):
            o = diff_combine(o, L, diff, eps).astype(h.dtype)
    with _scope("attn_out"):
        if pack > 1:
            o = _unpack_head_pairs(o, kv)
        if "wgate" in L:
            g = jax.nn.sigmoid(_mm_w(h, L, "wgate"))[0]
            o = o * g[..., None].astype(o.dtype)
        y = _mm_w(o.reshape(1, T, heads * dq), L, "wo")
        if "bo" in L:
            y = y + L["bo"]
        if mults:
            y = y * mults["attention_out"]
    return y, (kp, vp)


def _no_turn(T: int, d: int, dtype, zeros: bool = False):
    """`_gqa_mixer`'s `rope` for T rows of a model WITHOUT a rotary
    embedding: cos 1, and sin None (zeros made at the call: Nemotron-H's
    and Phi-4-flash's text) or, with `zeros`, made here (gpt's)."""
    return (jnp.ones((T, d // 2), dtype),
            jnp.zeros((T, d // 2), dtype) if zeros else None)


def _pattern_blocks(pattern: str) -> Tuple[str, ...]:
    """A hybrid's pattern as its blocks. A letter is a block of ONE
    sublayer on its own norm — a mixer (``M`` ``K`` ``S`` ``C`` ``*``
    ``L``) or an FFN (``E`` ``D``); ``[..]`` is a block whose one norm feeds
    SEVERAL mixers, their outputs summed into the residual (``[M*]D``: a
    Falcon-H1 layer). ``G<j>`` and ``X<j>`` are mixers that own NO
    memory and read block j's: a gated unit over the scan output of the
    ``S`` block j, an attention mixer over the pages of the ``*`` block
    j (``SD*DG0DX2D``: blocks 4 and 6 read blocks 0 and 2)."""
    blocks, i = [], 0
    while i < len(pattern):
        if pattern[i] == "[":
            j = pattern.index("]", i)
            blocks.append(pattern[i + 1:j])
        else:
            j = i
            while pattern[i] in "GX" and pattern[j + 1:j + 2].isdigit():
                j += 1
            blocks.append(pattern[i:j + 1])
        i = j + 1
    for n, b in enumerate(blocks):
        if b[:1] in ("G", "X") and b[1:].isdigit() and int(b[1:]) < n and \
                blocks[int(b[1:])] == "S*"[b[0] == "X"]:
            continue
        if not b or set(b) - set("MKSC*LED") or \
                len(b) > 1 and set(b) - set("MK*L"):
            raise ValueError(
                f"pattern {pattern!r}: a block is one letter of MKSC*LED, "
                f"several mixers (MK*L) in brackets, or G<j> / X<j> with j "
                f"an earlier S / * block")
    return tuple(blocks)


class _Block(NamedTuple):
    """One block of the chain: ONE norm feeding its mixers, whose outputs
    the residual takes one after the other, or ONE FFN."""

    kind: str                       # its letters (`_pattern_blocks`);
    #                                 ``B``: gpt's biased GELU FFN
    layer: int                      # it reads w["layers"][layer] ...
    norm: Tuple[str, ...]           # ... its norm under these keys: a gain
    #                                 (RMSNorm), or a weight and a bias
    #                                 (LayerNorm) ...
    hc: Optional[str] = None        # ... and its hyper-connection's weights
    #                                 under this one (`_HyperResidual`)
    attn: Optional[dict] = None     # its attention mixer's static keywords
    #                                 (`_gqa_mixer`, `_latent_mixer`)
    pages: int = 0                  # the kind of cache that mixer reads:
    #                                 0 keeps every page, 1 is the window's
    rope: str = ""                  # ... and its rope table's key suffix
    st: Optional[dict] = None       # its FFN's static (`_ffn_apply`)


class _Chain(NamedTuple):
    """What the ONE step builder (`ServingEngine._chain_unified_body`)
    reads of a family: made once, by `_chain_of`, from the decode
    parameters. The last four fields hold where the families' program
    TEXTS differed when their bodies were four (PR 58) and add nothing
    else."""

    blocks: Tuple[_Block, ...]
    eps: float
    head_norm: Tuple[str, ...]      # the last norm's keys in w, as a block's
    hyper: bool                     # the residual: `_HyperResidual` or plain
    mults: Optional[dict]           # static multipliers (Falcon-H1)
    #: the rotary rows the mixers get: one (cos, sin) a key suffix of
    #: the model's tables, or — a model without a rotary embedding —
    #: `_no_turn`'s (d, zeros) for the identity turn
    rope_tables: Tuple[str, ...]
    no_turn: Optional[Tuple[int, bool]]
    #: (pools, kv_lengths) -> (page pools, state pools, kv_lengths, the
    #: state table), and (page pools, state pools) -> the family's pytree
    split: Callable
    join: Callable
    moe_counts: bool                # the routed layers' counts are taken
    #: generation by diffusion over blocks: (block length, the mask
    #: token's id) — a decode slot's rows are a block that sees itself,
    #: and `kv_lengths` is a pair, like a hybrid's: the lengths and, a
    #: slot, the rows the launch's pass unmasks
    block: Optional[Tuple[int, int]]
    ends_scoped: bool               # the rotary rows are made inside
    #                                 `embed`, the counts inside `head`
    head_once: bool                 # the last norm goes through `_once`


def _chain_of(p, attn_static, layer_kind, pool_readers, kv_geom,
              moe_counts: bool) -> _Chain:
    """The chain of the decode parameters `p` (family llama / moe /
    laguna / gpt / mla / hybrid). A llama, gpt or mla LAYER is two
    blocks over one dict of weights, under that dict's own key names; a
    hybrid's pattern names its blocks, a dict each. `attn_static`,
    `layer_kind`, `pool_readers` have an entry a page-holding block, in
    order; `kv_geom` is the pool's (KV heads, width: a pair of heads
    where they are differential, the padded latent row)."""
    cfg, family, mu = p["cfg"], p["family"], p.get("mults")
    hybrid, gpt = family == "hybrid", family == "gpt"
    block = getattr(cfg, "block_length", None)
    eps = cfg.layer_norm_epsilon if hybrid else cfg.layer_norm_eps \
        if gpt else cfg.rms_norm_eps
    if hybrid:
        sts = iter(p["moe_static"])
        ffn = {k: mu[k] for k in ("mlp_gate", "mlp_down")} if mu else None
        blocks = [_Block(k, i, ("norm", "norm_b") if "norm_b" in L
                         else ("norm",), st=next(sts) if k == "E" else ffn)
                  for i, (k, L) in enumerate(zip(
                      _pattern_blocks(p["pattern"]), p["layers"]))]
    else:
        ln1, ln2 = (("ln1w", "ln1b"), ("ln2w", "ln2b")) if gpt \
            else (("ln1",), ("ln2",))
        sts = p.get("moe_static") or (None,) * len(p["layers"])
        blocks = [b for i, st in enumerate(sts) for b in (
            _Block("L" if family == "mla" else "*", i, ln1, "hc1"),
            _Block("B" if gpt else "E" if st else "D", i, ln2, "hc2",
                   st=st))]
    # each attention mixer's static keywords
    gqa = dict(kv=kv_geom[0], d=kv_geom[1], mults=mu, eps=eps)
    if block:
        gqa["block"] = block
    diff = p.get("diff", {})
    if _pairs_heads(p):
        gqa["pack"] = 2     # two published heads a stored row
    owned = iter(zip(layer_kind, attn_static, pool_readers))
    for i, b in enumerate(blocks):
        if b.kind[0] == "X":    # attention over another block's pages
            blocks[i] = b._replace(attn=dict(
                gqa, heads=cfg.num_attention_heads, diff=diff.get(i),
                borrowed=True))
        elif "L" in b.kind:
            next(owned)
            blocks[i] = b._replace(attn=dict(
                nh=cfg.num_attention_heads, dn=cfg.qk_nope_head_dim,
                dr=cfg.qk_rope_head_dim, dv=cfg.v_head_dim,
                r=cfg.kv_lora_rank, width=kv_geom[1], eps=eps,
                scale=cfg.softmax_scale))   # yarn's mscale^2 included
        elif "*" in b.kind:
            k, st, readers = next(owned)
            blocks[i] = b._replace(
                pages=k, rope=st["rope"], attn=dict(
                    gqa, heads=st["heads"], window=st["window"],
                    diff=diff.get(i), shared=readers > 1))
    tables = tuple(sfx for sfx in sorted({st["rope"] for st in attn_static})
                   if "cos" + sfx in p)
    gqa_blocks = any("*" in b.kind for b in blocks)
    return _Chain(
        tuple(blocks), eps,
        head_norm=("normw", "normb") if gpt else ("norm", "norm_b")
        if "norm_b" in p else ("norm",),
        hyper=family == "mla" and cfg.hc_mult > 1, mults=mu,
        rope_tables=tables,
        no_turn=(kv_geom[1], gpt) if gqa_blocks and not tables else None,
        split=(lambda pools, kvl: (pools["kv"], pools["ssm"], *kvl))
        if hybrid else (lambda pools, kvl: (pools, (), *kvl)) if block
        else (lambda pools, kvl: (pools, (), kvl, None)),
        join=(lambda kv, ssm: {"kv": kv, "ssm": ssm}) if hybrid
        else (lambda kv, ssm: kv),
        moe_counts=hybrid or moe_counts,
        block=(block, cfg.mask_token_id) if block else None,
        ends_scoped=not (hybrid or gpt),
        head_once=family not in ("hybrid", "gpt", "mla"))


def _gelu_ffn(L, h2):
    """gpt's FFN: two biased matrices around a tanh GELU."""
    with _scope("ffn"):
        return jax.nn.gelu(h2 @ L["wi"] + L["bi"],
                           approximate=True) @ L["wf"] + L["bf"]


# -- the residual: how a step body enters, feeds and leaves it ---------
class _Residual:
    """The ONE place a step body's residual is written. `enter`: the
    embedding's rows [1, T, C] -> what the layers carry; `feed`: that ->
    (a sublayer's input [1, T, C], before the sublayer's own norm, and
    what `leave` needs of this reading); `leave`: the residual, the
    sublayer's output y [1, T, C] -> the residual after it; `exit`: ->
    [1, T, C] for the last norm. This class is `x = x + f(norm(x))` and
    adds no operation: a body written on it lowers to the text it had
    with the adds written out."""

    def enter(self, x):
        return x

    def feed(self, x, hc=None):
        return x, None

    def leave(self, x, y, keep=None):
        return x + y

    def exit(self, x):
        return x


class _HyperResidual(_Residual):
    """A residual of ``hc_mult`` streams a token, [T, n C] in the
    weights' type, mixed around EVERY sublayer by manifold-constrained
    hyper-connections (`ops.pallas_mhc`: `feed` is ONE pass over the
    stream — the norm, the coefficients, their Sinkhorn projection, the
    sublayer's input — `leave` one more, in place). Entry copies the
    embedding to every stream, exit sums them. Built inside the traced
    step: ``live`` [T] marks the rows a sequence owns, over which the
    largest |column sum - 1| of any residual matrix is kept
    (`mhc_colsum_err_max`: whether the iterations converged)."""

    #: the device counts it hands back beside the logits
    counts = ("mhc_colsum_err_max",)

    def __init__(self, cfg, live):
        self.n = n = cfg.hc_mult
        self.knobs = dict(n=n, eps=cfg.rms_norm_eps, hc_eps=cfg.hc_eps,
                          iters=cfg.hc_sinkhorn_iters,
                          clamp=cfg.mhc_h_res_clamp)
        self.live = live
        self.err = 0.0          # [T, n]: the largest so far, a column

    def enter(self, x):
        with jax.named_scope("mhc_merge"):
            return mhc_enter(x[0], self.n)

    def feed(self, x, hc=None):
        n = self.n
        with jax.named_scope("mhc_pre"):
            a, coef = _once(mhc_pre, "mhc_pre", x, hc["phi_t"], hc["ab"],
                            **self.knobs)
            # column j's sum: lanes n + i n + j over the rows i, slices
            # added elementwise (ONE reduction, at the step's end)
            cols = sum(coef[:, n + i * n:n + (i + 1) * n]
                       for i in range(n))
            self.err = jnp.maximum(self.err, jnp.abs(cols - 1.0))
        return a[None], coef

    def leave(self, x, y, keep=None):
        return _once(mhc_post, "mhc_post", x, y[0], keep, n=self.n)

    def exit(self, x):
        with jax.named_scope("mhc_merge"):
            return mhc_exit(x, self.n)[None]

    def device_counts(self):
        return jnp.max(jnp.where(self.live[:, None], self.err, 0.0))[None]


_PLAIN = _Residual()


class _Launch:
    """One dispatched unified launch whose result the host has not read
    yet: the device arrays it returns, and what the host knew when it
    built it — who owns which row — which is all that retiring it
    (`ServingEngine.retire`) needs."""

    __slots__ = ("logits", "tokens", "moe", "preq", "n", "rows", "drafts",
                 "row_of", "counts")

    def __init__(self, logits, tokens, moe, preq, n, rows, drafts, row_of,
                 counts):
        self.logits = logits    # [S, vocab] on the device ([T, ..] K > 0)
        self.tokens = tokens    # [S] int32 on the device: _greedy(logits)
        self.moe = moe          # counts taken on the device, or None:
        #                         [5] of the routed layers, [passes] the
        #                         looped decoder's exit distribution
        self.preq = preq        # the request whose chunk rides it, or None
        self.n = n              # that chunk's rows
        self.rows = rows        # [(slot, request)] of its decode rows
        self.drafts = drafts    # {slot: drafted tokens} (spec decoding);
        #                         a block family: {slot: [(the pass of a
        #                         block, the block's passes, its given
        #                         tokens)]}, a riding commit of the block
        #                         before, then the slot's own rows'
        #: {id(request): row of `tokens`} of every request whose NEXT
        #: input token this launch produces: its decode rows, and the
        #: chunk's row when the chunk ends its prompt
        self.row_of = row_of
        self.counts = counts    # its part of the step record


#: counts of a launch that add up when one step record retires two
#: launches (a retire forced between two steps, then the step's own);
#: of the others the record keeps the later launch's
_ADDITIVE = frozenset(
    ("decode_rows", "prefill_rows", "rows_computed", "append_runs",
     "rows_dropped",
     "pages_live", "pages_visited", "attn_block_visits",
     "attn_narrow_updates")
    + _tracing.STEP_COUNTS_BY_KIND[:4]
    + _tracing.STEP_COUNTS_EVA[:4] + _tracing.STEP_COUNTS_EVA[-1:]
    + ("ssm_state_bytes_moved", "ssm_scan_rows", "ssm_state_resets")
    + _tracing.STEP_COUNTS_TAIL[:2]
    + _tracing.STEP_COUNTS_MHC[:1] + _tracing.STEP_COUNTS_DIFFUSION[:6])


class ServingEngine:
    """Continuous-batching engine for the llama / MoE / Laguna, gpt, mla,
    hybrid, chunk-summary and looped families (`generation._decode_params`).

    Typical loop::

        eng = ServingEngine(model, max_slots=4, page_size=16)
        eng.add_request(prompt_ids, max_new_tokens=32, eos_token_id=2)
        while eng.has_work():
            eng.step()
        results = eng.collect()   # {request_id: np.int32[max_new]}

    `config` (inference.Config) carries serving policy: `set_admission`
    bounds in-flight requests (Overloaded backpressure), `set_deadline`
    sets the default per-request budget (falsy TimeoutResult partials),
    `set_prefix_cache` toggles the global radix prefix cache.

    Multi-tenant fast path (all greedy-exact — engine output always
    matches solo `generate_cached`):

      - `enable_prefix_cache` (default on): prompt pages are cached in
        a global radix trie after prefill; a request whose prompt
        extends a cached prefix skips prefilling the shared pages;
      - `add_request(priority=, tenant=)` + `tenant_budgets`: priority
        classes with per-tenant in-flight token budgets; `preemption`
        lets a higher-priority arrival re-queue a low-priority decode
        with its pages intact (resume without re-prefill);
      - `spec_decode=k`: n-gram self-drafting speculative decoding —
        up to k drafted tokens per slot verified in the SAME unified
        ragged launch, greedy accept/rollback.
    """

    def __init__(self, model, max_slots: int = 4, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 max_context: Optional[int] = None,
                 prefill_chunk: int = 32,
                 weight_only_int8: bool = False,
                 weight_only_quant=None,
                 config=None,
                 prefix_sharing: bool = True,
                 enable_prefix_cache: Optional[bool] = None,
                 spec_decode: int = 0,
                 preemption: bool = True,
                 tenant_budgets: Optional[dict] = None,
                 role: str = "colocated",
                 replica: Optional[str] = None,
                 prefix_cache_admit: bool = True,
                 slo_targets=None):
        # the set-up ledger (`observability.tracing.recorder().setup()`):
        # one span over the whole constructor, its sections disjoint
        # children; every program traced or compiled below says which
        # section it was under
        with _obs.sections("serving.engine.construct") as section:
            self._construct(
                section, model, max_slots, page_size, num_pages,
                max_context, prefill_chunk, weight_only_int8,
                weight_only_quant, config, prefix_sharing,
                enable_prefix_cache, spec_decode, preemption,
                tenant_budgets, role, replica, prefix_cache_admit,
                slo_targets)

    def _construct(self, section, model, max_slots, page_size, num_pages,
                   max_context, prefill_chunk, weight_only_int8,
                   weight_only_quant, config, prefix_sharing,
                   enable_prefix_cache, spec_decode, preemption,
                   tenant_budgets, role, replica, prefix_cache_admit,
                   slo_targets) -> None:
        """The constructor's body, `section` its borders (`__init__`)."""
        if role not in ("prefill", "decode", "colocated"):
            raise ValueError(
                f"role must be prefill/decode/colocated, got {role!r}")
        # disaggregated serving (ROADMAP item 2): a prefill replica runs
        # chunked prefill into its own pool, then stages the request on
        # `handoff_ready` for export (KVPageHandoff) instead of decoding
        # it; a decode replica refuses add_request — `import_request` is
        # its intake — and resumes imported requests straight into
        # DECODE via the PR-10 preemption/resume path. colocated keeps
        # the single-replica behavior and can play either side.
        self.role = role
        self.replica = replica
        self.handoff_ready: List[Request] = []
        # engine-local handoff totals for scrape(): in-process fleets
        # share ONE default registry, so per-replica truth must come
        # from engine state, not the shared counters
        self._handoff_counts = {"export": 0, "import": 0}
        # the stored leaves, transposed and stacked as the step reads them
        section("weights")
        p = _decode_params(model, weight_only_int8, weight_only_quant)
        cfg = p["cfg"]
        self._family = p["family"]
        self.max_slots = int(max_slots)
        self.page_size = int(page_size)
        self.max_context = int(max_context or cfg.max_position_embeddings)
        if self.max_context > cfg.max_position_embeddings:
            raise ValueError(
                f"max_context {self.max_context} exceeds "
                f"max_position_embeddings {cfg.max_position_embeddings}")
        if "rope_fn" in p:
            # rope tables to the positions THIS engine serves, not to
            # the model's published maximum
            p.update(p.pop("rope_fn")(self.max_context))
        self._p = p
        self._w = _llama_weights(p)
        # what the family keeps of a sequence and may share of it: the
        # allocator, the scheduler, the prefix cache
        section("layout")
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.pages_per_seq = -(-self.max_context // self.page_size)
        # chunk-summary (EVA) attention: every layer keeps the exact
        # rows of a tumbling window beside one pooled row a chunk — two
        # page lists a sequence from ONE pool, read through one table
        self._eva = self._family == "eva"
        if self._eva:
            self.pages_per_seq = ChunkSummaryAllocator.table_pages(
                self.page_size, cfg.window_size, cfg.chunk_size,
                self.max_context)
        if num_pages is None:
            num_pages = self.max_slots * self.pages_per_seq + 1
        self.num_pages = int(num_pages)
        # per-layer geometry: query heads, window and rope table of each
        # layer, the model's own where it has them (Laguna), else cfg's
        # one head count. A model with sliding-window layers keeps TWO
        # kinds of cache — pools, page tables and page lifetimes by
        # layer kind, one allocator.
        self._attn_static = p.get("attn_static") or (dict(
            heads=cfg.num_attention_heads, window=None,
            rope=""),) * len(p["layers"])
        windows = sorted({st["window"] for st in self._attn_static
                          if st["window"] is not None})
        if len(windows) > 1:
            raise NotImplementedError(
                f"one window size a model, got {windows}")
        self._window = windows[0] if windows else None
        if self._window is not None:
            _refuse_shared_cache(
                f"this model has sliding-window layers (window "
                f"{self._window}): their pages are released as the "
                f"window passes them, so ", enable_prefix_cache,
                spec_decode, role)
            enable_prefix_cache = False
            # a live donor's early pages are gone from the window pool
            prefix_sharing = False
            # the window kind's pool follows from the slots and the
            # chunk: what every slot and one more sequence (one being
            # admitted, or preempted with its pages kept) can hold
            cap = -(-(self._window - 1 + self.prefill_chunk)
                    // self.page_size) + 1
            self.num_window_pages = (self.max_slots + 1) * cap + 1
        else:
            self.num_window_pages = 0
        # a looped decoder runs its layer list `total_ut_steps` times a
        # token over ONE set of weights, and each pass attends to its OWN
        # rows: a token holds passes x layers cache rows. Each layer's
        # pool holds `passes` x num_pages pages and pass u reads and
        # writes page p as page p + u * num_pages, so the allocator, the
        # row tables and the kernels see one page id a token
        self._passes = 1
        if self._family == "looped":
            self._passes = int(cfg.total_ut_steps)
            if cfg.early_exit_threshold < 1.0:
                raise NotImplementedError(
                    f"early_exit_threshold {cfg.early_exit_threshold} < 1: "
                    f"rows that leave a launch after an early pass still "
                    f"owe the later passes their cache rows; adaptive exit "
                    f"is not built (ROADMAP R13) and every token takes "
                    f"all {self._passes} passes")
            _refuse_shared_cache(
                f"this model runs its layers {self._passes} times a token "
                f"and a page id names {self._passes} pages of every "
                f"layer's pool, which a page copy does not move, so ",
                enable_prefix_cache, spec_decode, role)
            # a forked page's copy-on-write would copy pass 0 only
            enable_prefix_cache = prefix_sharing = False
        # a hybrid's state blocks keep a FIXED-SIZE memory a sequence, in
        # the slot the scheduler gave it, beside the pages of its
        # attention mixers — in other blocks (Nemotron-H, Ling, LFM2) or
        # in the SAME block, on the same norm (Falcon-H1, where every
        # layer holds a slot and pages): two kinds of cache, one engine.
        # Which memories can be cut at a token: K/V ROWS can (a page
        # holds rows that depend on the tokens up to it and on nothing
        # after), and so can a FINITE HISTORY (`C`: a short convolution
        # remembers the last K - 1 rows of one stream — the rows before
        # a token are all a continuation needs, so a copy of them taken
        # at a page's last row, a SNAPSHOT, lets a later sequence adopt
        # the pages up to there). A RECURRENT state (`M` `K` `S`) cannot:
        # it is one summary of everything before, nothing can adopt a
        # prefix of it without a copy of the whole state taken at that
        # token, roll it back or move it. And a slot that is given away
        # takes either kind of memory with it
        pattern = p["pattern"] if self._family == "hybrid" else ""
        self._blocks = _pattern_blocks(pattern)
        self._ssm_layers = sum(pattern.count(k) for k in "MKSC")
        # ... of ONE kind a model: Mamba-2's (`M`), the delta rule's
        # (`K`, a KDA linear-attention block), Mamba-1's (`S`) or a
        # short convolution's tail and NO state (`C`); and
        # the attention blocks' pages hold GQA rows (`*`) or latent rows
        # (`L`). A block that BORROWS (`G<j>`, `X<j>`) holds neither: it
        # reads block j's scan output / pages inside the launch
        self._state_kind = next((k for k in "KSC" if k in pattern), "M")
        # the state blocks' memory is a finite history and nothing else
        self._tail_only = self._state_kind == "C"
        # ... and a page id names, beside its K/V rows, the tails at its
        # last row in every such block (the prefix cache is on)
        self._tail_snapshots = False
        self._state_layout = HEADS_MINOR
        self._latent = self._family == "mla" or "L" in pattern
        # the blocks that read each page-holding block's pool in a
        # launch: itself and the `X` blocks that name it
        owners = [i for i, b in enumerate(self._blocks)
                  if "*" in b or "L" in b]
        self._pool_readers = [
            1 + sum(b == f"X{i}" for b in self._blocks) for i in owners] \
            if owners else [1] * len(self._attn_static)
        if self._ssm_layers:
            if sum(k in pattern for k in "MKSC") > 1 or \
                    "L" in pattern and "*" in pattern:
                # (so a model ALL of whose state blocks are finite
                # histories is one whose kind is `C`; a mix would keep
                # the whole refusal below, and is not built)
                raise NotImplementedError(
                    f"pattern {pattern!r}: one kind of state block and "
                    f"one kind of attention block a model")
            what = "linear-attention (delta-rule)" \
                if self._state_kind == "K" else "short-convolution" \
                if self._tail_only else "state-space"
            _refuse_shared_cache(
                f"this model has {self._ssm_layers} {what} blocks, "
                + ("whose memory of a sequence is the last rows of a "
                   "stream in its slot: a prefix is adopted at a PAGE "
                   "border from a snapshot, never at a token, so "
                   if self._tail_only else
                   "whose memory of a sequence is one recurrent state in "
                   "its slot, not rows that a snapshot could cut, so "),
                enable_prefix_cache and not self._tail_only, spec_decode,
                role)
            if self._tail_only:
                on = enable_prefix_cache if enable_prefix_cache is not None \
                    else getattr(config, "_prefix_cache", None)
                self._tail_snapshots = on in (None, True)
                if self._tail_snapshots and \
                        self.prefill_chunk % self.page_size:
                    raise ValueError(
                        f"prefill_chunk {self.prefill_chunk} must be whole "
                        f"pages of {self.page_size} (page_size) while the "
                        f"prefix cache is on: a page's snapshot of the "
                        f"convolutions' tails is taken at a STATIC row of "
                        f"the chunk that writes the page's last token, so "
                        f"every chunk starts on a page border")
            else:
                enable_prefix_cache = False
            # a live donor shares at a TOKEN, where no snapshot is; and a
            # preempted sequence's state or tails are not kept once its
            # slot is handed on (ROADMAP R4 b): the engine never preempts
            prefix_sharing = preemption = False
        # generation by diffusion over blocks: a decode slot owns a
        # BLOCK of `block_length` flat rows that see each other, fed for
        # `denoising_steps` denoise passes and one commit pass; the
        # model's config says so, never a switch. The block's K/V is
        # written every pass and final only after the commit pass
        self._block = int(getattr(cfg, "block_length", 0) or 0)
        # ... and the commits of that family ONE launch can carry riding
        # behind the block rows (`_build_unified`): none elsewhere
        self._riders = 0
        if self._block:
            self._diff_steps = int(cfg.denoising_steps)
            self._mask_id = int(cfg.mask_token_id)
            self._riders = _ride_slots(self.max_slots, self._diff_steps)
            _refuse_shared_cache(
                f"this model generates by diffusion over blocks of "
                f"{self._block} rows whose K/V is rewritten every pass "
                f"and final only after a commit pass: a page whose last "
                f"block is being rewritten cannot be shared or handed on, "
                f"and a block is not a draft, so ", enable_prefix_cache,
                spec_decode, role)
            for what, v in (("page_size", self.page_size),
                            ("prefill_chunk", self.prefill_chunk)):
                if v % self._block:
                    raise ValueError(
                        f"{what} {v} must be whole blocks of "
                        f"{self._block} (block_length): a block never "
                        f"crosses a page, and a prompt is prefilled in "
                        f"whole blocks")
            # committed pages could be shared, a request handed on or
            # preempted at a block border: ROADMAP
            enable_prefix_cache = prefix_sharing = preemption = False
        if self._eva:
            _refuse_shared_cache(
                "this model's layers are chunk-summary attention (pooled "
                "rows beside a tumbling window's pages, which are released "
                "together at its close), so ", enable_prefix_cache,
                spec_decode, role)
            if self.prefill_chunk % cfg.chunk_size:
                raise ValueError(
                    f"prefill_chunk {self.prefill_chunk} must be whole "
                    f"chunks of {cfg.chunk_size}: a chunk is pooled in "
                    f"the launch that writes its last token")
            # neither list's rows are shared, and a preempted sequence's
            # snapshot over two lists is not built (ROADMAP R4)
            enable_prefix_cache = prefix_sharing = preemption = False
            self.allocator = ChunkSummaryAllocator(
                self.num_pages, self.page_size, cfg.window_size,
                cfg.chunk_size, self.max_context)
        else:
            self.allocator = PageBlockAllocator(
                self.num_pages, self.page_size, self.pages_per_seq,
                window=self._window,
                window_pages=self.num_window_pages or None,
                window_span=self.prefill_chunk)
        # a residual of several streams a token (hyper-connections,
        # `_HyperResidual`): the configuration's, never a switch
        self._hc = cfg.hc_mult if self._family == "mla" else 1
        if self._hc > 1 and spec_decode:
            raise ValueError(
                f"this model's residual is {self._hc} streams mixed by "
                f"hyper-connections and its drafter would be its "
                f"prediction block, which is not loaded; n-gram drafts "
                f"through the wide stream are not measured (ROADMAP R9); "
                f"spec_decode must be 0")
        for rows in (self.max_slots + self.prefill_chunk,
                     self.max_slots) if self._hc > 1 else ():
            if not _mhc_step_eligible(rows, self._hc, cfg.hidden_size):
                raise ValueError(
                    f"the hyper-connection kernels do not tile a launch of "
                    f"{rows} rows (max_slots + prefill_chunk with a chunk, "
                    f"max_slots without) of {self._hc} "
                    f"streams of width {cfg.hidden_size} on this backend: "
                    f"the rows must be whole blocks of 128 and a stream "
                    f"whole 128-lane registers")
        self.prefix_sharing = bool(prefix_sharing)
        admission = getattr(config, "_admission", None)
        self._default_deadline_s = getattr(config, "_deadline_s", None)
        self.scheduler = Scheduler(
            self.max_slots,
            max_inflight=admission[0] if admission else None,
            queue_timeout_s=admission[1] if admission else 0.0,
            tenant_budgets=tenant_budgets)
        self._prefill_fifo: List[Request] = []
        # global radix prefix cache: engine kwarg wins, then the
        # inference.Config knob (set_prefix_cache), default on
        if enable_prefix_cache is None:
            enable_prefix_cache = getattr(config, "_prefix_cache", None)
        self.prefix_cache = PrefixCache(self.allocator, replica=replica) \
            if enable_prefix_cache in (None, True) else None
        # prefix-cache INSERT admission (the autopilot's thrash lever):
        # False stops new prompts entering the trie — lookups and
        # adopts stay live, so a warm tenant's pinned prefix survives a
        # never-repeating adversary instead of being churned out
        self.prefix_cache_admit = bool(prefix_cache_admit)
        self.preemption = bool(preemption)

        # family geometry + device page pools
        section("pools")
        dt = p["embed"].dtype
        n_layers = len(p["layers"])
        if self._family == "gpt":
            kv, d = cfg.num_attention_heads, cfg.head_dim
        elif self._latent:
            kv, d = 1, _latent_row_width(cfg.kv_lora_rank,
                                         cfg.qk_rope_head_dim)
        else:
            kv, d = cfg.num_key_value_heads, cfg.head_dim
            if p.get("diff"):
                # differential heads: the pool's KV head is a PAIR of
                # published heads side by side (`_gqa_mixer`)
                kv, d = kv // 2, 2 * d
            elif _pairs_heads(p):
                # a published head of half a 128-lane register: two of
                # them a stored row, so that a row is whole registers
                kv, d = kv // 2, 2 * d
        shape = (kv, self._passes * self.num_pages, self.page_size, d)
        wshape = (kv, self.num_window_pages, self.page_size, d)
        # each layer's kind: 0 keeps every page, 1 is the window kind
        self._layer_kind = [int(st["window"] is not None)
                            for st in self._attn_static]
        # the blocks that fetch the pages of each kind in a launch
        self._kind_readers = [
            sum(r for r, k in zip(self._pool_readers, self._layer_kind)
                if k == kind) for kind in (0, 1)]
        heads = sorted({st["heads"] for st in self._attn_static})
        if not _ragged_step_eligible(heads, kv, d, self.page_size):
            raise ValueError(
                f"family {self._family!r}: the ragged attention kernel "
                f"does not tile query heads {heads} over {kv} KV heads of "
                f"width {d} with pages of {self.page_size} on this "
                f"backend, and the engine serves through the unified "
                f"ragged step only")
        # what the ragged kernel's tiling follows (pages_visited): the
        # query rows a KV head of each layer kind's layers
        self._q_rep, self._q_dtype = heads[0] // kv, dt
        self._kind_rep = {
            k: sorted({st["heads"] // kv for st, kk in zip(
                self._attn_static, self._layer_kind) if kk == k})
            for k in set(self._layer_kind)}
        if self._family == "mla":
            # one pool per layer: each row is [latent | rope-key | pad],
            # K whole and V in its first kv_lora_rank columns
            self._pools = [jnp.zeros(shape, dt) for _ in range(n_layers)]
        elif self._ssm_layers:
            # the attention mixers' pages, and for each state-space
            # mixer the slot-indexed state pool (float32, heads-minor
            # or — fewer heads than lanes over a state of whole
            # registers — state-minor: `ops.pallas_ssm.state_layout`)
            # and the convolution's tail, one slot more than the
            # scheduler's: the spare takes what idle rows and an absent
            # chunk write
            # (a KDA block's state is heads-major, a [K, V] tile a head
            # with V along the lanes: `ops.pallas_kda` says why; its
            # tail holds the q, k and v convolutions' rows side by side)
            # (a `C` block has NO state: its pool entry is its tail and,
            # with the prefix cache on, the snapshot plane — the tails at
            # every page's last row, by page id)
            if self._tail_only:
                self._state_shape = None
            elif self._state_kind == "K":
                nh, hd = cfg.num_attention_heads, cfg.head_dim
                if not _kda_step_eligible(nh, hd, self.prefill_chunk,
                                          cfg.kda_sub_chunk):
                    raise ValueError(
                        f"the KDA kernels do not tile {nh} heads of "
                        f"{hd} x {hd} state on this backend, or a "
                        f"prefill chunk of {self.prefill_chunk} rows is "
                        f"not whole sub-chunks of {cfg.kda_sub_chunk}")
                self._state_shape = (self.max_slots + 1, nh, hd, hd)
            elif self._state_kind == "S":
                # Mamba-1: a decay a (channel, column); the channels
                # along the lanes, the 16 columns on the sublanes,
                # nothing padded (`ops.pallas_ssm`)
                self._state_shape = (self.max_slots + 1, 1,
                                     cfg.ssm_state_size, cfg.d_inner)
            else:
                self._state_layout = state_layout(cfg.mamba_num_heads,
                                                  cfg.ssm_state_size)
                self._state_shape = state_pool_shape(
                    self.max_slots + 1, cfg.mamba_num_heads,
                    cfg.mamba_head_dim, cfg.ssm_state_size,
                    self._state_layout)
            self._tail_shape = (self.max_slots + 1, cfg.conv_kernel - 1,
                                cfg.conv_dim)
            self._pools = {
                # (latent rows: one plane, K whole and V in its first
                # kv_lora_rank columns, as the mla family's)
                "kv": [jnp.zeros(shape, dt) if self._latent
                       else (jnp.zeros(sh, dt), jnp.zeros(sh, dt))
                       for sh in (wshape if k else shape
                                  for k in self._layer_kind)],
                "ssm": [(jnp.zeros(self._tail_shape, dt),)
                        + ((jnp.zeros((self.num_pages,)
                                      + self._tail_shape[1:], dt),)
                           if self._tail_snapshots else ())
                        if self._tail_only else
                        (jnp.zeros(self._state_shape, jnp.float32),
                         jnp.zeros(self._tail_shape, dt))
                        for _ in range(self._ssm_layers)]}
        else:
            self._pools = [(jnp.zeros(sh, dt), jnp.zeros(sh, dt))
                           for sh in (wshape if k else shape
                                      for k in self._layer_kind)]

        # the step's counts and the HBM accounting
        section("accounting")
        if spec_decode < 0:
            raise ValueError("spec_decode must be >= 0")
        if self._eva and jax.default_backend() == "tpu" and \
                cfg.chunk_size % (32 // jnp.dtype(dt).itemsize):
            raise ValueError(
                f"chunk_size {cfg.chunk_size} is not whole sublane tiles "
                f"of {jnp.dtype(dt).name}: fused_chunk_pool reads a chunk "
                f"as one block")
        # speculative decoding: each decode slot owns 1 + spec_k flat
        # rows of the unified step (n-gram drafts verified in the SAME
        # ragged launch)
        self.spec_k = int(spec_decode)
        self.launches = 0      # device program launches by THIS engine
        # the flat rows those launches computed, and of them the rows a
        # sequence owned (`scrape`; the step record's `rows_computed`)
        self.rows_computed = self.rows_owned = 0
        self.steps = 0         # step() calls: the step timeline's `seq`
        #: set to a callable (request, logits row [vocab]) to be handed
        #: the host copy of the row each emitted token was sampled from
        #: (a check against a reference in logits); None costs nothing
        self.on_logits = None
        #: a block family's twin: a callable (request, pass index, the
        #: block's passes, the block's tokens going in [B], its logits
        #: rows [B, vocab] float32, the block after the pass [B]) called
        #: as each pass of a block RETIRES — a commit that rode in the
        #: next block's first launch as the block's last pass, with no
        #: logits (None), before that launch's own pass 0; `on_logits`
        #: is not called for such a family (a token is not sampled from
        #: one row)
        self.on_block = None
        # the open step's counts, taken where the work happens and
        # closed into the recorder's step record (tracing.STEP_COUNTS)
        self._count_names = _tracing.STEP_COUNTS
        if self._window is not None:
            self._count_names += _tracing.STEP_COUNTS_BY_KIND
        if any(st and "held" in st for st in p.get("moe_static") or ()):
            self._count_names += _tracing.STEP_COUNTS_MOE
        if self._latent:
            self._count_names += _tracing.STEP_COUNTS_LATENT
        if self._eva:
            self._count_names += _tracing.STEP_COUNTS_EVA
        if self._family == "looped":
            self._count_names += _tracing.STEP_COUNTS_LOOP
        if self._ssm_layers:
            self._count_names += _tracing.STEP_COUNTS_SSM
        if self.prefix_cache is not None:
            self._count_names += _tracing.STEP_COUNTS_PREFIX
        if self._tail_only:
            self._count_names += _tracing.STEP_COUNTS_TAIL
        if max(self._pool_readers) > 1:
            self._count_names += _tracing.STEP_COUNTS_SHARED
        if self._hc > 1:
            self._count_names += _tracing.STEP_COUNTS_MHC
        if self._block:
            self._count_names += _tracing.STEP_COUNTS_DIFFUSION
        #: the counts a launch takes on the device and returns beside
        #: its logits, in the order of the one array they come in
        self._device_count_names = (
            _tracing.STEP_COUNTS_MOE
            if _tracing.STEP_COUNTS_MOE[0] in self._count_names else ()) \
            + (_HyperResidual.counts if self._hc > 1 else ())
        self._counts = dict.fromkeys(self._count_names, 0)
        # the pool handles this step's launches were handed (dead
        # arrays, no buffers): `pools_in_place` asks them at account
        self._launched: List[object] = []
        # the launch queue, depth one: the unified launch that is
        # dispatched and not yet retired, and the counts of what was
        # retired since `step()` last returned
        self._inflight: Optional[_Launch] = None
        self._retired = {"prefill_tokens": 0, "decoded": 0, "finished": 0}
        self._retired_counts: Dict[str, float] = {}

        # live HBM accounting (ISSUE 11): static residency is published
        # once; a cumulative analytical ledger turns each launch into
        # measured bytes, divided by tokens processed for the
        # bytes-per-token gauge the observatory checks against the
        # costmodel budget
        self._kv_geom = (kv, d)
        self._kv_itemsize = int(jnp.dtype(dt).itemsize)
        self._tiling: Dict[int, dict] = {}      # `_attn_tiling`, by rows
        # the unit of work of the rope + append kernel
        # (`ops.fused.append_run_table`): the rows of one cache tile
        self._append_tile = append_tile(dt, self.page_size)
        planes = 1 if self._latent else 2
        # what the cost model's cache formulas call this engine's rows
        self._cache_family = "mla" if self._latent else self._family
        self._hbm_weights_bytes = _costmodel.tree_bytes(self._w)
        self._hbm_pool_bytes = self._passes * sum(
            planes * kv * (self.num_window_pages if k else self.num_pages)
            * self.page_size * d * self._kv_itemsize
            for k in self._layer_kind)
        # a state-space block's pool: every slot's state and tail
        self._ssm_state_bytes = self._ssm_slot_bytes = 0
        if self._ssm_layers:
            # the tail a slot holds in ONE state block
            self._tail_bytes = int(np.prod(self._tail_shape[1:])) \
                * self._kv_itemsize
            # (tail only: no state held, none moved)
            self._ssm_state_bytes = 0 if self._tail_only \
                else 4 * int(np.prod(self._state_shape[1:]))
            if self._tail_only:
                self._ssm_slot_bytes = self._tail_bytes
            elif self._state_kind == "K":
                self._ssm_slot_bytes = \
                    _costmodel.kda_state_bytes_per_seq_layer(
                        heads=cfg.num_attention_heads,
                        head_dim=cfg.head_dim, conv_kernel=cfg.conv_kernel,
                        conv_dtype_bytes=self._kv_itemsize)
            elif self._state_kind == "S":
                self._ssm_slot_bytes = \
                    _costmodel.ssm_state_bytes_per_seq_layer(
                        heads=1, head_dim=cfg.d_inner,
                        state_size=cfg.ssm_state_size,
                        conv_dim=cfg.conv_dim, conv_kernel=cfg.conv_kernel,
                        conv_dtype_bytes=self._kv_itemsize)
            else:
                self._ssm_slot_bytes = \
                    _costmodel.ssm_state_bytes_per_seq_layer(
                        heads=cfg.mamba_num_heads,
                        head_dim=cfg.mamba_head_dim,
                        state_size=cfg.ssm_state_size,
                        conv_dim=cfg.conv_dim, conv_kernel=cfg.conv_kernel,
                        conv_dtype_bytes=self._kv_itemsize)
            self._hbm_pool_bytes += self._ssm_layers * (
                self.max_slots + 1) * self._ssm_slot_bytes
            if self._tail_snapshots:
                self._hbm_pool_bytes += self._ssm_layers * self.num_pages \
                    * self._tail_bytes
        # a wide residual: the bytes of one row of the stream as stored
        self._stream_row_bytes = (self._hc * cfg.hidden_size
                                  * self._kv_itemsize) if self._hc > 1 else 0
        # what ONE launch reads of the weights: the layers once a pass
        self._hbm_weight_read_bytes = self._hbm_weights_bytes + (
            self._passes - 1) * _costmodel.tree_bytes(self._w["layers"])
        self._ledger_bytes = 0.0
        self._ledger_model_bytes = 0.0
        self._ledger_tokens = 0
        self._ledger_launches = 0   # self.launches at the last account
        if _obs.enabled():
            _G_HBM_WEIGHTS.set(self._hbm_weights_bytes)
            _G_HBM_POOL.set(self._hbm_pool_bytes)
            _G_HBM_DRAFT.set(0)

        # the step programs, the feeds' compile-and-run, the copy
        # program's warm run
        section("programs")
        # what the one step builder reads of this family (eva and looped
        # have bodies of their own)
        self._chain = None if self._family in ("eva", "looped") \
            else _chain_of(p, self._attn_static, self._layer_kind,
                           self._pool_readers, self._kv_geom,
                           _tracing.STEP_COUNTS_MOE[0] in self._count_names)
        # the fixed-shape programs: built ONCE here, never in the step
        # loop (paddlelint PT002)
        self._build_programs()
        self.rebuilds = 0   # reconfigure()-triggered program rebuilds

        # engine-local speculative-decode totals: the process-wide
        # serving.spec_decode.* counters are shared by every in-process
        # replica, so the controller's per-engine acceptance signal
        # must come from here
        self.spec_drafted = 0
        self.spec_accepted = 0
        # SLO autopilot (ISSUE 18): declaring targets attaches a
        # feedback controller stepped from the tail of step()
        if slo_targets is not None:
            from .controller import EngineController
            self.controller = EngineController(self, slo_targets)
        else:
            self.controller = None

    # Read by benchmarks/systems/*_serving.py (the `paths` line of every
    # run), benchmarks/tests/, benchmarks/tools/sweep_engine.py and
    # chip_smoke.py, and by nothing in the package: three constants —
    # the engine has one step program and no fused halves — and the one
    # chain's launches a layer after and before attention. They go with
    # the `benchmark` PR of ROADMAP R0 (c).
    ragged = True
    megafront = megadecode = False
    back_half_launches = 6      # o-proj, add, norm; gate/up, act, down

    @property
    def front_half_launches(self) -> int:
        if self._family == "gpt":
            return 3            # norm + wqkv dot + rope-append
        if self._family == "mla":
            qlora = any("wqa" in L or "wqa_q" in L or "wqa_q4" in L
                        for L in self._p["layers"])
            # norm + q dot(s, + q-lora norm) + kv_a dot + latent norm
            # + row append
            return 7 if qlora else 5
        return 5                # norm + q/k/v dots + rope-append

    def _chunk_parts(self) -> Dict[str, int]:
        """{suffix of the program names: the chunk part's length} the
        step body is compiled at: the prefill chunk's rows behind the
        decode rows (`unified`, `feed`), and none (`unified_nochunk`,
        `feed_nochunk`)."""
        return {"": self.prefill_chunk, "_nochunk": 0}

    @property
    def _slot_rows(self) -> int:
        """Flat rows a decode slot owns in a launch: its token and its
        drafts, or — generation by diffusion over blocks — its block."""
        return self._block or 1 + self.spec_k

    def _launch_rows(self, chunk: int) -> int:
        """Flat rows of a launch whose chunk part is `chunk` rows: the
        decode slots', the riding commits' region (a block family's:
        `_riders` blocks), the chunk part."""
        return (self.max_slots + self._riders) * self._slot_rows + chunk

    def _prompt_rows(self, req: Request) -> int:
        """Prompt tokens of `req` that are PREFILLED: all of them, or —
        a block family — its whole blocks (the remainder opens the
        first block as given tokens)."""
        n = int(req.prompt.size)
        return n - n % self._block if self._block else n

    def _attn_tiling(self, T: int) -> Dict[str, Dict[int, int]]:
        """The KV heads and the query tiles one page visit of the ragged
        kernel serves, and the rows of a tile it computes for a sequence
        that owns a few, for each query group size: the kernel's own
        choice at a launch of `T` flat rows (`attn_block_visits`,
        `pages_visited`, `attn_narrow_updates`), asked once a row
        count."""
        if T in self._tiling:
            return self._tiling[T]
        kv, d = self._kv_geom
        dt, latent = self._q_dtype, self._latent
        out = {"head_block": {}, "tile_block": {}, "narrow_rows": {}}
        for r in {r for reps in self._kind_rep.values() for r in reps}:
            tq = ragged_tile_tokens(T, r, dt)
            out["head_block"][r] = hb = ragged_head_block(
                kv, tq * r, d, self.page_size, self._kv_itemsize,
                latent=latent)
            out["tile_block"][r] = tb = ragged_tile_block(
                hb, -(-T // tq), tq * r, d, self.page_size,
                self._kv_itemsize,
                self._p["cfg"].kv_lora_rank if latent else None)
            out["narrow_rows"][r] = ragged_narrow_rows(r, tq * r, dt, tb)
        self._tiling[T] = out
        return out

    def _build_programs(self) -> None:
        """(Re)build the fixed-shape jitted programs for the CURRENT
        max_slots/prefill_chunk/spec_k. Called once from __init__ and
        again from `reconfigure()` — fresh `jax.jit` objects each time,
        so `program_cache_sizes()` stays at 1 per program (PT002).

        The step body is built at each length of its chunk part
        (`_chunk_parts`): `unified` carries the prefill chunk's rows
        behind the decode rows, `unified_nochunk` is the same body over
        the decode rows alone, for a launch that dispatches no prompt.
        Each has the token feed of its own row count beside it.

        Every step program takes the page pools as argument 2, returns
        the pools that replace them, and OWNS the ones it is handed
        (``donate_argnums``): XLA pairs each pool parameter with the
        output of its shape, in order, and the kernels' in-place row
        writes (`ops.fused.fused_rope_append`) land in the live buffer
        instead of a copy of it. So the caller's pools are dead after
        the launch — `_launch` is the one caller."""
        self._programs = self._step_programs()
        # the token feed: EVERY `tok` a step program sees comes out of
        # the feed of its row count (one type, one sharding, one
        # committed-ness: the step keeps one cache entry) — row r takes
        # the host's token where src[r] < 0, else row src[r] of the
        # launch in flight's greedy tokens, which never leave the
        # device. Compiled and run ONCE here, on the tokens of no launch
        self._no_tokens = jax.jit(lambda: jnp.zeros(
            self._launch_rows(self.prefill_chunk) if self.spec_k
            else self.max_slots * self._block if self._block
            else self.max_slots + 1, jnp.int32))()
        for sfx, chunk in self._chunk_parts().items():
            T = self._launch_rows(chunk)
            self._programs["feed" + sfx](
                self._no_tokens, np.zeros(T, np.int32),
                np.full(T, -1, np.int32))
        # the copy-on-write program (`_apply_copies`): as many pairs as
        # one sequence's new rows of one step can touch shared pages,
        # compiled and run ONCE here (trash page onto itself) so that
        # no copy ever compiles inside a serving loop — where a page can
        # come to be shared at all (a live donor's fork, a cached
        # prefix's pin): a cache that refuses both (a window kind, two
        # page lists, passes, a state) never copies, and does not pay
        # for the run
        rows = max(self.prefill_chunk, 1 + self.spec_k)
        self._copy_slots = -(-rows // self.page_size) + 1
        self._jit_copy = jax.jit(
            lambda pools, src, dst: jax.tree_util.tree_map(
                lambda p: p.at[:, dst].set(p[:, src]), pools),
            donate_argnums=0)
        # (nor where pages are adopted WHOLE and never forked — the
        # finite-history family: an adopter's first write is a fresh page)
        if (self.prefix_sharing or self.prefix_cache is not None) \
                and not self._tail_snapshots:
            self._copy_pages(*np.zeros((2, self._copy_slots), np.int32))
        # a block's logits rows for `on_block`: [B, vocab] of [slots x B,
        # vocab], the slot a traced argument (one compile, at first use)
        self._jit_block_rows = jax.jit(
            lambda logits, r0: jax.lax.dynamic_slice_in_dim(
                logits, r0, self._block, 0)) if self._block else None

    @property
    def _jit_unified(self):
        """The step program at the full row count, for whoever lowers
        it (`chip_smoke.py`, `benchmarks/tests`); the engine launches
        from `_programs`."""
        return self._programs["unified"]

    def _copy_pages(self, src, dst) -> None:
        """Pages `src` copied onto pages `dst` in every page pool (a
        state-space block has no pages: only the attention blocks')."""
        pools = self._live_pools()
        if isinstance(pools, dict):
            self._pools = dict(pools, kv=self._jit_copy(pools["kv"], src,
                                                        dst))
        else:
            self._pools = self._jit_copy(pools, src, dst)

    def _step_programs(self) -> Dict[str, object]:
        """{name: a FRESH `jax.jit`} of the step programs at the current
        max_slots/prefill_chunk/spec_k: the one body and its token feed
        at each length of the chunk part."""
        parts = self._chunk_parts()

        def step(sfx):
            return jax.jit(self._make_unified_body(parts[sfx]),
                           donate_argnums=2)

        def feed():
            return jax.jit(lambda prev, tok, src: jnp.where(
                src < 0, tok, prev[jnp.maximum(src, 0)]))

        return {"unified": step(""), "feed": feed(),
                "unified_nochunk": step("_nochunk"), "feed_nochunk": feed()}

    def _live_pools(self):
        """The page pools, for whoever reads or replaces them between
        launches (handoff, copy-on-write). A launch that failed AFTER
        it took the pools leaves nothing to serve from: the engine
        says so here rather than hand out deleted buffers."""
        if self._pools is None:
            raise RuntimeError(
                "the KV page pools were lost to a launch that raised "
                "after it had taken them (they are donated to the "
                "jitted step); this engine cannot run again — build a "
                "new ServingEngine and resubmit its requests")
        return self._pools

    def _launch(self, program, tok, *tables):
        """Call ``program(w, tok, pools, *tables)`` and rebind the pools
        to the ones it returns, in one statement: the pools handed in
        are dead once the call is dispatched. Returns what the program
        returned without the pools: (logits, *more)."""
        pools = self._live_pools()
        try:
            logits, self._pools, *more = program(self._w, tok, pools,
                                                 *tables)
        except BaseException:
            # before the dispatch (tracing, compiling) the pools are
            # untouched and the engine goes on; after it they are gone
            if any(a.is_deleted() for a in jax.tree_util.tree_leaves(pools)):
                self._pools = None
            raise
        self._launched.append(pools)   # handles only: `pools_in_place`
        self.launches += 1
        return (logits, *more)

    def reconfigure(self, prefill_chunk: Optional[int] = None,
                    spec_decode: Optional[int] = None) -> bool:
        """Retune the shape-baked serving knobs on a LIVE engine — the
        autopilot's chunk/spec-k actuator. Greedy-exactness is
        preserved: chunk size only changes how many prompt tokens ride
        each launch, and spec decoding is accept/rollback-exact at any
        k, so in-flight requests continue bit-identically. Returns True
        when the jitted programs were rebuilt (a recompile on next
        step), False for a no-op."""
        new_chunk = self.prefill_chunk if prefill_chunk is None \
            else int(prefill_chunk)
        if new_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        new_k = self.spec_k if spec_decode is None else int(spec_decode)
        if new_k < 0:
            raise ValueError("spec_decode must be >= 0")
        if (new_chunk, new_k) == (self.prefill_chunk, self.spec_k):
            return False
        # the launch in flight was built by the programs that go
        self.retire()
        if self._window is not None and (
                new_k or new_chunk > self.allocator.window_span):
            raise ValueError(
                "a model with sliding-window layers reserves its window "
                "pages for the prefill chunk it was built with: the chunk "
                "can only shrink, and spec_decode stays 0")
        if self._ssm_layers and new_k:
            raise ValueError(
                "a model with state-space blocks cannot roll a rejected "
                "draft's state back: spec_decode stays 0")
        if self._tail_snapshots and new_chunk % self.page_size:
            raise ValueError(
                f"prefill_chunk {new_chunk} must be whole pages of "
                f"{self.page_size} while the prefix cache keeps a "
                f"snapshot of the convolutions' tails a page")
        if self._block and (new_k or new_chunk % self._block):
            raise ValueError(
                f"a model that generates by diffusion over blocks takes "
                f"prefill chunks of whole blocks of {self._block}, and "
                f"spec_decode stays 0")
        if self._eva and (new_k or new_chunk
                          % self._p["cfg"].chunk_size):
            raise ValueError(
                "a model with chunk-summary layers takes prefill chunks of "
                "whole pooling chunks, and spec_decode stays 0")
        self.prefill_chunk = new_chunk
        self.spec_k = new_k
        self._build_programs()
        self.rebuilds += 1
        if _obs.enabled():
            _REBUILDS.labels(replica=self.replica or "solo").inc()
        return True

    # ------------------------------------------------------------- public
    def add_request(self, prompt, max_new_tokens: int = 20,
                    eos_token_id: Optional[int] = None,
                    pad_token_id: int = 0,
                    deadline_s: Optional[float] = None,
                    request_id=None,
                    priority: int = 0,
                    tenant: Optional[str] = None) -> Request:
        """Enqueue a request (FCFS within its priority class). Raises
        resilience.Overloaded when admission backpressure refuses it at
        the door."""
        if self.role == "decode":
            raise ValueError(
                "decode-role replica does not prefill: route fresh "
                "requests to a prefill/colocated replica "
                "(import_request is this engine's intake)")
        _TRACE.set_replica_context(self.replica)
        req = Request(prompt, max_new_tokens, eos_token_id=eos_token_id,
                      pad_token_id=pad_token_id,
                      deadline_s=(deadline_s if deadline_s is not None
                                  else self._default_deadline_s),
                      request_id=request_id,
                      priority=priority, tenant=tenant,
                      block=self._block or 1)
        if req.total_tokens > self.max_context:
            raise ValueError(
                f"prompt+max_new_tokens = {req.total_tokens} exceeds "
                f"max_context {self.max_context}"
                + (f" (whole blocks of {self._block})" if self._block
                   else ""))
        try:
            self.scheduler.submit(req)
        except _res.Shed:
            if _obs.enabled():
                _REQS.labels(outcome="shed").inc()
            raise
        except _res.Overloaded:
            if _obs.enabled():
                _REQS.labels(outcome="overloaded").inc()
            raise
        if _obs.enabled():
            _REQS.labels(outcome="submitted").inc()
        return req

    def has_work(self) -> bool:
        # a launch in flight is work: its requests are unfinished until
        # it retires (so the scheduler says so too), and a row computed
        # for a request that has ended since is still to be dropped
        return self._inflight is not None or self.scheduler.has_work()

    def retire(self) -> None:
        """Read back the launch in flight, if there is one, and emit its
        tokens: afterwards every `Request` shows all the work that was
        dispatched for it. Whatever reads or edits a sequence between
        two launches calls this first (preemption, the deadline sweep,
        export / import, `reconfigure`, a router's drain); the counts
        go into the next `step()` return."""
        fl = self._inflight
        if fl is not None:
            self._sample_unified(fl, *self._await_launch(fl))

    def step(self) -> Dict[str, int]:
        """One engine iteration: cull expired requests, admit waiting
        ones into free slots, then run the step's device work — ONE
        unified ragged launch carrying every decode slot's token plus
        one prefill chunk, dispatched before the previous launch is
        read back. Returns counts for observability/benching;
        `prefill_tokens`, `decoded` and `finished` are those of work
        RETIRED in this call (results the host holds), not of the launch
        it queued."""
        out = {"admitted": 0, "prefill_tokens": 0, "decoded": 0,
               "finished": 0}
        self.steps += 1
        self._counts = dict.fromkeys(self._count_names, 0)
        self._launched = []
        _TRACE.set_replica_context(self.replica)
        _TRACE.open_step(self.steps, "serving.engine.step")
        try:
            with _obs.span("serving.engine.step", step=self.steps):
                self._step_phases(out)
        finally:
            self._take_retired(out)     # a retire in the account phase
            self._counts.update(self._retired_counts)
            self._retired_counts = {}
            self._counts["admitted"] = out["admitted"]
            self._counts["finished"] = out["finished"]
            _TRACE.close_step(self._counts)
        return out

    def _take_retired(self, out: Dict[str, int]) -> None:
        """Move the counts of what was retired since they were last
        taken into a `step()` return."""
        for k, v in self._retired.items():
            out[k] += v
            self._retired[k] = 0

    def _step_phases(self, out: Dict[str, int]) -> None:
        """The step timeline: disjoint child spans of
        ``serving.engine.step`` — admit, then build / launch / sync /
        sample inside `_unified_step`, then account."""
        with _obs.span("serving.engine.admit"):
            for req in self.scheduler.expire_waiting():
                # a PREEMPTED request expiring in the queue still owns
                # its allocator sequence (pages kept for the resume
                # that never came) — free it here or the pool leaks
                if self.allocator.has_seq(req.request_id):
                    self.allocator.free(req.request_id)
                if _obs.enabled():
                    _REQS.labels(outcome="overloaded"
                                 if isinstance(req.result, _res.Overloaded)
                                 else "timeout").inc()
                out["finished"] += 1
            # deadline sweep over in-flight requests: partial result,
            # pages freed immediately
            late = [req for _, req in self.scheduler.active()
                    if req.deadline_expired()]
            if late:
                self.retire()       # their partial result is what ran
            for req in late:
                if req.slot is not None:    # not ended or staged by it
                    self._finish(req)
                    out["finished"] += 1
            out["admitted"] = self._admit()
            self._counts["live"] = self.scheduler.inflight
            self._counts["waiting"] = len(self.scheduler.waiting)
        self._unified_step()
        self._take_retired(out)
        with _obs.span("serving.engine.account"):
            # what the observability itself costs, measured by itself
            if _obs.enabled():
                _ACTIVE.set(self.scheduler.inflight)
                _WAITING.set(len(self.scheduler.waiting))
                self._account_step(out)
            self.allocator.publish_gauges()
            if _obs.enabled():
                # counter tracks move in lockstep with the step spans
                _TRACE.sample_gauges(_COUNTER_GAUGES)
            if self.controller is not None:
                self.controller.on_step(out)
            if self._eva:
                # a window that closed in this call's launch: its pages
                # go back to the pool now, all together (the launch in
                # flight reads them before any later launch writes them)
                freed = sum(self.allocator.release_window(req.request_id)
                            for _, req in self.scheduler.active()
                            if self.allocator.has_seq(req.request_id))
            full = (self.allocator.num_pages - 1,
                    self.allocator.num_pages - 1 - self.allocator.free_pages)
            win = (0, 0)
            if self._window is not None:
                # the window kind's lifetime: pages no future query sees
                # go back to their pool before the next step admits
                freed = sum(self.allocator.release_window(req.request_id)
                            for _, req in self.scheduler.active()
                            if self.allocator.has_seq(req.request_id))
                win = (self.allocator.window_pages - 1,
                       self.allocator.window_pages - 1
                       - self.allocator.free_window_pages)
                self._counts.update({
                    "window_pages_freed": freed,
                    "pool_pages_total.full": full[0],
                    "pool_pages_used.full": full[1],
                    "pool_pages_total.window": win[0],
                    "pool_pages_used.window": win[1]})
            if self._eva:
                summ, exact = self.allocator.pages_by_list()
                self._counts.update({
                    "window_pages_freed": freed,
                    "pool_pages_total.summary": full[0],
                    "pool_pages_total.exact": full[0],
                    "pool_pages_used.summary": summ,
                    "pool_pages_used.exact": exact})
            if self._ssm_layers:
                self._counts["state_pool_slots_used"] = \
                    self.scheduler.inflight
                self._counts["state_pool_slots_total"] = self.max_slots
            self._counts["pool_pages_total"] = full[0] + win[0]
            self._counts["pool_pages_used"] = full[1] + win[1]
            # did every launch of the step write its pools in place?
            # (a backend that declines the donation leaves them alive)
            handed = jax.tree_util.tree_leaves(self._launched)
            self._counts["pools_in_place"] = int(
                bool(handed) and all(a.is_deleted() for a in handed))

    # ------------------------------------------------- HBM accounting
    def _account_step(self, out: Dict[str, int]) -> None:
        """Fold this step's launches into the measured bytes-per-token
        ledger and refresh the costmodel budget gauge (ISSUE 11).

        Measured = analytical bytes at the step's ACTUAL geometry: the
        weight tree once per device launch plus page-granular cache
        reads at each live slot's current length (what the paged/ragged
        kernels really transfer), cumulative over the engine's life.
        Model = `decode_step_budget` at the same batch and the MEAN
        context.  The two agree up to page rounding and prefill chunks
        riding the unified launch — the slack the observatory's 25%
        gate allows."""
        kv, d = self._kv_geom
        n_layers = len(self._layer_kind)    # the layers that keep pages
        per_tok = _costmodel.kv_bytes_per_token_layer(
            self._cache_family, kv_heads=kv, head_dim=d,
            kv_latent_dim=(d if self._latent else 0),
            kv_dtype_bytes=self._kv_itemsize, passes=self._passes)
        # (chunk-summary layers read a sequence's visible pooled rows and
        # its window's, not its length)
        length = (lambda rid: self.allocator.attention_view(rid)[2]) \
            if self._eva else self.allocator.seq_length
        lens = [length(req.request_id)
                for _, req in self.scheduler.active()
                if self.allocator.has_seq(req.request_id)]
        spec_rows = 1 + self.spec_k
        dl = self.launches - self._ledger_launches
        self._ledger_launches = self.launches
        self._ledger_tokens += (int(out["decoded"])
                                + int(out["prefill_tokens"]))
        if dl:
            pages = sum(-(-ln // self.page_size) for ln in lens)
            # (a pool is fetched once by every block that reads it)
            readers = self._kind_readers
            layer_pages = pages * readers[0]
            if self._window is not None:
                # a window layer reads the pages its window spans
                wcap = -(-self._window // self.page_size) + 1
                layer_pages += sum(min(-(-ln // self.page_size), wcap)
                                   for ln in lens) * readers[1]
            self._ledger_bytes += (
                dl * self._hbm_weight_read_bytes
                + dl * layer_pages * self.page_size * per_tok
                * spec_rows)
            if lens:
                # the budget's view of the SAME step: one weight pass +
                # every live cache byte at the mean context
                budget = _costmodel.decode_step_budget(
                    self._cache_family, batch=len(lens),
                    context=sum(lens) / len(lens), layers=n_layers,
                    weight_bytes=self._hbm_weight_read_bytes,
                    kv_heads=kv, head_dim=d,
                    kv_latent_dim=(d if self._latent else 0),
                    kv_dtype_bytes=self._kv_itemsize,
                    page_size=self.page_size, spec_rows=spec_rows,
                    passes=self._passes)
                self._ledger_model_bytes += budget["bytes_per_step"]
        if self._ledger_tokens:
            _G_BPT_MEASURED.set(self._ledger_bytes
                                / self._ledger_tokens)
            _G_BPT_MODEL.set(self._ledger_model_bytes
                             / self._ledger_tokens)
        _G_HBM_DRAFT.set(len(lens) * self.spec_k * 2 * 4)

    def hbm_accounting(self) -> Dict[str, float]:
        """Live HBM/bandwidth ledger snapshot for the observatory:
        static residency (weights, page pool, draft state) plus the
        measured and model bytes-per-token the 25% acceptance check
        compares."""
        acct = {
            "weights_bytes": float(self._hbm_weights_bytes),
            "page_pool_bytes": float(self._hbm_pool_bytes),
            "state_pool_bytes": float(self._ssm_layers * (
                self.max_slots + 1) * self._ssm_slot_bytes),
            "draft_bytes": float(_G_HBM_DRAFT.value),
            # a residual of several streams a token: what the step's
            # flat buffer holds of it between two sublayers
            "residual_stream_bytes": float(
                self._launch_rows(self.prefill_chunk)
                * self._stream_row_bytes),
            "ledger_bytes": float(self._ledger_bytes),
            "ledger_tokens": int(self._ledger_tokens),
            "bytes_per_token_measured": (
                self._ledger_bytes / self._ledger_tokens
                if self._ledger_tokens else 0.0),
            "bytes_per_token_model": (
                self._ledger_model_bytes / self._ledger_tokens
                if self._ledger_tokens else 0.0),
        }
        if self._tail_snapshots:
            # the tails at every page's last row, every `C` block
            # (inside `page_pool_bytes`, as the state pool is)
            acct["tail_snapshot_bytes"] = float(
                self._ssm_layers * self.num_pages * self._tail_bytes)
        # KV heads and query tiles a page visit of the ragged kernel
        # serves, and the rows it computes for a sequence that owns a
        # few of a tile's (0: always the tile's), by layer kind (the
        # fewest over the kind's head counts), at the row count of a
        # launch with a chunk
        for k, reps in self._kind_rep.items():
            for name, choice in self._attn_tiling(
                    self._launch_rows(self.prefill_chunk)).items():
                acct["attn_" + name + (".window" if k else "")] = \
                    float(min(choice[r] for r in reps))
        return acct

    def program_cache_sizes(self) -> Dict[str, int]:
        """{program name: compiled-variant count} for this engine's
        jitted programs, {"unified": n, "feed": n, "unified_nochunk":
        n, "feed_nochunk": n} — the PT002 no-retrace guard's hook. Every
        count must stay at 1 after any join/leave pattern (a step
        program counts 0 until its first launch: `unified_nochunk` on an
        engine that has not decoded yet, or never does —
        ``role="prefill"``)."""
        return {name: fn._cache_size()
                for name, fn in self._programs.items()}

    def compiled_programs(self) -> Dict[str, object]:
        """{program name: its `jax.stages.Compiled`} of this engine's
        step programs, for whoever reads a trace of them afterwards
        (`observability.attribution.op_scopes`). Each is lowered again
        at the shapes its launches have — an idle launch's row tables
        at the program's own row count, the weights and pools as they
        stand — and compiled, which the
        compile cache answers where it answered the step; nothing is
        launched and no pool is taken. Called by no part of the engine:
        a run that does not ask pays nothing."""
        with _obs.span("serving.engine.compiled_programs"):
            args = {}
            for sfx, chunk in self._chunk_parts().items():
                args["unified" + sfx], args["feed" + sfx] = \
                    self._program_shapes(chunk)
            return {name: compile_named(
                        fn, args[name],
                        lambda n=name: self._step_programs()[n])
                    for name, fn in self._programs.items()}

    def _program_shapes(self, chunk: int):
        """(the step program's arguments, its token feed's) at a chunk
        part of `chunk` rows, in shapes: an idle launch's row tables,
        the pools as they stand; the weights themselves."""
        def shaped(a):
            return jax.ShapeDtypeStruct(np.shape(a), a.dtype)

        host, src, *_ = self._build_unified(None, [], None, chunk)
        tok, *rest = jax.tree_util.tree_map(shaped, host)
        pools = jax.tree_util.tree_map(shaped, self._live_pools())
        return ((self._w, tok, pools, *rest),
                (shaped(self._no_tokens), tok, shaped(src)))

    def collect(self) -> Dict[object, object]:
        """Results of every request finished since the last collect():
        {request_id: np.int32[max_new_tokens] | TimeoutResult |
        Overloaded}."""
        return {r.request_id: r.result
                for r in self.scheduler.drain_finished()}

    def scrape(self) -> Dict[str, object]:
        """This replica's registry snapshot for fleet federation
        (`FleetRouter.scrape()` → `observability.fleet.federate`).

        In-process fleets share ONE default registry, so the per-replica
        families here (``serving.replica.*``) are built from engine-local
        state — slots, queue, allocator, trie, launch and handoff totals
        — into a fresh registry and returned in `Registry.snapshot()`
        format. Returns {} with metrics disabled (the federation
        mutation entry point honors `FLAGS_metrics`)."""
        if not _obs.enabled():
            return {}
        reg = _obs.Registry()
        reg.gauge("serving.replica.info",
                  "replica role marker (value always 1)",
                  labels=("role",)).labels(role=self.role).set(1)
        reg.gauge("serving.replica.active_slots",
                  "requests holding a slot").set(self.scheduler.inflight)
        reg.gauge("serving.replica.waiting",
                  "requests queued for admission").set(
                      len(self.scheduler.waiting))
        st = self.allocator.stats()
        reg.gauge("serving.replica.kv_pages_used",
                  "KV pool pages in use").set(st["pages_used"])
        reg.gauge("serving.replica.kv_pages_free",
                  "KV pool pages free").set(st["pages_free"])
        reg.gauge("serving.replica.kv_utilization",
                  "KV pool utilization [0,1]").set(st["utilization"])
        if self.prefix_cache is not None:
            reg.gauge("serving.replica.prefix_pages",
                      "radix-trie pages pinned on this replica").set(
                          self.prefix_cache.pages)
        reg.counter("serving.replica.launches",
                    "device program launches").inc(self.launches)
        reg.counter("serving.replica.rows_computed",
                    "flat rows the step launches computed: a launch with "
                    "a prompt's chunk max_slots x (1 + spec_k) + "
                    "prefill_chunk, one without max_slots x (1 + spec_k) "
                    "(a block family: blocks, and its riding region)"
                    ).inc(self.rows_computed)
        reg.counter("serving.replica.rows_owned",
                    "of the rows computed, those a sequence owned (decode "
                    "and draft rows, prompt rows)").inc(self.rows_owned)
        hc = reg.counter("serving.replica.handoffs",
                         "KV-page handoffs by direction",
                         labels=("direction",))
        for direction, n in self._handoff_counts.items():
            hc.labels(direction=direction).inc(n)
        return reg.snapshot()

    def run_to_completion(self) -> Dict[object, object]:
        """Step until idle; collect everything."""
        results: Dict[object, object] = {}
        while self.has_work():
            self.step()
            results.update(self.collect())
        results.update(self.collect())
        return results

    # ------------------------------------------------------------ handoff
    def set_replica(self, name: str) -> None:
        """Name this replica for routing/metrics (the FleetRouter calls
        this for replicas constructed without `replica=`)."""
        self.replica = name
        if self.prefix_cache is not None:
            self.prefix_cache.set_replica(name)

    def _stage_handoff(self, req: Request) -> None:
        """Prefill-role completion: give up the slot and queue the
        request for export — a decode replica resumes it without
        re-prefill. Called right after the first token was emitted, so
        the KV-length invariant (length == prompt.size, pending ==
        tokens[-1]) holds."""
        self.scheduler.detach(req)
        self.handoff_ready.append(req)
        _TRACE.stamp(req.request_id, "handoff_ready",
                     kv_tokens=self.allocator.seq_length(req.request_id))

    def export_request(self, req: Request) -> KVPageHandoff:
        """Export an in-flight request as a `KVPageHandoff`: pin its
        pages, snapshot (page table, block payload, sampler state),
        and remove it from this replica. Works for staged prefill
        completions, running decodes, and preempted-waiting requests —
        any request whose prefill is complete (the drain path exports
        mid-stream decodes pages-intact). The export pins keep the
        pages readable until the importer's `release()`, and trie pins
        keep shared prompt pages warm on this replica regardless."""
        rid = req.request_id
        self._no_handoff("export_request")
        _TRACE.set_replica_context(self.replica)
        self.retire()   # the payload is what every dispatched row wrote
        if req.pending is None or req.prefill_pos < int(req.prompt.size):
            raise ValueError(
                f"request {rid} is not exportable mid-prefill "
                f"({req.prefill_pos}/{int(req.prompt.size)} tokens)")
        if req in self.handoff_ready:
            self.handoff_ready.remove(req)
        else:
            self.scheduler.detach(req)
        exp = self.allocator.export_seq(rid)
        pages = np.asarray(exp["pages"], np.int32)
        if self._family == "mla":
            blocks = [np.asarray(pool[:, pages])
                      for pool in self._live_pools()]
        else:
            blocks = [(np.asarray(kp[:, pages]), np.asarray(vp[:, pages]))
                      for kp, vp in self._live_pools()]
        # remaining deadline travels with the request (the importer's
        # submit() restarts the clock)
        dl = req.deadline_s
        if req._deadline is not None:
            dl = max(1e-6, req._deadline.budget_s
                     - req._deadline.elapsed_s)
        # the sequence leaves this replica the moment the payload is
        # snapshotted; the export pins (dropped by release()) keep the
        # protocol window consistent even so
        self.allocator.free(rid)
        alloc = self.allocator
        handoff = KVPageHandoff(
            request_id=rid, prompt=req.prompt,
            max_new_tokens=req.max_new_tokens,
            eos_token_id=req.eos_token_id, pad_token_id=req.pad_token_id,
            priority=req.priority, tenant=req.tenant, deadline_s=dl,
            tokens=list(req.tokens), pending=int(req.pending),
            shared_tokens=req.shared_tokens,
            kv_length=int(exp["length"]), blocks=blocks,
            page_size=self.page_size, family=self._family,
            source=self.replica or "", _release=lambda:
            alloc.release_export(exp))
        self._handoff_counts["export"] += 1
        if _obs.enabled():
            HANDOFFS.labels(direction="export").inc()
            HANDOFF_PAGES.inc(len(exp["pages"]))
            HANDOFF_BYTES.inc(handoff.payload_bytes)
        _TRACE.stamp(rid, "handoff_export", pages=len(exp["pages"]),
                     kv_tokens=handoff.kv_length)
        # the trace context travels WITH the KV pages: the importer
        # adopts it so the request keeps one timeline across replicas
        handoff.trace = _TRACE.export_context(rid)
        return handoff

    def _no_handoff(self, what: str) -> None:
        if self._block:
            raise NotImplementedError(
                f"{what}: this model generates by diffusion over blocks; "
                f"a handoff at a block border is not implemented")
        if self._ssm_layers:
            raise NotImplementedError(
                f"{what}: this model has state blocks; a handoff of a "
                f"sequence's recurrent state or convolution tails beside "
                f"its KV pages is not implemented")
        if self._passes > 1:
            raise NotImplementedError(
                f"{what}: this model runs its layers {self._passes} times "
                f"a token; a KV-page handoff of {self._passes} pages a "
                f"page id is not implemented")
        if self._eva:
            raise NotImplementedError(
                f"{what}: this model's layers are chunk-summary attention; "
                f"a KV-page handoff of pooled rows and a tumbling "
                f"window's pages is not implemented")
        if self._window is not None:
            raise NotImplementedError(
                f"{what}: this model has sliding-window layers, whose "
                f"pages are released as the window passes them; a "
                f"KV-page handoff of two page kinds is not implemented")

    def import_request(self, handoff: KVPageHandoff) -> Request:
        """Receive side of the handoff: allocate destination pages,
        copy the block payload into this replica's pools, and submit
        the rebuilt request with `preempted=True` so the scheduler
        resumes it straight into DECODE (the PR-10 resume path) — no
        re-prefill. Raises `resilience.Overloaded` (allocator or
        admission gate) with this replica unchanged, so the router can
        retry the same handoff elsewhere."""
        self._no_handoff("import_request")
        if self.role == "prefill":
            raise ValueError("prefill-role replica cannot decode an "
                             "imported request")
        if handoff.family != self._family:
            raise ValueError(
                f"family mismatch: handoff {handoff.family} vs engine "
                f"{self._family}")
        if handoff.page_size != self.page_size:
            raise ValueError(
                f"page_size mismatch: handoff {handoff.page_size} vs "
                f"engine {self.page_size}")
        _TRACE.set_replica_context(self.replica)
        self.retire()
        _TRACE.adopt(handoff.request_id, handoff.trace)
        req = Request(handoff.prompt, handoff.max_new_tokens,
                      eos_token_id=handoff.eos_token_id,
                      pad_token_id=handoff.pad_token_id,
                      deadline_s=handoff.deadline_s,
                      request_id=handoff.request_id,
                      priority=handoff.priority, tenant=handoff.tenant)
        pages = self.allocator.import_seq(
            req.request_id, handoff.kv_length, req.total_tokens)
        dst = np.asarray(pages, np.int32)
        if self._family == "mla":
            self._pools = [pool.at[:, dst].set(jnp.asarray(blk))
                           for pool, blk in zip(self._live_pools(),
                                                handoff.blocks)]
        else:
            self._pools = [(kp.at[:, dst].set(jnp.asarray(kb)),
                            vp.at[:, dst].set(jnp.asarray(vb)))
                           for (kp, vp), (kb, vb)
                           in zip(self._live_pools(), handoff.blocks)]
        req.tokens = list(handoff.tokens)
        req.pending = handoff.pending
        req.prefill_pos = int(req.prompt.size)
        req.shared_tokens = handoff.shared_tokens
        req.preempted = True
        try:
            self.scheduler.submit(req)
        except _res.Overloaded:
            self.allocator.free(req.request_id)
            raise
        # warm THIS replica's trie with the prompt pages so the router's
        # locality score sends the tenant's next request here. The
        # inserted full prompt pages are never rewritten: decode writes
        # land at positions >= kv_length >= prompt.size, past them.
        if self.prefix_cache is not None and self.prefix_cache_admit:
            self.prefix_cache.insert(req.prompt, pages)
        self._handoff_counts["import"] += 1
        if _obs.enabled():
            HANDOFFS.labels(direction="import").inc()
            _REQS.labels(outcome="imported").inc()
        _TRACE.stamp(req.request_id, "handoff_import",
                     source=handoff.source, replica=self.replica or "",
                     pages=len(pages))
        handoff.release()
        return req

    # ---------------------------------------------------------- admission
    def _admit(self) -> int:
        admitted = 0
        while True:
            req = self.scheduler.next_admittable()
            if req is None:
                req = self._preempt_for_waiting()
                if req is None:
                    break
                continue   # the freed slot re-enters next_admittable
            if req.preempted:
                # resume: the allocator sequence — pages, length,
                # pending token — survived preemption untouched, so the
                # request goes straight back to DECODE. No re-prefill.
                self.scheduler.admit(req)
                admitted += 1
                continue
            if not self._reserve_pages(req):
                break   # head-of-class waits for pages; no skip
            self.scheduler.admit(req)
            if req.shared_tokens > 0:
                _TRACE.stamp(req.request_id,
                             "prefix_hit" if req._share_source == "cache"
                             else "prefix_share",
                             tokens=req.shared_tokens,
                             **req._share_meta)
            if self._prompt_rows(req):
                self._prefill_fifo.append(req)
            else:       # a prompt shorter than a block: all given tokens
                req.state = DECODE
            admitted += 1
        return admitted

    def _reserve_pages(self, req: Request) -> bool:
        """Reserve the request's pages, sharing the longest available
        prefix — a live donor's prefilled prompt (token-granular fork)
        or the global prefix cache (page-granular adopt), whichever is
        longer. Under pool pressure, cold trie pages are evicted and
        the reservation retried ONCE. Every failure path releases the
        lookup's pins (no leaked refcounts); returns False so the
        request keeps waiting."""
        share, donor = 0, None
        if self.prefix_sharing:
            for _, cand in self.scheduler.active():
                # only the donor's PREFILLED prompt tokens are
                # reusable — those whose chunk is dispatched: the rider
                # reads them in a later launch, which the device runs
                # after the one that writes them; cap at len(prompt)-1
                # so the last prompt token is always re-run for this
                # request's logits
                s = min(_lcp(req.prompt, cand.prompt),
                        self._sent_pos(cand, self._inflight),
                        int(req.prompt.size) - 1)
                if s > share:
                    share, donor = s, cand
        match = self.prefix_cache.lookup(req.prompt) \
            if self.prefix_cache is not None else None
        use_cache = match is not None and match.tokens > share

        def take() -> None:
            if use_cache:
                self.allocator.adopt(req.request_id, match.pages,
                                     match.tokens, req.total_tokens)
            elif share > 0:
                self.allocator.fork(donor.request_id, req.request_id,
                                    share, req.total_tokens)
            else:
                self.allocator.allocate(req.request_id, req.total_tokens)

        try:
            try:
                take()
            except _res.Overloaded:
                if self.prefix_cache is None:
                    raise
                eff = match.tokens if use_cache else share
                need = self.allocator.pages_needed(req.total_tokens, eff)
                freed = self.prefix_cache.evict(
                    need - self.allocator.available_pages)
                self._counts["prefix_pages_evicted"] += freed
                if freed <= 0:
                    raise
                take()
        except _res.Overloaded:
            if match is not None:
                match.release()
            return False
        if use_cache:
            self.prefix_cache.note_adopted(match.tokens)
            req._share_source = "cache"
            req._share_meta = {"pages": len(match.pages)}
            req.prefill_pos = req.shared_tokens = match.tokens
            self._counts["prefix_tokens_adopted"] += match.tokens
            self._counts["prefix_pages_adopted"] += len(match.pages)
        elif share > 0:
            req._share_source = "donor"
            req._share_meta = {"donor": donor.request_id}
            req.prefill_pos = req.shared_tokens = share
        else:
            req._share_source = None
            req._share_meta = {}
            req.prefill_pos = req.shared_tokens = 0
        if match is not None:
            match.release()   # adopt holds its own refcounts by now
        return True

    def _preempt_for_waiting(self) -> Optional[Request]:
        """Make room for the highest-priority waiting request by
        re-queueing a strictly lower-priority DECODE victim with its
        pages intact. Only fires when the candidate's pages would
        actually fit (the victim keeps its pages, so preempting for a
        pool-blocked candidate would just thrash)."""
        if not self.preemption:
            return None
        cand = self.scheduler.next_candidate()
        if cand is None:
            return None
        victim = self.scheduler.pick_victim(cand.priority)
        if victim is None:
            return None
        if not cand.preempted:
            share = self.prefix_cache.match_length(cand.prompt) \
                if self.prefix_cache is not None else 0
            need = self.allocator.pages_needed(cand.total_tokens, share)
            spare = self.allocator.available_pages + (
                self.prefix_cache.evictable_pages()
                if self.prefix_cache is not None else 0)
            if need > spare or (self._window is not None and not
                                self.allocator.can_admit(cand.total_tokens)):
                return None
        if self._inflight is not None:
            # the victim's next token is on the device: read it back,
            # then ask again (a slot may have come free by itself)
            self.retire()
            return cand
        self.scheduler.preempt(victim)
        self._counts["preempted"] += 1
        if _obs.enabled():
            _PREEMPTIONS.inc()
        return cand

    # ------------------------------------------------------------ unified
    def _unified_step(self) -> None:
        """ONE ragged launch for the whole step: decode slot `s` owns
        flat rows [s*R, s*R + 1 + k) with R = 1 + spec_k — its pending
        token plus k n-gram-drafted tokens verified in the SAME launch
        — and the oldest prefilling request's chunk rides rows
        [max_slots*R, max_slots*R + n). Row tables (num_tokens /
        kv_lengths / page tables, seq_start baked into the jitted body)
        tell the ragged kernel who owns which rows; idle rows write to
        the trash page and emit garbage logits the host never reads.

        A launch computes the rows it carries: when no prompt is being
        dispatched (`_next_chunk_request` gives None for THIS launch)
        the flat buffer ends at row max_slots*R and the launch runs the
        body compiled at that row count (`unified_nochunk`, with its
        feed), the prefix of the tables a launch with a chunk takes.
        The sequence tables keep their one shape, the chunk's sequence
        empty, so what a launch returns and what retires it do not
        know which of the two ran (`rows_computed` says).

        A launch queue of depth one: this call builds and DISPATCHES
        its launch first and only then reads back the launch the call
        before dispatched (`_await_launch` in the sync phase,
        `_sample_unified` in the sample phase), so the device runs one
        launch while the host retires the one before and builds the
        next. Building needs no token VALUE, only what is known at
        dispatch: a decode row's position is the sequence's length (the
        allocator is extended at dispatch), its input token is row
        `src` of the launch in flight's greedy tokens and is fed on the
        device (the `feed` program), a request whose token in flight is its
        `max_new_tokens`-th gets no row, and a prompt advances by
        `_sent_pos`. What cannot be known a launch ahead is seen a
        launch late: an EOS finish leaves one row in the next launch,
        computed and dropped (`rows_dropped`). Its pages, like a
        window's passed pages in `account`, go back to the pool at
        once: every device program takes the pools the one before it
        returned, so the device runs them in dispatch order — a freed
        page is next WRITTEN by a later launch (or copy), after the
        launch that still names it has run, and no `kv_lengths` reaches
        a row its owner has not written.

        Speculative decoding drafts from the last token on the host,
        so such an engine retires its own launch before it returns
        (depth 0: the same code, the queue empty at every build).
        Accept/rollback is greedy-exact: position j's argmax depends
        only on rows 0..j of the slot (per-row causality), so drafted
        tokens are accepted while they match the argmax chain and the
        KV length is shrunk past the first mismatch — engine output is
        bit-identical to plain decode, just fewer launches.

        A request that completes its prefill emits its first token from
        its chunk's launch and takes its first decode step in the NEXT
        one."""
        prev = self._inflight
        preq = self._next_chunk_request(prev)
        rows = self._decode_rows(prev)
        if prev is None and preq is None and not rows:
            return
        new, work = None, preq is not None or bool(rows)
        # the launch's programs: those of the rows it carries
        sfx = "" if preq is not None else "_nochunk"
        with _obs.span("serving.engine.build"):
            if work:
                host, src, drafts, n, start, counts = \
                    self._build_unified(preq, rows, prev,
                                        self._chunk_parts()[sfx])
        with _obs.span("serving.engine.launch"):
            if work:
                tok = self._programs["feed" + sfx](
                    self._no_tokens if prev is None else prev.tokens,
                    host[0], src)
                logits, tokens, *moe = self._launch(
                    self._programs["unified" + sfx], tok,
                    *(jax.tree_util.tree_map(jnp.asarray, t)
                      for t in host[1:]))
                row_of = {id(req): slot for slot, req, _ in rows}
                if self._block:
                    # a slot's NEXT input is its block after this pass,
                    # rows [slot x B, ..) of the launch's tokens — or the
                    # host's own (-1): a new block, all given and masks
                    row_of = {id(req): slot * self._block
                              if req.block_pass else -1
                              for slot, req, _ in rows}
                if preq is not None:
                    if start + n == self._prompt_rows(preq):
                        row_of[id(preq)] = -1 if self._block \
                            else self.max_slots
                    _TRACE.stamp(preq.request_id, "prefill_chunk",
                                 tokens=n, start=start)
                new = self._inflight = _Launch(
                    logits, tokens, moe[0] if moe else None, preq, n,
                    [(slot, req) for slot, req, _ in rows], drafts,
                    row_of, counts)
                self._counts["launch_ahead"] = int(prev is not None)
                self.rows_computed += counts["rows_computed"]
                self.rows_owned += counts["decode_rows"] + n
                if _obs.enabled():
                    _LAUNCHES.labels(path="unified").inc()
                    _STEPS.labels(phase="unified").inc()
                    if n:
                        _TOKENS.labels(phase="prefill").inc(n)
        # retire the launch before this one; a drafting engine its own
        due = prev if prev is not None else new if self.spec_k else None
        with _obs.span("serving.engine.sync"):
            back = self._await_launch(due) if due is not None else None
        with _obs.span("serving.engine.sample"):
            if due is not None:
                self._sample_unified(due, *back)

    def _sent_pos(self, req: Request, fl: Optional[_Launch]) -> int:
        """Prompt tokens of `req` whose chunk has been DISPATCHED:
        `Request.prefill_pos` counts the chunks that have retired, the
        launch in flight `fl` may carry one more."""
        return req.prefill_pos + (
            fl.n if fl is not None and fl.preq is req else 0)

    def _next_chunk_request(self, fl: Optional[_Launch]) \
            -> Optional[Request]:
        """The oldest request with prompt left to dispatch."""
        fifo = self._prefill_fifo
        while fifo and (fifo[0].state != PREFILL
                        or self._sent_pos(fifo[0], fl)
                        >= self._prompt_rows(fifo[0])):
            fifo.pop(0)
        return fifo[0] if fifo else None

    def _decode_rows(self, fl: Optional[_Launch]):
        """[(slot, request, src)] of the next launch's decode rows:
        every request in a slot whose prompt is dispatched in full and
        that has a token left to make. `src` is the row of the launch
        in flight `fl` that produces the request's input token, None
        when the host holds it (`Request.pending`). A block family: every
        request with a block left to dispatch — the host knows how many
        its prompt and budget make (`_blocks_of`) — and `src` the first
        row of its block after the pass in flight, None where the host
        holds the block (`Request.block_tokens`) or opens a new one; the
        rows of a commit that rides with a new block (`_build_unified`)
        are fed from the same `src`."""
        rows = []
        for slot, req in self.scheduler.active():
            src = fl.row_of.get(id(req)) if fl is not None else None
            if req.state != DECODE and (src is None
                                        or self.role == "prefill"):
                continue    # mid-prompt, or staged for export next
            if self._block:
                if req.blocks_sent < self._blocks_of(req):
                    rows.append((slot, req, None if src is None or src < 0
                                 else src))
            elif len(req.tokens) + (src is not None) < req.max_new_tokens:
                rows.append((slot, req, src))
        return rows

    def _blocks_of(self, req: Request) -> int:
        """Blocks a request generates: its given tokens (the prompt's
        remainder) and its budget in whole blocks; the last one is cut."""
        B = self._block
        return -(-(int(req.prompt.size) % B + req.max_new_tokens) // B)

    def _build_unified(self, preq: Optional[Request], rows,
                       fl: Optional[_Launch], chunk: int):
        """The host half of the unified launch: extend every sequence
        (applying copy-on-write copies) and fill the row tables, T =
        `_launch_rows(chunk)` = max_slots x (1 + spec_k) + `chunk` flat
        rows long (a block family: + the riding region): `chunk` is
        `prefill_chunk` where the launch carries `preq`'s rows and 0
        where it carries none (`_unified_step`; `compiled_programs`
        asks for an idle launch's tables at either length).
        Returns ((tok, positions, num_tokens, kv_lengths, tables,
        tok_page, tok_off), src, drafts by slot, prefill rows, their
        start, the launch's counts for the step record that retires
        it). `src` [T] says where each row's token comes from: -1 the
        host's `tok`, else that row of the launch in flight's tokens.
        With sliding-window layers `tables` and `tok_page` are pairs:
        (the full kind's, the window kind's). With chunk-summary layers
        a sequence's table is its visible pooled pages then its
        window's, `kv_lengths` counts from the table's first row and
        three operands are pairs: (`kv_lengths`, the pooled rows
        visible), (`tok_page`, [2, P] pages: where each closing chunk's
        tokens lie and where its pooled row goes), (`tok_off`, [2, P]:
        the chunk within that page, the row within that one).

        A block family: a slot's rows are pass `Request.block_pass` of
        its open block. Where that pass is the COMMIT, the request has a
        block after it and the region behind the block rows has room
        (`_riders` blocks a launch), the launch carries both: the
        commit's rows ride there as a sequence of their own, and the
        slot's rows are pass 0 of the NEXT block. The slot counts one
        pass (denoise, and `diffusion_passes_fused`); `drafts[slot]`
        lists both for the retire, the commit first."""
        B, C, K = self.max_slots, chunk, self.spec_k
        R, blk = self._slot_rows, self._block
        # the chunk's rows start behind the decode slots' and, a block
        # family, the riding commits' (sequences B .. B + riders - 1)
        base = (B + self._riders) * R
        T, S = base + C, B + self._riders + 1
        tiling = self._attn_tiling(T)   # the kernel's, at THIS row count
        ps, nj = self.page_size, self.pages_per_seq
        tok = np.zeros(T, np.int32)
        src = np.full(T, -1, np.int32)
        positions = np.zeros(T, np.int32)
        num_tokens = np.zeros(S, np.int32)
        kv_lengths = np.zeros(S, np.int32)
        tables = np.zeros((S, nj), np.int32)   # idle -> trash page 0
        tok_page = np.zeros(T, np.int32)
        tok_off = np.zeros(T, np.int32)
        row_live = np.zeros(T, bool)    # rows a sequence owns ...
        row_first = np.zeros(T, bool)   # ... and each sequence's first
        windowed = self._window is not None
        if windowed:
            wtables = np.zeros((S, nj), np.int32)
            wtok_page = np.zeros(T, np.int32)
        eva = self._eva
        if eva:
            # one pooling slot a decode row, then one for each chunk
            # the prefill rows can close; idle ones read and write the
            # trash page
            P = B + C // self.allocator.chunk
            summary_rows = np.zeros(S, np.int32)
            pool_page = np.zeros((2, P), np.int32)
            pool_off = np.zeros((2, P), np.int32)

        def place(rid, seq, r0, pos, p0):
            """Sequence `seq`'s new rows [r0, r0 + pos.size) at
            positions `pos` (the allocator extended): its page table and
            KV length as attention reads them and the page each row
            lands in; with chunk-summary layers also the chunks the
            rows close, in pooling slots from p0."""
            rows = slice(r0, r0 + pos.size)
            positions[rows] = pos
            num_tokens[seq] = pos.size
            row_live[rows], row_first[r0] = True, True
            tok_off[rows] = pos % ps
            if eva:
                (tables[seq], summary_rows[seq],
                 kv_lengths[seq]) = self.allocator.attention_view(rid)
                tok_page[rows] = self.allocator.token_pages(rid, pos)
                ck = self.allocator.closing_chunks(rid, int(pos[0]),
                                                   pos.size)
                pool_page[:, p0:p0 + len(ck)] = ck[:, (0, 2)].T
                pool_off[:, p0:p0 + len(ck)] = ck[:, (1, 3)].T
                return
            kv_lengths[seq] = pos[-1] + 1
            tables[seq] = tbl = self.allocator.table(rid)
            tok_page[rows] = tbl[pos // ps]
            if windowed:
                wtables[seq] = wt = self.allocator.window_table(rid)
                wtok_page[rows] = wt[pos // ps]

        drafts: Dict[int, List[int]] = {}
        if blk:
            # rows a slot's pass unmasks (0: a commit pass, an idle slot)
            take = np.zeros(B, np.int32)
            diff = dict.fromkeys(_tracing.STEP_COUNTS_DIFFUSION[:4], 0)
            riding = 0      # riding commits placed so far
        for slot, req, feed in rows:
            if blk:
                # pass `p` of the request's open block, of `total`: the
                # schedule is static, so the host knows it a launch ahead
                g = int(req.prompt.size) % blk if not req.blocks_sent else 0
                p, total = req.block_pass, block_passes(
                    blk, self._diff_steps, g)
                r0, commit = slot * R, p == total - 1
                drafts[slot] = []
                if commit and riding < self._riders \
                        and req.blocks_sent + 1 < self._blocks_of(req):
                    # the commit RIDES: the block's final rows go in as
                    # a block-sized prefill of the slot's own sequence,
                    # in the region behind the block rows (written,
                    # attended under a `kv_lengths` that ends at their
                    # block, never sampled), and the slot's own rows
                    # open the next block, whose pass 0 reads this
                    # block's final K/V from the pages: every layer
                    # appends before any row attends
                    rr = (B + riding) * R
                    if feed is None:
                        tok[rr:rr + blk] = req.block_tokens
                    else:
                        src[rr:rr + blk] = feed + np.arange(blk)
                    ln = self.allocator.seq_length(req.request_id)
                    place(req.request_id, B + riding, rr,
                          ln - blk + np.arange(blk), slot)
                    drafts[slot].append((p, total, g))
                    req.blocks_sent += 1
                    riding += 1
                    diff["diffusion_passes_fused"] += 1
                    g, p, feed, commit = 0, 0, None, False
                    total = block_passes(blk, self._diff_steps)
                if p == 0:
                    # the block's rows join the sequence, and stay
                    self.allocator.extend(req.request_id, blk)
                    tok[r0:r0 + blk] = self._fresh_block(req, g)
                elif feed is None:
                    tok[r0:r0 + blk] = req.block_tokens
                else:       # the block is still on the device
                    src[r0:r0 + blk] = feed + np.arange(blk)
                per = blk // self._diff_steps
                take[slot] = 0 if commit else per
                ln = self.allocator.seq_length(req.request_id)
                place(req.request_id, slot, r0, ln - blk + np.arange(blk),
                      slot)
                drafts[slot].append((p, total, g))
                req.block_pass = 0 if commit else p + 1
                req.blocks_sent += commit
                diff["diffusion_passes_commit" if commit
                     else "diffusion_passes_denoise"] += 1
                diff["diffusion_rows_masked"] += \
                    0 if commit else blk - g - p * per
                continue
            ln = self.allocator.seq_length(req.request_id)
            d: List[int] = []
            if K:
                # never draft past max_new - 1: the verify step itself
                # emits up to k+1 tokens
                cap = req.max_new_tokens - len(req.tokens) - 1
                if cap > 0:
                    d = ngram_draft(
                        np.concatenate([req.prompt, req.tokens]),
                        min(K, cap))
            drafts[slot] = d
            nt = 1 + len(d)
            self._apply_copies(self.allocator.extend(req.request_id, nt),
                               req)
            r0 = slot * R
            if feed is None:
                tok[r0:r0 + nt] = [req.pending] + d
            else:
                src[r0] = feed      # its token is still on the device
            place(req.request_id, slot, r0, ln + np.arange(nt), slot)
            if d:
                _TRACE.stamp(req.request_id, "draft", tokens=len(d))
        n, start = 0, 0
        if preq is not None:
            start = self._sent_pos(preq, fl)
            n = min(C, self._prompt_rows(preq) - start)
            if eva:     # a chunk may not straddle a window
                n = min(n, self.allocator.span
                        - start % self.allocator.span)
            self._apply_copies(self.allocator.extend(preq.request_id, n),
                               preq)
            tok[base:base + n] = preq.prompt[start:start + n]
            place(preq.request_id, S - 1, base, start + np.arange(n), B)
        # (riding rows are decode rows: block rows that were computed)
        counts = {"decode_rows": int(num_tokens[:S - 1].sum()),
                  "prefill_rows": n, "rows_computed": T}
        # each REQUEST's cache tokens, once: a riding commit's entry
        # reads pages its slot's own entry reads too
        kv_once = np.delete(kv_lengths, slice(B, S - 1))
        if blk:
            counts.update(diff, diffusion_blocks_open=len(rows),
                          diffusion_kv_tokens=int(kv_once.sum()))
        seq_start = np.arange(S) * R
        if self._latent:
            counts["chunk_kv_len"] = int(kv_lengths[S - 1])
            counts["latent_row_bytes"] = \
                self._kv_geom[1] * self._kv_itemsize
        # the runs the launch's append makes of these rows, one layer of
        # each kind (the kinds' pages turn together)
        counts["append_runs"] = (1 + windowed) * append_run_count(
            row_live, row_first, tok_page, tok_off, self._append_tile)
        # pages that hold this launch's tokens, against the K/V page
        # fetches the ragged kernel makes for each KV head (a sequence's
        # pages once for every grid cell — a block of query tiles —
        # that holds rows of it); a visit brings the page for a block
        # of KV heads at once, so the visits it makes are the fetches
        # of all heads over the block
        counts["attn_block_visits"] = counts["attn_narrow_updates"] = 0

        def visited(kind, window=None, tiles=False):
            # one layer of each head count of the kind, summed; with
            # `tiles` the (tile, page) softmax updates a KV head: what
            # the fetches would be at one tile a cell
            total = 0
            for r in self._kind_rep[kind]:
                pages, narrow = ragged_visit_counts(
                    seq_start, num_tokens, kv_lengths, T=T, rep=r,
                    dtype=self._q_dtype, page_size=ps, pages_per_seq=nj,
                    window=window,
                    tb=1 if tiles else tiling["tile_block"][r])
                total += pages
                if not tiles:
                    counts["attn_block_visits"] += \
                        pages * self._kv_geom[0] // tiling["head_block"][r]
                    # of those updates, the ones on the few rows their
                    # sequence owns (the kernel's rule, once a pair)
                    counts["attn_narrow_updates"] += narrow
            return total

        if self._latent:
            counts["attn_tile_chains"] = visited(0, tiles=True)
        if self._hc > 1:
            # every row of the flat buffer is mixed, owned or not
            counts.update({"mhc_rows": T,
                           "mhc_sublayers": 2 * len(self._p["layers"])})
        if self._family == "looped":
            n_layers = len(self._p["layers"])
            counts.update({
                "ut_steps": self._passes,
                "layer_applications": self._passes * n_layers,
                "cache_row_bytes": self._passes * n_layers * 2
                * self._kv_geom[0] * self._kv_geom[1] * self._kv_itemsize})
        live = int(np.sum(-(-kv_once // ps)))
        counts["pages_live"] = live
        counts["pages_visited"] = visited(0)
        if self._ssm_layers:
            # whose state the launch's rows name: the live decode slots
            # first (the spare slot B pads the list), how many, the
            # chunk's slot (the spare without a chunk) and whether this
            # launch STARTS its sequence — from zero state, by this
            # flag, on the device
            # (a family whose pages carry snapshots of its tails: one
            # entry more, the page whose snapshot the chunk CONTINUES —
            # the last page its sequence adopted — or 0, the trash page,
            # for none)
            named = [slot for slot, _, _ in rows]
            tab = np.full(B + 3 + self._tail_snapshots, B, np.int32)
            tab[:len(named)] = named
            tab[B] = len(named)
            # (a sequence starts behind what it adopted: 0 where nothing
            # can be)
            starts = int(preq is not None and start == preq.shared_tokens)
            tab[B + 2] = starts
            if preq is not None:
                tab[B + 1] = preq.slot
            slots = len(named) + (preq is not None)
            counts.update({
                "ssm_slots_live": slots,
                "ssm_state_bytes": 0 if self._tail_only
                else self._ssm_slot_bytes,
                # (a Python int: 5.4e9 at the benchmark's sizes)
                "ssm_state_bytes_moved": (2 * slots - starts)
                * self._ssm_layers * self._ssm_state_bytes,
                "ssm_scan_rows": n, "ssm_state_resets": starts})
            if self._tail_only:
                restore = starts and start > 0
                if self._tail_snapshots:
                    tab[B + 3] = self.allocator.table(
                        preq.request_id)[start // ps - 1] if restore else 0
                counts.update({
                    # the pages whose last row the chunk writes (their
                    # tails go to the planes, every `C` block's), whether
                    # it continues a snapshot, and a slot's tail in ONE
                    # such block
                    "tail_snapshots_written":
                    (start + n) // ps - start // ps
                    if self._tail_snapshots else 0,
                    "tail_restores": int(restore),
                    "tail_bytes": self._tail_bytes})
        # what the step takes as `kv_lengths`: with a state table beside
        kvl = (kv_lengths, tab) if self._ssm_layers \
            else (kv_lengths, take) if blk else kv_lengths
        if eva:
            seen = num_tokens > 0
            ends = positions[(seq_start + num_tokens - 1)[seen]] + 1
            counts.update({
                "summary_rows_live": int(summary_rows[seen].sum()),
                "window_rows_live": int(
                    (kv_lengths - -(-summary_rows // ps) * ps)[seen].sum()),
                "summaries_written": int((pool_page[1] > 0).sum()),
                "pool_append_runs": append_run_count(
                    pool_page[1] > 0, False, pool_page[1], pool_off[1],
                    self._append_tile),
                "windows_closed": int(
                    (ends % self.allocator.span == 0).sum()),
                "cache_row_bytes": 2 * self._kv_geom[0] * self._kv_geom[1]
                * self._kv_itemsize})
            return ((tok, positions, num_tokens, (kv_lengths, summary_rows),
                     tables, (tok_page, pool_page), (tok_off, pool_off)),
                    src, drafts, n, start, counts)
        if not windowed:
            return ((tok, positions, num_tokens, kvl, tables,
                     tok_page, tok_off), src, drafts, n, start, counts)
        # the window kind: the pages between each sequence's oldest
        # visible key and its newest
        W = self._window
        oldest = np.maximum(kv_lengths - num_tokens - W + 1, 0)
        wlive = int(np.sum(np.where(
            num_tokens > 0, (kv_lengths - 1) // ps - oldest // ps + 1, 0)))
        wvisited = visited(1, W)
        counts.update({
            "pages_live.full": live, "pages_live.window": wlive,
            "pages_visited.full": counts["pages_visited"],
            "pages_visited.window": wvisited})
        if max(self._pool_readers) > 1:
            # the launches of this step that fetch the full kind's pages
            counts["shared_pool_readers"] = self._kind_readers[0]
        counts["pages_live"] += wlive
        counts["pages_visited"] += wvisited
        return ((tok, positions, num_tokens, kvl,
                 (tables, wtables), (tok_page, wtok_page), tok_off),
                src, drafts, n, start, counts)

    def _fresh_block(self, req: Request, given: int) -> List[int]:
        """A block as it is opened: its given tokens (the prompt's last
        `given`), then the mask token."""
        return [int(t) for t in req.prompt[int(req.prompt.size) - given:]] \
            + [self._mask_id] * (self._block - given)

    def _await_launch(self, fl: _Launch):
        """The wait for launch `fl` and the copy back: its greedy tokens
        ([S] int32; [T] with drafts), the routed layers' counts, and
        the logits rows themselves only where someone asked for them
        (`on_logits`) — from the same launch of the same program."""
        tokens = np.asarray(fl.tokens)
        logits = np.asarray(fl.logits) if self.on_logits is not None \
            and not self._block else None
        if self._block and self.on_block is not None:
            # a live slot's rows only: [slots x B, vocab] is 0.3 GB
            logits = {slot: np.asarray(self._jit_block_rows(
                fl.logits, slot * self._block)) for slot, _ in fl.rows}
        if fl.moe is not None and self._family == "looped":
            # the mean exit distribution of the rows a request owned
            fl.counts["ut_exit_mass"] = tuple(
                float(v) for v in np.asarray(fl.moe))
        elif fl.moe is not None:
            # the routed layers' counts (and a wide residual's) came
            # back with the tokens
            fl.counts.update(zip(self._device_count_names,
                                 (float(v) for v in np.asarray(fl.moe))))
        return tokens, logits

    def _sample_unified(self, fl: _Launch, tokens: np.ndarray,
                        logits: Optional[np.ndarray]) -> None:
        """Retire launch `fl`: emit the prefill chunk's first token when
        it ended its prompt and one token per decode row (drafts
        verified), and add what it did to `_retired` — the counts
        `step()` returns and the launch's part of the step record. A
        row of a request that has ended since the launch was built (an
        EOS seen one launch late) is dropped."""
        if fl is self._inflight:
            self._inflight = None
        B, K = self.max_slots, self.spec_k
        R = self._slot_rows
        base = B * R
        done = self._retired
        counts = fl.counts
        counts["rows_dropped"] = 0

        def row(i):
            return None if logits is None else logits[i]

        preq, n = fl.preq, fl.n
        if preq is not None:
            preq.prefill_pos += n
            done["prefill_tokens"] += n
            if preq.prefill_pos == self._prompt_rows(preq):
                preq.state = DECODE
                # (a block family: no token comes of a prompt's last
                # chunk — its logits are not read; its first block is
                # next — and no page of it is shared)
                if not self._block:
                    # cache the full prompt pages BEFORE _emit can finish
                    # the request and return its pages — trie pins keep
                    # them warm for the next tenant
                    if self.prefix_cache is not None \
                            and self.prefix_cache_admit:
                        self.prefix_cache.insert(
                            preq.prompt,
                            self.allocator.seq_pages(preq.request_id))
                    i = base + n - 1 if K else B
                    fin = self._emit(preq, int(tokens[i]), row(i))
                    done["finished"] += fin
                    if not fin and self.role == "prefill":
                        self._stage_handoff(preq)
        decoded = 0
        for slot, req in fl.rows:
            if req.state != DECODE:
                counts["rows_dropped"] += self._block or 1
                continue
            if self._block:
                *rode, own = fl.drafts[slot]
                fin = 0
                for commit in rode:
                    # a riding commit retires BEFORE the slot's own
                    # rows, the next block's pass 0: its tokens are the
                    # block as the host holds it, its logits nobody's
                    n_out, fin = self._retire_block(
                        req, req.block_tokens, *commit, None)
                    decoded += n_out
                if fin:     # an EOS inside it: the opened block goes
                    counts["rows_dropped"] += R
                else:
                    n_out, fin = self._retire_block(
                        req, tokens[slot * R:(slot + 1) * R], *own,
                        None if logits is None else logits[slot])
                    decoded += n_out
                done["finished"] += fin
                continue
            d = fl.drafts[slot]
            r0 = slot * R
            if not d:
                done["finished"] += self._emit(req, int(tokens[r0]),
                                               row(r0))
                decoded += 1
                continue
            greedy = [int(tokens[r0 + j]) for j in range(len(d) + 1)]
            m = accept_length(d, greedy)
            fin = 0
            for j in range(m + 1):
                decoded += 1
                fin = self._emit(req, greedy[j], row(r0 + j))
                if fin:
                    break   # EOS/max_new: _finish already freed the seq
            done["finished"] += fin
            if not fin:
                # reject the tail: pure length rollback — stale KV past
                # the new length is never readable (kv_lengths caps the
                # attention window) and is overwritten by later tokens
                self.allocator.shrink(req.request_id, len(d) - m)
            record_verify(len(d), m)
            self.spec_drafted += len(d)
            self.spec_accepted += m
            _TRACE.stamp(req.request_id, "verify_accept",
                         drafted=len(d), accepted=m)
        if self._block:
            counts["diffusion_tokens_committed"] = decoded
            if _obs.enabled():
                for kind in ("denoise", "commit", "fused"):
                    _DIFF_PASSES.labels(kind=kind).inc(
                        counts["diffusion_passes_" + kind])
        done["decoded"] += decoded
        if _obs.enabled() and decoded:
            _TOKENS.labels(phase="decode").inc(decoded)
        # the record of the step that retires a launch describes THAT
        # launch: its rows and pages beside the tokens `step()` returns
        # and the device time its span mostly holds
        for k, v in counts.items():
            self._retired_counts[k] = v + self._retired_counts.get(k, 0) \
                if k in _ADDITIVE else v

    def _retire_block(self, req: Request, after: np.ndarray, p: int,
                      total: int, given: int, logits) -> Tuple[int, int]:
        """Pass `p` of `total` of the request's open block has retired
        with the block `after` it [B] (what the launch fed on, on the
        device). The host keeps the block; a COMMIT pass — the block's
        K/V is final — emits its tokens past the `given` ones, up to the
        request's budget or an EOS, and counts the given ones as
        prefilled. A commit that rode in the next block's first launch
        retires through here too, first, with the block the host holds
        as `after` and no `logits`: the listener's and the timeline's
        protocol does not know where a pass ran. -> (tokens emitted, 1
        if the request finished)."""
        before = self._fresh_block(req, given) if p == 0 \
            else req.block_tokens
        req.block_tokens = [int(t) for t in after]
        if self.on_block is not None:
            self.on_block(req, p, total, np.asarray(before, np.int32),
                          logits, np.asarray(after, np.int32))
        if p < total - 1:
            return 0, 0
        req.prefill_pos += given
        out = req.block_tokens[given:][:req.max_new_tokens
                                       - len(req.tokens)]
        if req.eos_token_id in out:
            out = out[:out.index(req.eos_token_id) + 1]
        _TRACE.stamp(req.request_id, "block_commit", tokens=len(out),
                     passes=total)
        fin = 0
        for t in out:
            fin = self._emit(req, t)
        return len(out), fin

    def _emit(self, req: Request, tok: int,
              row: Optional[np.ndarray] = None) -> int:
        """Record one sampled token; finish on EOS/max-tokens (pages
        freed the same step), else stage it for the next decode step.
        `row` is the logits row the token was taken from, for whoever
        set `on_logits`."""
        if self.on_logits is not None and row is not None:
            self.on_logits(req, row)
        req.tokens.append(tok)
        _TRACE.stamp(req.request_id, "token", index=len(req.tokens) - 1)
        done = (req.eos_token_id is not None and tok == req.eos_token_id) \
            or len(req.tokens) >= req.max_new_tokens
        if done:
            self._finish(req)
            return 1
        req.pending = tok
        return 0

    def _finish(self, req: Request) -> None:
        req.finalize()
        self.allocator.free(req.request_id)
        self.scheduler.release(req)
        timeout = isinstance(req.result, _res.TimeoutResult)
        _TRACE.finish(req.request_id, "timeout" if timeout else "finish",
                      tokens=len(req.tokens))
        if _obs.enabled():
            _REQS.labels(outcome="timeout" if timeout
                         else "completed").inc()

    def _apply_copies(self, copies, req: Optional[Request] = None) -> None:
        """Apply the allocator's copy-on-write page copies to the device
        pools before the write that triggered them: the ONE fixed-shape
        program `_build_programs` built and ran once (`_jit_copy`: where
        this engine's pages can be shared at all), so
        a copy compiles nothing when it comes — a shared first token of
        two random prompts is enough to bring one — `_copy_slots`
        (source, destination) pairs a call, padded with the trash page
        onto itself."""
        if not copies:
            return
        # (a copied page is a shared page: never under a window, whose
        # allocator refuses fork and adopt; nor where pages are adopted
        # whole and only then — the copy would leave the page's snapshot
        # behind)
        if self._tail_snapshots:
            raise RuntimeError("a page was forked where prefixes are "
                               "adopted at page borders only")
        self._counts["cow_pages"] += len(copies)
        if req is not None:
            _TRACE.stamp(req.request_id, "cow", pages=len(copies))
        n = self._copy_slots
        for i in range(0, len(copies), n):
            pairs = np.zeros((2, n), np.int32)
            part = np.asarray(copies[i:i + n], np.int32).T
            pairs[:, :part.shape[1]] = part
            self._copy_pages(*pairs)

    # ----------------------------------------------------- jitted bodies
    def _make_unified_body(self, chunk: int):
        """The family's step body with a chunk part of `chunk` rows
        behind the decode rows (`_chunk_parts`)."""
        if self._family == "eva":
            return self._eva_unified_body(chunk)
        if self._family == "looped":
            return self._looped_unified_body(chunk)
        return self._chain_unified_body(chunk)

    # -- unified ragged step -------------------------------------------
    # One fused launch per engine step: T = max_slots x (1 + spec_k) + C
    # flat token rows — C the chunk part's length, `prefill_chunk` or 0,
    # the builders' one argument — and S = max_slots + 1 sequences with
    # BAKED seq_start [0..B-1, B] (decode slot i owns row i; the prefill
    # chunk owns rows B..B+n-1; with C = 0 the last sequence is empty
    # and starts where the buffer ends; a block family has `_riders`
    # block-sized sequences between the slots' and the chunk's,
    # `_seq_starts`). The ragged kernel cuts those
    # rows into tiles of TQ
    # tokens and walks, for each tile, only the live pages of the
    # sequences with rows in it (decode slots share a tile; the chunk
    # spans several and refetches its pages once a tile); its work list
    # is built from the row tables in XLA, once a step (the layers'
    # identical copies merge). The per-layer body is ONE chain: norm ->
    # q / k / v projections -> fused_rope_append (MLA:
    # fused_append_rows) -> ragged_paged_attention -> o-proj -> norm ->
    # _ffn_apply. THREE builders make a step: `_chain_unified_body`
    # walks a family's list of blocks (`_chain_of`, made once at
    # construction: llama / MoE / Laguna, gpt, mla under either
    # residual, the four hybrids); `_eva_unified_body` (a float32
    # stream, a pooling kernel between append and attention) and
    # `_looped_unified_body` (a compiled loop of passes over the layer
    # list) stand beside it. Entry (_seq_starts) and exit (_logit_rows,
    # _head_logits, _greedy) are shared by all three: a step returns
    # (logits rows, the pools, their greedy tokens[, the counts taken on
    # the device]).
    # No flags_guard: nothing in the chain is flag-routed.

    def _run_table(self, seq_start):
        """(num_tokens, tok_page, tok_off) -> the work list of the
        step's `fused_rope_append` calls, made on the device from the
        row tables the step already takes. Its length is the most runs
        a launch of the tables' row count can make: every decode row
        its own, the chunk's one for each tile it touches. With riding
        commits (`_riders`) a pair: the list without their rows, and the
        list of their rows alone, for the append of their own
        (`_gqa_mixer`): a block the tiles it crosses."""
        tile = self._append_tile
        B, R, riders = self.max_slots, self._slot_rows, self._riders
        base = B * R

        def run_table(num_tokens, tok_page, tok_off):
            chunk = tok_page.shape[0] - base - riders * R
            runs = append_run_table(
                seq_start, num_tokens.at[B:B + riders].set(0)
                if riders else num_tokens, tok_page, tok_off, tile=tile,
                max_runs=base + -(-chunk // tile) + 1)
            if not riders:
                return runs
            at = slice(base, base + riders * R)
            return runs, append_run_table(
                seq_start[B:B + riders] - base, num_tokens[B:B + riders],
                tok_page[at], tok_off[at], tile=tile,
                max_runs=riders * (-(-R // tile)
                                   + bool(R % tile and tile % R)))

        return run_table

    def _slot_run_table(self, pool_page, pool_off):
        """The chunk-summary step's second, smaller work list: of the
        pooled rows of its pooling slots (a slot whose summary page is
        not the trash page closed a chunk). At most a decode slot's row
        a run, and one for each tile the chunk's C / chunk consecutive
        rows of a summary page touch."""
        tile = self._append_tile
        chunks = pool_page.shape[0] - self.max_slots
        return append_slot_run_table(
            pool_page, pool_off, tile=tile,
            max_runs=self.max_slots + -(-chunks // tile) + 1)

    def _eva_unified_body(self, C: int):
        """Chunk-summary (EVA) attention on the one chain, a float32
        residual stream. Per layer: norm (gain 1 + g) -> q / k / v ->
        `fused_rope_append` into the window's pages -> `eva_pool`: every
        chunk whose last token this launch wrote is pooled from the
        cache (`fused_chunk_pool`) and its row appended to the
        sequence's summary page, where every later window's queries
        read it -> `eva_attention`: ONE softmax over the visible pooled
        rows and the window's exact rows (`summary_rows=`) -> o-proj and
        SwiGLU accumulated and added in float32. Head 0 of the byte
        heads is the next byte: the logits rows returned and sampled."""
        cfg = self._p["cfg"]
        H, D, V = cfg.num_attention_heads, cfg.head_dim, cfg.vocab_size
        eps, ck = cfg.rms_norm_eps, cfg.chunk_size
        scale = D ** -0.5
        B = self.max_slots
        T = B + C
        seq_start = _seq_starts(B, 1)
        run_table = self._run_table(seq_start)
        f32 = jnp.float32

        def step(w, tok, pools, positions, num_tokens, kv_lengths,
                 tables, tok_page, tok_off):
            kv_lengths, summary_rows = kv_lengths
            tok_page, pool_page = tok_page
            tok_off, pool_off = tok_off
            dt = w["embed"].dtype

            def norm(x, gain):      # float32 in, the weights' type out
                y = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
                return (y * gain).astype(dt)

            with _scope("embed"):
                x = w["embed"][tok][None].astype(f32)    # [1, T, H*D]
                c, s = w["cos"][positions], w["sin"][positions]
            with _scope("cache_write"):
                runs = run_table(num_tokens, tok_page, tok_off)
                pool_runs = self._slot_run_table(pool_page[1],
                                                 pool_off[1])
            new_pools = []
            for L, (kp, vp) in zip(w["layers"], pools):
                with _scope("attn_norm"):
                    h = norm(x, L["ln1"])
                with _scope("qkv_proj"):
                    q, k, v = (_mm_heads(h, L, w).reshape(T, H, D)
                               for w in ("wq", "wk", "wv"))
                # `eva_pool` / `eva_attention` stay the kernels' own
                # (innermost) names: the trace's readers find them so
                with _scope("cache_write"):
                    q, kp, vp = _once(fused_rope_append, "cache_write",
                                      q, k, v, c, s, kp, vp, runs)
                    with jax.named_scope("eva_pool"):
                        kt, vt = _once(
                            fused_chunk_pool, "eva_pool", kp, vp, L["phi"],
                            L["mu"], pool_page[0], pool_off[0], chunk=ck,
                            scale=scale)
                        kp, vp = fused_append_rows(
                            (kp, vp), (kt, vt), pool_runs, scope="eva_pool")
                new_pools.append((kp, vp))
                with _scope("attention"), jax.named_scope("eva_attention"):
                    o = ragged_paged_attention(
                        q, kp, vp, seq_start, num_tokens, kv_lengths,
                        tables, scale=scale, summary_rows=summary_rows,
                        scope="eva_attention")
                with _scope("attn_out"):
                    x = x + jnp.dot(o.reshape(1, T, H * D), L["wo"],
                                    preferred_element_type=f32)
                with _scope("ffn_norm"):
                    h2 = norm(x, L["ln2"])
                with _scope("ffn"):
                    x = x + jnp.dot(
                        jax.nn.silu(h2 @ L["wg"]) * (h2 @ L["wu"]),
                        L["wd"], preferred_element_type=f32)
            with _scope("head"):
                last = _logit_rows(norm(x, w["norm"]), seq_start,
                                   num_tokens, 0)
                logits = jnp.dot(last, w["head"][:, :V],
                                 preferred_element_type=f32)
                tokens = _greedy(logits)
            return logits, new_pools, tokens

        return step

    def _looped_unified_body(self, C: int):
        """A looped decoder on the one chain: the layer list runs
        `total_ut_steps` times inside the launch over weights held once.
        Pass u of layer l keeps its own cache rows: page p of the row
        tables is page p + u * num_pages of the layer's pool (the table,
        and the page column of the append's run table, shifted). A layer
        is a sandwich — norm -> q / k / v -> `fused_rope_append` ->
        `ragged_paged_attention` -> o-proj -> NORM -> add; norm ->
        SwiGLU -> NORM -> add — and every pass ends with the model's
        last norm, whose output feeds the next pass and the exit gate
        (`loop_norm`). The logits are the last pass's; the mean exit
        distribution of the owned rows goes back beside them
        (`ut_exit_mass`). The passes are a compiled loop, not unrolled:
        both forms were measured (PERF.md section 6, PR 39: the same
        step, half the compile, a third of the text)."""
        cfg = self._p["cfg"]
        H, KV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        eps, U, N = cfg.rms_norm_eps, self._passes, self.num_pages
        B = self.max_slots
        T = B + C
        seq_start = _seq_starts(B, 1)
        run_table = self._run_table(seq_start)

        def step(w, tok, pools, positions, num_tokens, kv_lengths,
                 tables, tok_page, tok_off):
            with _scope("embed"):
                x = w["embed"][tok][None]                # [1, T, hidden]
                c, s = w["cos"][positions], w["sin"][positions]
            with _scope("cache_write"):
                runs = run_table(num_tokens, tok_page, tok_off)
                # the run table's page column (`append_run_table`)
                G = runs.shape[0] // 5
                page_col = (jnp.arange(5 * G) // G == 2).astype(jnp.int32)

            # ONE jitted layer, called once a layer: the program's text
            # holds its kernels once, not once a layer, and lowering
            # them is most of a warm start (XLA inlines the calls)
            @jax.jit
            def layer(L, x, kp, vp, table, runs_u, c, s, num_tokens,
                      kv_lengths):
                with _scope("attn_norm"):
                    h = fused_rms_norm(x, L["ln1"], eps)
                with _scope("qkv_proj"):
                    q, k, v = (_mm_heads(h, L, w)
                               for w in ("wq", "wk", "wv"))
                with _scope("cache_write"):
                    q, kp, vp = fused_rope_append(
                        q.reshape(T, H, D), k.reshape(T, KV, D),
                        v.reshape(T, KV, D), c, s, kp, vp, runs_u)
                with _scope("attention"):
                    o = ragged_paged_attention(
                        q, kp, vp, seq_start, num_tokens, kv_lengths,
                        table, scale=D ** -0.5, scope="attention")
                with _scope("attn_out"):
                    x = x + fused_rms_norm(
                        o.reshape(1, T, H * D) @ L["wo"], L["ln1_out"], eps)
                with _scope("ffn_norm"):
                    h2 = fused_rms_norm(x, L["ln2"], eps)
                with _scope("ffn"):
                    x = x + fused_rms_norm(
                        (jax.nn.silu(h2 @ L["wg"]) * (h2 @ L["wu"]))
                        @ L["wd"], L["ln2_out"], eps)
                return x, kp, vp

            def one_pass(u, carry):
                x, pools, lam = carry
                off = u * N
                table, runs_u = tables + off, runs + page_col * off
                new_pools = []
                for L, (kp, vp) in zip(w["layers"], pools):
                    x, kp, vp = layer(L, x, kp, vp, table, runs_u, c, s,
                                      num_tokens, kv_lengths)
                    new_pools.append((kp, vp))
                with _scope("loop_norm"):
                    x = fused_rms_norm(x, w["norm"], eps)
                    gate = jax.nn.sigmoid(
                        jnp.dot(x[0], w["gate_w"],
                                preferred_element_type=jnp.float32)
                        + w["gate_b"].astype(jnp.float32))
                    lam = jax.lax.dynamic_update_index_in_dim(
                        lam, gate, u, 0)
                return x, new_pools, lam

            x, new_pools, lam = jax.lax.fori_loop(
                0, U, one_pass,
                (x, list(pools), jnp.zeros((U, T), jnp.float32)))
            with _scope("head"):
                logits = _head_logits(
                    w, _logit_rows(x, seq_start, num_tokens, 0))
                tokens = _greedy(logits)
            with _scope("loop_norm"):
                own = _owned_rows(T, seq_start, num_tokens)
                mass = jnp.where(own[:, None], _exit_distribution(lam.T), 0)
                mass = mass.sum(0) / jnp.maximum(own.sum(), 1)
            return logits, new_pools, tokens, mass

        return step

    def _state_mixers(self, C: int):
        """{letter: mixer} of a hybrid's state blocks at a launch whose
        chunk part is `C` rows. A mixer's memory of a sequence is its slot
        of the block's state pool and of its convolution tail: ``(L, a [T,
        hidden], the state pool, the tail pool, num_tokens, the state
        table) -> (its output [T, hidden], the state pool, the tail
        pool[, its scan output])``. The state table [B + 3]: the live
        decode slots then the spare, their count, the chunk's slot,
        whether the launch starts it.

        Which of these memories can be CUT at a token: the tail can — it
        is the last K - 1 rows of a stream, and the rows before a token
        are all its continuation needs — the recurrent state cannot (one
        summary of everything before). So ``C``, whose memory is a tail
        and NOTHING else, keeps the prefix cache, and ``M`` ``K`` ``S``
        refuse it.

        ``C``, a gated short convolution (LFM2): ``(L, a, the tail
        pool[, the snapshot plane], num_tokens, the state table, the
        chunk's page ids) -> (its output, the tail pool[, the plane])``.
        In-projection to three streams B | C | z (`lfm_in_proj`) -> u =
        B * z through `conv_tails` with NO activation (`lfm_conv`) ->
        the gate C and the out-projection (`lfm_out`). With the prefix
        cache on the block holds a second plane [num_pages, K - 1,
        hidden], the tails at every page's LAST row by page id, and the
        table one entry more: the chunk of a launch writes the plane at
        the pages it fills (`tail_snapshot`) and, where it is the first
        chunk of a sequence that ADOPTED a cached prefix, reads the last
        adopted page's entry where a fresh sequence reads zeros.

        ``S``, a Mamba-1 state-space mixer (a decay a (channel, state
        column); the slot's state [1, N, C], channels along the lanes):
        in-projection (`ssm1_in_proj`) -> `_conv_tails` (`ssm1_conv`)
        -> dt / B / C projections, every live decode slot ONE step in
        place (`ssm1_state_update`), the chunk's rows the selective
        scan from its slot's state (`ssm1_chunk_scan`; rows past the
        chunk's length carry dt 0, the identity), `ssm_state_put`
        (`ssm1_scan`) -> the gate and the out-projection (`ssm1_out`).
        Its scan output y (with the D term, before the gate) is what a
        ``G<j>`` block reads.

        ``M``, a Mamba-2 state-space mixer:
        in-projection (`ssm_in_proj`) -> the causal convolution, a decode
        row from its slot's tail, the chunk's rows from its slot's tail
        (zeros where the launch starts the sequence) and from each other,
        the tails of the last valid rows written back (`ssm_conv`) -> the
        state: every live decode slot ONE step of the recurrence, in place
        (`ssm_state_update`), the chunk's rows a scan in the config's
        chunks from its slot's state (`ssm_chunk_scan`; rows past the
        chunk's length carry dt 0, the identity), its last state put back
        in place (`ssm_state_put`) (`ssm_scan`) -> the gated group norm
        and the out-projection (`ssm_out`). A launch without a chunk skips
        the scan and moves no state for it.

        ``K``, a KDA linear-attention mixer: the same slot, tail and
        table, another update rule — projections (`kda_in_proj`) -> the
        q | k | v convolutions through `_conv_tails`, the heads' L2 norm
        and the gates (`kda_conv`) -> `kda_state_update` in place
        (`kda_state_update`) -> the chunk's `kda_chunk_scan` (rows past
        its length carry g 0 and beta 0, the identity) and
        `ssm_state_put` (`kda_chunk_scan`) -> each head's norm, its
        gate and the out-projection (`kda_out`)."""
        cfg, pattern = self._p["cfg"], self._p["pattern"]
        mu = self._p.get("mults")
        eps, K = cfg.layer_norm_epsilon, cfg.conv_kernel
        layout = self._state_layout
        B = self.max_slots
        T = B + C
        f32 = jnp.float32
        if mu:
            from ..models.falcon_h1 import mup_vector
        if "M" in pattern:
            Hm, P, G = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups
        if "K" in pattern:
            Hk, Dk = cfg.num_attention_heads, cfg.head_dim

        def with_spare(m):
            """A launch's operand m [T, ...] with a row B, the spare
            slot's: the chunk's first row, which no live slot reads,
            or — no chunk part — a row of zeros behind the decode
            rows."""
            if C:
                return m
            return jnp.pad(m, ((0, 1),) + ((0, 0),) * (m.ndim - 1))

        def conv_tails(u, t_pool, conv_w, conv_b, live, n_c, cslot, starts,
                       conv=ssm_conv, snap=None):
            """The causal convolution of the launch's rows u [T, W], a
            decode row from its slot's tail, the chunk's rows from its
            slot's tail (zeros at a start) and from each other; the
            last K - 1 rows before each sequence's next one are written
            back. -> (the convolved rows, the tail pool). `conv`: the
            convolution and its activation (`ssm_conv`: silu).

            `snap` = (the snapshot plane [num_pages, K - 1, W], the page
            whose entry a STARTING chunk continues or 0, the page ids of
            the chunk's pages [C / page_size]): the chunk starts on a
            page border (the engine refuses another chunk length), so
            page j of the chunk ends at the STATIC row page_size * (j +
            1) - 1 and the tails there are the page's last K - 1 rows of
            u. Pages past the chunk's length are the trash page or not
            yet full: what is written for them is garbage that nobody
            reads — the trie holds FULL prompt pages only, and a page id
            that is reused is overwritten, by the chunk that fills it,
            before it is inserted again. -> (..., the plane)."""
            # decode row s is slot s: its tail, then its own row
            tails = t_pool[:B]
            ext = jnp.concatenate([tails, u[:B, None]], 1)
            conv_d = jax.vmap(conv, (0, None, None))(
                ext, conv_w, conv_b)[:, 0]
            if not C:       # no chunk part: the decode rows' tails alone
                return (conv_d, t_pool.at[:B].set(
                    jnp.where(live[:, None, None], ext[:, 1:], tails))) \
                    + (() if snap is None else snap[:1])
            # the chunk: its slot's tail (zeros at a start), its rows
            tail_c = jnp.where(starts, 0, t_pool[cslot])
            if snap is not None:
                plane, adopted, pages = snap
                # ... or, behind an adopted prefix, the tails at its end
                tail_c = jnp.where(starts & (adopted > 0), plane[adopted],
                                   tail_c)
                with jax.named_scope("tail_snapshot"):
                    ps = self.page_size
                    plane = plane.at[pages].set(
                        u[B:].reshape(C // ps, ps, -1)[:, ps - (K - 1):])
            ext_c = jnp.concatenate([tail_c, u[B:]])
            conv_c = conv(ext_c, conv_w, conv_b)
            t_pool = t_pool.at[:B].set(
                jnp.where(live[:, None, None], ext[:, 1:], tails))
            t_pool = jax.lax.dynamic_update_slice(
                t_pool, jax.lax.dynamic_slice(
                    ext_c, (n_c, 0), (K - 1, ext_c.shape[1]))[None],
                (cslot, 0, 0))
            return (jnp.concatenate([conv_d, conv_c]), t_pool) \
                + (() if snap is None else (plane,))

        def chunk_state(z_pool, n_c, cslot, starts, scan, y_shape, scope):
            """The chunk's rows through ``scan(state it starts from)``
            -> (y, the state it leaves), where the launch has a chunk,
            and that state put back in its slot in place (under the
            caller's name `scope`)."""
            def run():
                return scan(jax.lax.cond(
                    starts, lambda: jnp.zeros(z_pool.shape[1:], f32),
                    lambda: jax.lax.dynamic_index_in_dim(
                        z_pool, cslot, 0, keepdims=False)))

            y_c, s1 = jax.lax.cond(
                n_c > 0, run,
                lambda: (jnp.zeros(y_shape, f32),
                         jnp.zeros(z_pool.shape[1:], f32)))
            return y_c, _once(
                ssm_state_put, scope, z_pool,
                jnp.stack([cslot, (n_c > 0).astype(jnp.int32)]), s1)

        def ssm(L, a, z_pool, t_pool, num_tokens, tab):
            """The state-space mixer of a [T, hidden] -> (its output [T,
            hidden], the state pool, the tail pool)."""
            dt_w = a.dtype
            slots, n_live = tab[:B], tab[B:B + 1]
            cslot, starts = tab[B + 1], tab[B + 2] > 0
            n_c = num_tokens[B]
            live = num_tokens[:B] > 0
            with jax.named_scope("ssm_in_proj"):
                if mu:
                    zxbcdt = ((a * mu["ssm_in"]) @ L["w_in"]) \
                        * mup_vector(cfg, dt_w)
                else:
                    zxbcdt = a @ L["w_in"]
                z, u, dt = ssm_split(zxbcdt, cfg)
            with jax.named_scope("ssm_conv"):
                u, t_pool = conv_tails(u, t_pool, L["conv_w"], L["conv_b"],
                                       live, n_c, cslot, starts)
            with jax.named_scope("ssm_scan"):
                x, dt, dA, bm, cm = ssm_operands(u, dt, L, cfg)
                xf = x.astype(f32)
                # the decode rows: one step each, the state in place
                # (row s of the operands is slot s; row B the spare's)
                rows = slice(0, B + 1)
                xs, dts, dAs = with_spare(xf), with_spare(dt), with_spare(dA)
                if layout == HEADS_MINOR:
                    # a group's B / C rows expanded to its heads, [N, H]
                    heads = lambda m: jnp.repeat(          # noqa: E731
                        with_spare(m)[rows], Hm // G, axis=1).swapaxes(1, 2)
                else:
                    # state-minor: a group's rows as they are, [G, N]
                    heads = lambda m: with_spare(m)[rows]  # noqa: E731
                y_d, z_pool = _once(
                    ssm_state_update, "ssm_scan", z_pool, slots, n_live,
                    (xs[rows] * dts[rows, :, None]).swapaxes(1, 2),
                    jnp.exp(dAs[rows])[:, None, :], heads(bm), heads(cm),
                    layout=layout)
                y = jnp.where(live[:, None, None],
                              y_d[:B].swapaxes(1, 2), 0)
                if C:
                    # the chunk: a scan from its slot's state, where
                    # there is one; rows past its length are the identity
                    valid = (jnp.arange(C) < n_c)[:, None]
                    dt_c = jnp.where(valid, dt[B:], 0)
                    y_c, z_pool = chunk_state(
                        z_pool, n_c, cslot, starts,
                        lambda s0: _once(
                            ssm_chunk_scan, "ssm_scan",
                            xf[B:] * dt_c[..., None],
                            jnp.where(valid, dA[B:], 0), bm[B:], cm[B:], s0,
                            chunk=cfg.chunk_size, layout=layout), (C, Hm, P),
                        "ssm_scan")
                    y = jnp.concatenate([y, y_c])
                y = y + L["D"].astype(f32)[None, :, None] * xf
            with jax.named_scope("ssm_out"):
                y = ssm_gated_norm(y.reshape(T, Hm * P), z, L["norm_g"], G,
                                   eps).astype(dt_w)
                y = y @ L["w_out"]
                if mu:
                    y = y * mu["ssm_out"]
                return y, z_pool, t_pool

        def ssm1(L, a, z_pool, t_pool, num_tokens, tab):
            """The Mamba-1 mixer of a [T, hidden] -> (its output [T,
            hidden], the state pool, the tail pool, its scan output y
            [T, d_inner] float32: with the D term, before the gate)."""
            slots, n_live = tab[:B], tab[B:B + 1]
            cslot, starts = tab[B + 1], tab[B + 2] > 0
            n_c = num_tokens[B]
            live = num_tokens[:B] > 0
            Ci = cfg.d_inner
            with jax.named_scope("ssm1_in_proj"):
                xz = a @ L["w_in"]
                u, z = xz[:, :Ci], xz[:, Ci:]
            with jax.named_scope("ssm1_conv"):
                u, t_pool = conv_tails(u, t_pool, L["conv_w"], L["conv_b"],
                                       live, n_c, cslot, starts)
            with jax.named_scope("ssm1_scan"):
                dt, bm, cm = ssm1_operands(u, L, cfg)
                uf = u.astype(f32)
                neg_a = -jnp.exp(L["a_log_t"].astype(f32))      # [N, C]
                # the decode rows: one step each, the state in place
                # (row s of the operands is slot s; row B the spare's)
                rows = slice(0, B + 1)
                y_d, z_pool = _once(
                    ssm1_state_update, "ssm1_scan", z_pool, slots, n_live,
                    with_spare(dt)[rows], with_spare(uf)[rows], neg_a,
                    with_spare(bm)[rows], with_spare(cm)[rows])
                y = jnp.where(live[:, None], y_d[:B], 0)
                if C:
                    # the chunk: the selective scan from its slot's
                    # state; rows past its length are the identity
                    valid = (jnp.arange(C) < n_c)[:, None]
                    y_c, z_pool = chunk_state(
                        z_pool, n_c, cslot, starts,
                        lambda s0: _once(
                            ssm1_chunk_scan, "ssm1_scan",
                            jnp.where(valid, dt[B:], 0), uf[B:], neg_a,
                            bm[B:], cm[B:], s0), (C, Ci), "ssm1_scan")
                    y = jnp.concatenate([y, y_c])
                y = y + L["D"].astype(f32)[None] * uf
            with jax.named_scope("ssm1_out"):
                out = (y * jax.nn.silu(z.astype(f32))).astype(a.dtype) \
                    @ L["w_out"]
                return out, z_pool, t_pool, y

        def kda(L, a, z_pool, t_pool, num_tokens, tab):
            """The KDA mixer of a [T, hidden] -> (its output [T, hidden],
            the state pool, the tail pool)."""
            slots, n_live = tab[:B], tab[B:B + 1]
            cslot, starts = tab[B + 1], tab[B + 2] > 0
            n_c = num_tokens[B]
            live = num_tokens[:B] > 0
            with jax.named_scope("kda_in_proj"):
                u = jnp.concatenate(
                    [a @ L["wq"], a @ L["wk"], a @ L["wv"]], -1)
                f, b, gate = a @ L["wf"], a @ L["wb"], a @ L["wgate"]
            with jax.named_scope("kda_conv"):
                u, t_pool = conv_tails(u, t_pool, L["conv_w"], None, live,
                                       n_c, cslot, starts)
                q, k, v, g, beta = kda_operands(u, f, b, L, cfg)
            with jax.named_scope("kda_state_update"):
                # the decode rows: one step each, the state in place
                # (row s of the operands is slot s; row B the spare's)
                rows = slice(0, B + 1)
                qs, ks, vs, gs, bs = (with_spare(m)
                                      for m in (q, k, v, g, beta))
                o, z_pool = _once(
                    kda_state_update, "kda_state_update", z_pool, slots,
                    n_live, qs[rows], ks[rows], vs[rows], gs[rows],
                    bs[rows, :, None])
                o = jnp.where(live[:, None, None], o[:B], 0)
            if C:
                with jax.named_scope("kda_chunk_scan"):
                    # the chunk: a scan from its slot's state, where
                    # there is one; rows past its length are the identity
                    valid = (jnp.arange(C) < n_c)[:, None]
                    o_c, z_pool = chunk_state(
                        z_pool, n_c, cslot, starts,
                        lambda s0: _once(
                            kda_chunk_scan, "kda_chunk_scan",
                            q[B:], k[B:], v[B:],
                            jnp.where(valid[..., None], g[B:], 0),
                            jnp.where(valid, beta[B:], 0), s0,
                            chunk=cfg.kda_sub_chunk), (C, Hk, Dk),
                        "kda_chunk_scan")
                    o = jnp.concatenate([o, o_c])
            with jax.named_scope("kda_out"):
                y = kda_gated_norm(o, gate, L["norm_g"],
                                   eps).astype(a.dtype)
                return y @ L["wo"], z_pool, t_pool

        def lfm(L, a, mem, num_tokens, tab, pages):
            """The gated short convolution of a [T, hidden]; ``mem`` is
            the block's pool entry, (the tail pool[, the snapshot
            plane]) -> (its output [T, hidden], the entry after)."""
            cslot, starts = tab[B + 1], tab[B + 2] > 0
            with jax.named_scope("lfm_in_proj"):
                gate_b, gate_c, z = jnp.split(a @ L["w_in"], 3, axis=-1)
                u = gate_b * z
            with jax.named_scope("lfm_conv"):
                u, *mem = conv_tails(
                    u, mem[0], L["conv_w"], None, num_tokens[:B] > 0,
                    num_tokens[B], cslot, starts,
                    conv=functools.partial(ssm_conv, act=None),
                    snap=(mem[1], tab[B + 3], pages) if mem[1:] else None)
            with jax.named_scope("lfm_out"):
                return (gate_c * u) @ L["w_out"], tuple(mem)

        return {"M": ssm, "K": kda, "S": ssm1, "C": lfm}

    def _chain_unified_body(self, C: int):
        """The step of every family whose layers are a list of BLOCKS
        (`_chain_of`: llama / MoE / Laguna, gpt, latent attention under
        either residual, the four hybrids): block l is `x + sum of
        mixers_l(norm(x))`, its ONE norm feeding the mixers its letters
        name (one a block for every family but Falcon-H1, whose ``[M*]``
        holds a state-space AND an attention mixer side by side), or `x
        + ffn_l(norm(x))`. The norm is RMSNorm or — two keys — LayerNorm
        with weight and bias (gpt, Phi-4-flash). The residual is a seam:
        the plain add, or `_HyperResidual`'s streams mixed around every
        block (``feed`` / ``leave``).

        A mixer OWNS its memory — the next entry of the family's page
        pools or state pools, in the order of the blocks — or owns NONE
        (``G<j>``, ``X<j>``): it reads what block j made for the SAME
        rows earlier in this launch, handed down the body as a value —
        block j's pages after its append, block j's scan output — and
        takes no pool entry.

        ``*``, grouped-query attention: `_gqa_mixer`, turned by the rows
        of the rope table the block names or — gpt, Nemotron-H and
        Phi-4-flash have no rotary embedding — by none, over the pages
        of the block's KIND: where the model has sliding-window layers
        (`attn_static`) `tables` and `tok_page` are pairs, and a window
        block reads the window kind's pages, table and append runs,
        whose pages the allocator releases as the window passes.
        ``X<j>``: the same mixer with a query and an output projection
        only, over block j's pages (`shared_attention`). ``L``, latent
        attention: `_latent_mixer`, over pages that hold latent rows.
        ``M`` ``K`` ``S``: `_state_mixers`. ``G<j>``: `silu(a W_a) * y`
        of block j's scan output y, then W_b (`gmu`).

        ``E``, a routed FFN (latent or not), and ``D``, a dense SwiGLU
        FFN: `_ffn_apply` (`routed_ffn`, `latent_proj`,
        `shared_expert`; `ffn`); ``B``: `_gelu_ffn`.

        A model's static multipliers (``mults``, Falcon-H1's fourteen)
        are applied where its equations put them, inside the scope of
        the operation they scale; without them no operation is added.

        Where a family has state pools `kv_lengths` is a pair (the
        attention blocks' lengths, the state table) and `pools` a dict
        (`_Chain.split` / `join`)."""
        d, cfg = self._chain, self._p["cfg"]
        eps, mu = d.eps, d.mults
        B, K = self.max_slots, self.spec_k
        R = self._slot_rows
        T = self._launch_rows(C)
        seq_start = _seq_starts(B, R, self._riders)
        run_table = self._run_table(seq_start)
        state = self._state_mixers(C) if self._ssm_layers else {}
        # the rows of the riding commits, where the launch has them
        ride = {"ride": (B * R, (B + self._riders) * R)} \
            if self._riders else {}
        f32 = jnp.float32

        def norm(x, scope, w, keys, once=True):
            fn = fused_layer_norm if len(keys) > 1 else fused_rms_norm
            gains = [w[k] for k in keys]
            return _once(fn, scope, x, *gains, eps=eps) if once \
                else fn(x, *gains, eps)

        def step(w, tok, pools, positions, num_tokens, kv_lengths,
                 tables, tok_page, tok_off):
            kv_pools, ssm_pools, kv_lengths, tab = d.split(pools, kv_lengths)
            kv_pools, ssm_pools = iter(kv_pools), iter(ssm_pools)
            with _scope("embed"):
                x = w["embed"][tok]
                if "pos" in w:      # learned positions (gpt)
                    x = x + w["pos"][positions]
                x = x[None]                              # [1, T, hidden]
                if mu:
                    x = x * mu["embedding"]
            # the rows' angles [T, D / 2], one (cos, sin) a rope table
            # the blocks name; without a table, the identity turn
            with _scope("embed") if d.ends_scoped else nullcontext():
                trig = {sfx: (w["cos" + sfx][positions],
                              w["sin" + sfx][positions])
                        for sfx in d.rope_tables}
                if d.no_turn:
                    trig[""] = _no_turn(T, d.no_turn[0], x.dtype,
                                        d.no_turn[1])
            # the append's runs, once a kind of cache (a model with
            # window layers: the full kind's, then the window's)
            if not isinstance(tables, tuple):
                tables, tok_page = (tables,), (tok_page,)
            with _scope("cache_write"):
                runs = [run_table(num_tokens, page, tok_off)
                        for page in tok_page]
            new_kv, new_ssm = [], []
            moe_stats = [] if d.moe_counts else None
            live = _owned_rows(T, seq_start, num_tokens) \
                if d.moe_counts or d.hyper else None
            # what a block that owns no memory reads: by the block that
            # made it, this launch
            pages_of, scan_of = {}, {}
            res = _HyperResidual(cfg, live) if d.hyper else _PLAIN
            x = res.enter(x)
            for i, blk in enumerate(d.blocks):  # all of `blk`: static
                L = w["layers"][blk.layer]
                a, keep = res.feed(x, L.get(blk.hc))
                a = norm(a, "ffn_norm" if blk.kind in "EDB"
                         else "attn_norm", L, blk.norm)
                if blk.kind in "EDB":
                    x = res.leave(x, _gelu_ffn(L, a) if blk.kind == "B"
                                  else _ffn_apply(L, a, blk.st, moe_stats,
                                                  live), keep)
                    continue
                if blk.kind[0] == "G":  # a gated unit over a scan output
                    with jax.named_scope("gmu"):
                        g = jax.nn.silu((a[0] @ L["w_a"]).astype(f32))
                        x = res.leave(
                            x, ((g * scan_of[int(blk.kind[1:])][0])
                                .astype(a.dtype) @ L["w_b"])[None], keep)
                    continue
                if blk.kind[0] == "X":  # attention over another's pages
                    y, _ = _gqa_mixer(
                        L, a, trig[blk.rope], pages_of[int(blk.kind[1:])],
                        seq_start, num_tokens, kv_lengths, tables[0], None,
                        **blk.attn)
                    x = res.leave(x, y, keep)
                    continue
                # the block's mixers, each on the one normed input; the
                # residual takes one after the other
                for kind in blk.kind:
                    if kind == "C":     # a tail and no state
                        y, mem = state[kind](
                            L, a[0], next(ssm_pools), num_tokens, tab,
                            tok_page[0][B * R:B * R + C:self.page_size])
                        new_ssm.append(mem)
                        y = y[None]
                    elif kind in "MKS":
                        y, z_pool, t_pool, *scan = state[kind](
                            L, a[0], *next(ssm_pools), num_tokens, tab)
                        new_ssm.append((z_pool, t_pool))
                        scan_of[i] = scan   # ([y] of an `S` mixer)
                        y = y[None]
                    elif kind == "L":
                        y, pool = _latent_mixer(
                            L, a, _halves_rope(*trig[blk.rope]),
                            next(kv_pools), seq_start, num_tokens,
                            kv_lengths, tables[0], runs[0], **blk.attn)
                        new_kv.append(pool)
                    else:
                        y, pool = _gqa_mixer(
                            L, a, trig[blk.rope], next(kv_pools), seq_start,
                            num_tokens, kv_lengths, tables[blk.pages],
                            runs[blk.pages], **blk.attn, **ride)
                        new_kv.append(pool)
                        pages_of[i] = pool
                    x = res.leave(x, y, keep)
            x = res.exit(x)
            with _scope("head"):
                x = norm(x, "head", w, d.head_norm, d.head_once)
                if d.block:
                    # every block row's logits, in float32 (the rule
                    # compares confidences); the chunk's rows have none:
                    # no token comes of a prompt's last chunk
                    logits = jnp.dot(
                        x[0, :B * R], w["head"] if w["head"] is not None
                        else w["embed"].T, preferred_element_type=f32)
                else:
                    logits = _head_logits(
                        w, _logit_rows(x, seq_start, num_tokens, K))
                    if mu:
                        logits = logits * mu["lm_head"]
                    tokens = _greedy(logits)
            if d.block:
                # the blocks after this pass: the next launch's input
                with _scope("unmask"):
                    tokens = _unmask(tok[:B * R].reshape(B, R), logits,
                                     tab, d.block[1]).reshape(-1)
            out = logits, d.join(new_kv, new_ssm), tokens
            # the counts taken on the device ride beside the logits as
            # ONE array, in the order of `_device_count_names`
            with _scope("head") if d.ends_scoped else nullcontext():
                counts = [_moe_step_counts(moe_stats)] if moe_stats else []
                if d.hyper:
                    counts.append(res.device_counts())
                if len(counts) > 1:
                    counts = [jnp.concatenate(counts)]
            return out + tuple(counts)

        return step
