"""paddle_tpu.observability.costmodel — analytical per-kernel cost
registry (ISSUE 11 tentpole).

One entry per authored kernel in ``ops/oracles.py`` (all 17): HBM bytes
read / written and FLOPs as closed-form functions of the launch shapes
and dtypes.  The byte formulas for the attention families mirror the
Pallas BlockSpec accounting exactly — fetch *runs* x block bytes, where
a block is re-fetched at every grid step whose index differs from the
previous step's (so flash K/V pay once per q-block, paged K/V once per
page per batch row) — and `tests/test_costmodel.py` asserts they equal
the sizes `analysis/kernelmodel.py` derives from the committed
grids/BlockSpecs.  Scalar-prefetch operands (lengths, page tables) are
EXCLUDED everywhere: they are KBs against MBs and live in SMEM.
Drift between this registry and the committed kernels is machine-
checked from both sides: paddlelint's PF406 (via
``analysis/vmemmodel.py``) re-derives every kernel's bytes from the
BlockSpecs and fails CI past ``COST_DRIFT_RTOL``, and
``tools/perf_gate.py --check`` applies the same tolerance to
observatory candidates — edit a kernel's tiling and the cost formula
here must move with it.

On top of the registry sit the composite budgets the rest of the repo
consumes so train and serve share one cost vocabulary:

  - `decode_step_budget` — the serving HBM roofline (weights + KV read
    per engine step, int4/int8 aware via ``weight_bytes`` /
    ``kv_dtype_bytes``; ``page_size=None`` reproduces the naive
    row-granular roofline SERVING_BENCH committed, an int gives the
    page-granular figure the engine actually transfers);
  - `decode_layer_kernels` — the per-kernel decomposition of one decode
    layer body (which `tools/observatory.py` renders as the roofline
    table and `tools/perf_gate.py` bands per kernel);
  - `pretrain_step_budget` / `train_mfu` — the 6N FLOPs ledger the
    trainer's MFU gauge is derived from (`trainer.py` falls back to
    `flops_per_sample(...)` when TrainingArguments doesn't pin one).

Pure python + math: importable from tools and tests without jax.
`tree_bytes` (the one helper that touches arrays) duck-types leaves.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Mapping, Optional

__all__ = [
    "CostEstimate", "KernelCost", "register_cost", "costs", "cost",
    "decode_step_budget", "decode_layer_kernels", "pretrain_step_budget",
    "flops_per_sample", "train_mfu", "roofline_tokens_per_s",
    "tree_bytes", "HBM_BW", "PEAK_FLOPS",
]

#: per-chip HBM bandwidth (bytes/s) — same table serving_bench publishes
HBM_BW: Dict[str, float] = {"v5e": 819e9, "v5p": 2765e9, "v4": 1228e9,
                            "v6e": 1640e9}

#: per-chip bf16 peak (FLOP/s) for MFU / roofline-knee math
PEAK_FLOPS: Dict[str, float] = {"v5e": 197e12, "v5p": 459e12,
                                "v4": 275e12, "v6e": 918e12}


@dataclasses.dataclass(frozen=True)
class CostEstimate:
    """Analytical cost of ONE launch: HBM bytes each way, FLOPs, and an
    optional named byte breakdown (weights / kv / activations / ...)."""

    bytes_read: int
    bytes_written: int
    flops: int
    breakdown: Optional[Mapping[str, int]] = None

    @property
    def hbm_bytes(self) -> int:
        return self.bytes_read + self.bytes_written

    @property
    def arithmetic_intensity(self) -> float:
        """FLOP per HBM byte — which side of the roofline knee."""
        return self.flops / max(self.hbm_bytes, 1)

    def theoretical_us(self, hbm_bw: float,
                       peak_flops: Optional[float] = None) -> float:
        """Roofline-optimal launch time: max of the bandwidth and the
        compute bound (compute bound skipped when peak_flops is None)."""
        t = self.hbm_bytes / hbm_bw
        if peak_flops:
            t = max(t, self.flops / peak_flops)
        return t * 1e6


@dataclasses.dataclass(frozen=True)
class KernelCost:
    name: str
    fn: Callable[..., CostEstimate]
    doc: str = ""


_COSTS: Dict[str, KernelCost] = {}


def register_cost(name: str, fn: Optional[Callable[..., CostEstimate]]
                  = None, doc: str = ""):
    """Register the cost function for kernel `name` (the ops/oracles.py
    name). Usable as a decorator; re-registration replaces (mirrors
    register_oracle)."""
    def _reg(f: Callable[..., CostEstimate]) -> Callable[..., CostEstimate]:
        _COSTS[name] = KernelCost(name=name, fn=f,
                                  doc=doc or (f.__doc__ or "").strip())
        return f
    return _reg(fn) if fn is not None else _reg


def costs() -> Mapping[str, KernelCost]:
    """Read-only view of the registry (name -> KernelCost)."""
    return dict(_COSTS)


def cost(name: str, **shapes: Any) -> CostEstimate:
    """Evaluate the registered cost of `name` at the given shapes."""
    try:
        entry = _COSTS[name]
    except KeyError:
        raise KeyError(
            f"no cost registered for kernel {name!r}; "
            f"known: {sorted(_COSTS)}") from None
    return entry.fn(**shapes)


def _ceil_div(a: int, b: int) -> int:
    return -(-int(a) // int(b))


# ---------------------------------------------------------------------------
# elementwise / fused-op kernels (ops/fused.py)
# ---------------------------------------------------------------------------

@register_cost("fused_rms_norm")
def _c_fused_rms_norm(*, T: int, H: int, dtype_bytes: int = 2
                      ) -> CostEstimate:
    """x [T, H] + weight [H] -> [T, H]; square/mean/rsqrt/scale."""
    return CostEstimate(bytes_read=(T * H + H) * dtype_bytes,
                        bytes_written=T * H * dtype_bytes,
                        flops=4 * T * H,
                        breakdown={"activations": 2 * T * H * dtype_bytes,
                                   "weights": H * dtype_bytes})


@register_cost("fused_layer_norm")
def _c_fused_layer_norm(*, T: int, H: int, dtype_bytes: int = 2
                        ) -> CostEstimate:
    """x [T, H] + weight/bias [H] -> [T, H]; mean/var/normalize/affine."""
    return CostEstimate(bytes_read=(T * H + 2 * H) * dtype_bytes,
                        bytes_written=T * H * dtype_bytes,
                        flops=6 * T * H,
                        breakdown={"activations": 2 * T * H * dtype_bytes,
                                   "weights": 2 * H * dtype_bytes})


@register_cost("fused_bias_residual_layer_norm")
def _c_fused_brln(*, T: int, H: int, dtype_bytes: int = 2) -> CostEstimate:
    """x + residual [T, H] + bias/weight/ln-bias [H] -> [T, H]."""
    return CostEstimate(bytes_read=(2 * T * H + 3 * H) * dtype_bytes,
                        bytes_written=T * H * dtype_bytes,
                        flops=8 * T * H,
                        breakdown={"activations": 3 * T * H * dtype_bytes,
                                   "weights": 3 * H * dtype_bytes})


@register_cost("fused_moe_dispatch_combine")
def _c_fused_moe_dc(*, T: int, K: int, E: int, C: int,
                    dtype_bytes: int = 4) -> CostEstimate:
    """keep [T,K,E] + oh_loc [T,K,C] + gv [T,K] -> two [T,E,C] scatter
    planes (dispatch one-hot and gate-weighted combine)."""
    read = T * (K * E + K * C + K) * dtype_bytes
    return CostEstimate(bytes_read=read,
                        bytes_written=2 * T * E * C * dtype_bytes,
                        flops=2 * T * K * C,
                        breakdown={"activations": read})


@register_cost("fused_rope")
def _c_fused_rope(*, B: int, S: int, H: int, D: int, Hk: int = 0,
                  dtype_bytes: int = 2) -> CostEstimate:
    """Rotary embedding over q [B,S,H,D] (+ optionally k with Hk heads);
    cos/sin ride once per position ([B,S,1,D/2] each)."""
    heads = H + Hk
    act = B * S * heads * D * dtype_bytes
    trig = B * S * D * dtype_bytes          # cos + sin, D/2 each
    return CostEstimate(bytes_read=act + trig, bytes_written=act,
                        flops=3 * B * S * heads * D,
                        breakdown={"activations": 2 * act + trig})


def _append_tile(tile: Optional[int], page_size: int,
                 dtype_bytes: int) -> int:
    """`ops.fused.append_tile`: a sublane tile of the dtype, or the
    whole page where a page is not whole tiles."""
    if tile is not None:
        return tile
    rows = 32 // dtype_bytes
    return rows if page_size % rows == 0 else page_size


@register_cost("fused_rope_append")
def _c_fused_rope_append(*, T: int, Hq: int, KV: int, D: int,
                         page_size: int, dtype_bytes: int = 2,
                         runs: Optional[int] = None,
                         tile: Optional[int] = None,
                         rope: bool = True) -> CostEstimate:
    """Rope(q, k) over all T rows (`fused_rope`'s dense row blocks),
    then the paged K/V append by RUNS, grid (runs,): the roped K rows
    and the V rows resident once, and ONE (KV, tile, D) block of each
    cache plane read and written a run. `tile` defaults to the sublane
    tile of the dtype (the whole page where a page is not whole tiles),
    `runs` to T: a decode row is a run of its own, a prefill chunk makes
    one for each `tile` rows. `rope=False` is the append launch alone
    (the site `analysis/vmemmodel.py` checks this entry against)."""
    tile = _append_tile(tile, page_size, dtype_bytes)
    runs = T if runs is None else runs
    rows = 2 * T * KV * D * dtype_bytes                # roped K, V
    tiles = 2 * runs * KV * tile * D * dtype_bytes     # k_pages + v_pages
    est = CostEstimate(bytes_read=rows + tiles, bytes_written=tiles,
                       flops=0, breakdown={"activations": rows,
                                           "kv": 2 * tiles})
    if not rope:
        return est
    front = cost("fused_rope", B=1, S=T, H=Hq, Hk=KV, D=D,
                 dtype_bytes=dtype_bytes)
    return CostEstimate(
        bytes_read=front.bytes_read + est.bytes_read,
        bytes_written=front.bytes_written + est.bytes_written,
        flops=front.flops,
        breakdown={"activations": front.hbm_bytes + rows,
                   "kv": 2 * tiles})


@register_cost("fused_append_rows")
def _c_fused_append_rows(*, T: int, KV: int, D: int, page_size: int,
                         dtype_bytes: int = 2,
                         runs: Optional[int] = None,
                         tile: Optional[int] = None) -> CostEstimate:
    """T rows [KV, D] into ONE paged pool by RUNS, grid (runs,): the
    launch's rows resident once, and ONE (KV, tile, D) block read and
    written a run (aliased in+out); `tile` and `runs` default as
    `fused_rope_append`'s do. The rows ride as float32 where KV is not
    whole sublane tiles of the pool's type (the latent row's one head).
    A K / V pair is `fused_rope_append` with ``rope=False``."""
    tile = _append_tile(tile, page_size, dtype_bytes)
    runs = T if runs is None else runs
    rows = T * KV * D * (4 if KV % (32 // dtype_bytes) else dtype_bytes)
    tiles = runs * KV * tile * D * dtype_bytes
    return CostEstimate(bytes_read=rows + tiles, bytes_written=tiles,
                        flops=0, breakdown={"kv": 2 * tiles,
                                            "activations": rows})


@register_cost("fused_chunk_pool")
def _c_fused_chunk_pool(*, P: int, KV: int, D: int, chunk: int,
                        dtype_bytes: int = 2) -> CostEstimate:
    """P chunks of `chunk` cached K and V rows [KV, D] pooled to one K
    and one V row each: scores 2D a row, two weighted sums 2D a row."""
    rows = 2 * P * KV * chunk * D * dtype_bytes
    out = 2 * P * KV * D * dtype_bytes
    return CostEstimate(bytes_read=rows + 2 * KV * D * 4,
                        bytes_written=out, flops=6 * P * KV * chunk * D,
                        breakdown={"kv": rows, "activations": out})


@register_cost("ssm_state_update")
def _c_ssm_state_update(*, live: int, P: int, N: int, H: int,
                        dtype_bytes: int = 2, layout: str = "heads_minor",
                        G: int = 1) -> CostEstimate:
    """One step of the Mamba-2 recurrence for `live` slots of a
    slot-indexed float32 state pool (aliased in+out), [slots, P, N, H]
    heads-minor or [slots, H, P, N] state-minor (`ops.pallas_ssm`): each
    live slot's state once in and once out, its row's dt x [P, H] and
    decay [H] in float32, B and C — expanded to heads, [N, H] in the
    serving type, heads-minor; a group's rows [G, N] float32,
    state-minor; y [P, H] out. An idle slot is neither read nor written.
    5 FLOPs an element of the state (decay, the outer product's
    multiply-add, the read-out's)."""
    state = live * P * N * H * 4
    bc = 2 * G * N * 4 if layout == "state_minor" \
        else 2 * N * H * dtype_bytes
    rows_in = live * ((P * H + H) * 4 + bc)
    rows_out = live * P * H * 4
    return CostEstimate(bytes_read=state + rows_in,
                        bytes_written=state + rows_out,
                        flops=5 * live * P * N * H,
                        breakdown={"state": 2 * state,
                                   "activations": rows_in + rows_out})


@register_cost("ssm1_state_update")
def _c_ssm1_state_update(*, live: int, C: int, N: int = 16) -> CostEstimate:
    """One step of the Mamba-1 recurrence (a decay a (channel, state
    column)) for `live` slots of the float32 pool [slots, 1, N, C]
    (aliased in+out; `ops.pallas_ssm`): each live slot's state once in
    and once out, its row's dt and x [C] and its B and C rows [N] in
    float32, y [C] out; A [N, C] once a launch. 7 FLOPs an element of the
    state (dt A, the decay's multiply, dt x B, the add, the read-out's
    multiply-add) beside its exponential."""
    state = live * N * C * 4
    rows_in = live * (2 * C + 2 * N) * 4 + (N * C * 4 if live else 0)
    rows_out = live * C * 4
    return CostEstimate(bytes_read=state + rows_in,
                        bytes_written=state + rows_out,
                        flops=7 * live * N * C,
                        breakdown={"state": 2 * state,
                                   "activations": rows_in + rows_out})


@register_cost("ssm1_chunk_scan")
def _c_ssm1_chunk_scan(*, rows: int, C: int, N: int = 16,
                       CB: int = 512) -> CostEstimate:
    """The Mamba-1 selective scan over a chunk of `rows` rows of ONE
    sequence, in channel blocks of `CB` lanes: the state [N, C] once in
    and once out, a row's dt and x in and y out [C] in float32, its B
    and C rows [N] once A CHANNEL BLOCK (every block walks all the
    rows), A once. 7 FLOPs an element of the state a row, elementwise
    and sequential in the rows: vector work, which no matrix peak
    bounds."""
    state = N * C * 4
    rows_in = rows * (2 * C + 2 * N * max(C // CB, 1)) * 4 + state
    return CostEstimate(bytes_read=state + rows_in,
                        bytes_written=state + rows * C * 4,
                        flops=7 * rows * N * C,
                        breakdown={"state": 2 * state,
                                   "activations": rows_in + rows * C * 4})


def shared_pool_read_bytes(*, pages: int, page_bytes: int,
                           readers: int) -> int:
    """HBM bytes the launches of ONE step read of a page pool that
    `readers` blocks attend over (the block that owns it and the blocks
    that borrow it: a cross-decoder's layers over one layer's keys and
    values): every reader fetches every visited page — one pool stored,
    `readers` times read."""
    return pages * page_bytes * readers


@register_cost("ssm_state_put")
def _c_ssm_state_put(*, P: int, N: int, H: int) -> CostEstimate:
    """One slot [P, N, H] (or state-minor [H, P, N]: the same bytes)
    float32 of the state pool replaced in place:
    the new state read, the slot written (its old content rides in with
    the aliased block and is dropped)."""
    state = P * N * H * 4
    return CostEstimate(bytes_read=2 * state, bytes_written=state, flops=0,
                        breakdown={"state": 3 * state})


@register_cost("kda_state_update")
def _c_kda_state_update(*, live: int, H: int, K: int, V: int) -> CostEstimate:
    """One step of the gated delta rule (KDA) for `live` slots of a
    slot-indexed state pool [slots, H, K, V] float32 (aliased in+out):
    each live slot's state once in and once out, its row's q, k and log
    decay [H, K], v [H, V] and beta [H] in float32; o [H, V] out. An idle
    slot is neither read nor written. 7 FLOPs an element of the state:
    the decay, S'^T k and S'^T q (a multiply-add each), the rank-one
    write's multiply-add."""
    state = live * H * K * V * 4
    rows_in = live * H * (3 * K + V + 1) * 4
    rows_out = live * H * V * 4
    return CostEstimate(bytes_read=state + rows_in,
                        bytes_written=state + rows_out,
                        flops=7 * live * H * K * V,
                        breakdown={"state": 2 * state,
                                   "activations": rows_in + rows_out})


@register_cost("mhc_pre")
def _c_mhc_pre(*, T: int, n: int, C: int, dtype_bytes: int = 2
               ) -> CostEstimate:
    """What a sublayer reads of a residual of `n` streams (hyper-
    connections, `ops.pallas_mhc.mhc_pre`): the stream [T, n C] ONCE in,
    the turned mixing weights [32, n C] and their [32, 128] float32
    scale / bias register once a launch; the sublayer's input [T, C] and the
    coefficients [T, 128] float32 out. FLOPs a row: the product with
    the weights (2 x n C x (n^2 + 2 n)), the sum of squares and the
    weighted sum of the streams (2 x n C each). These are the bytes the
    BlockSpecs move, as every entry's; ``breakdown["stream"]`` is kept
    apart because WHERE the stream lies is the compiler's choice: a
    launch whose stream fits on-chip memory (384 rows x 28,672 B on a
    v5e: `S(1)` in the compiled step) does not cross HBM with it, and
    `benchmarks/lib/costs_xing.py` counts the same bytes and leaves
    exactly these out of the step's HBM roofline."""
    stream = T * n * C * dtype_bytes
    weights = 32 * n * C * dtype_bytes + 32 * 128 * 4
    out = T * C * dtype_bytes + T * 128 * 4
    return CostEstimate(bytes_read=stream + weights, bytes_written=out,
                        flops=T * (2 * n * C * (n * n + 2 * n) + 4 * n * C),
                        breakdown={"stream": stream, "weights": weights,
                                   "activations": out})


@register_cost("mhc_post")
def _c_mhc_post(*, T: int, n: int, C: int, dtype_bytes: int = 2
                ) -> CostEstimate:
    """The stream after a sublayer (`ops.pallas_mhc.mhc_post`): the
    stream [T, n C] once in and once out (aliased), the sublayer's
    output [T, C] and the coefficients [T, 128] float32 in. FLOPs a
    row: n^2 + n multiply-adds a column. ``breakdown["stream"]`` as
    `mhc_pre`'s: not HBM bytes where the launch's stream stays on the
    chip."""
    stream = T * n * C * dtype_bytes
    rows_in = T * C * dtype_bytes + T * 128 * 4
    return CostEstimate(bytes_read=stream + rows_in, bytes_written=stream,
                        flops=2 * T * (n * n + n) * C,
                        breakdown={"stream": 2 * stream,
                                   "activations": rows_in})


def kda_chunk_scan_cost(*, rows: int, sub: int, H: int, K: int,
                        V: int) -> CostEstimate:
    """(Not in the kernel registry: the scan is plain XLA, no BlockSpec
    to check it against.) `rows` rows (whole sub-chunks of `sub`) of ONE sequence through
    the gated delta rule in the WY form, from and to a state [H, K, V]
    float32: what the ALGORITHM needs. Bytes: the state once in and once
    out, the rows' q, k, g [H, K], v [H, V], beta [H] in, o [H, V] out.
    FLOPs a sub-chunk a head: the two decayed Gram matrices (3 x sub^2 x
    K each: the decay's product, the multiply-add), the unit-lower
    inverse (2 x sub^3 / 3), and the matmuls with the state and the
    pseudo-values (q S, k S, T r, B u: 2 x sub x K x V twice, 2 x sub^2
    x V twice; the state's update 2 x sub x K x V)."""
    n = rows // sub
    state = H * K * V * 4
    rows_in = rows * H * (3 * K + V + 1) * 4
    rows_out = rows * H * V * 4
    flops = n * H * (6 * sub * sub * K + 2 * sub ** 3 // 3
                     + 6 * sub * K * V + 4 * sub * sub * V)
    return CostEstimate(bytes_read=state + rows_in,
                        bytes_written=state + rows_out, flops=flops,
                        breakdown={"state": 2 * state,
                                   "activations": rows_in + rows_out})


@register_cost("swiglu")
def _c_swiglu(*, T: int, H: int, dtype_bytes: int = 2) -> CostEstimate:
    """gate/up [T, H] -> silu(gate) * up [T, H]."""
    return CostEstimate(bytes_read=2 * T * H * dtype_bytes,
                        bytes_written=T * H * dtype_bytes,
                        flops=6 * T * H,
                        breakdown={"activations": 3 * T * H * dtype_bytes})


# ---------------------------------------------------------------------------
# attention kernels — byte formulas mirror the BlockSpec fetch accounting
# ---------------------------------------------------------------------------

def _flash_blocks(Sq: int, Sk: int, block_q: int, block_k: int):
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    return bq, bk, Sq // bq, Sk // bk


def _flash_bytes(B: int, H: int, Sq: int, Sk: int, D: int, bq: int,
                 bk: int, nq: int, nk: int, dtype_bytes: int,
                 seg_bytes: int):
    # fetch runs (see module docstring): q once; k/v once per q-block;
    # the int32 segment-id rows ride the same grids
    q = B * H * nq * bq * D * dtype_bytes
    kv = 2 * B * H * nq * nk * bk * D * dtype_bytes
    seg = (B * H * nq * bq + B * H * nq * nk * bk) * seg_bytes
    out = B * H * Sq * D * dtype_bytes
    lse = B * H * Sq * 4                      # f32 row stats
    return q, kv, seg, out, lse


@register_cost("flash_sdpa")
def _c_flash_sdpa(*, B: int, H: int, Sq: int, Sk: int, D: int,
                  block_q: int = 512, block_k: int = 512,
                  causal: bool = False, dtype_bytes: int = 2,
                  seg_bytes: int = 4) -> CostEstimate:
    """Tiled online-softmax attention, fwd grid (B, H, visited pairs — a
    query block's key blocks in a row): q read once, K/V re-fetched per
    q-block (the flash HBM contract). `causal` halves the FLOPs; the
    bytes stay the rectangle's (flashmask's sweep, which shares this
    form, still walks it), an upper bound for flash's visit table."""
    bq, bk, nq, nk = _flash_blocks(Sq, Sk, block_q, block_k)
    q, kv, seg, out, lse = _flash_bytes(B, H, Sq, Sk, D, bq, bk, nq, nk,
                                        dtype_bytes, seg_bytes)
    flops = 4 * B * H * Sq * Sk * D
    if causal:
        flops //= 2
    return CostEstimate(bytes_read=q + kv + seg, bytes_written=out + lse,
                        flops=flops + 6 * B * H * Sq * Sk,
                        breakdown={"activations": q + kv + out,
                                   "stats": seg + lse})


@register_cost("flashmask_sdpa")
def _c_flashmask_sdpa(*, B: int, H: int, Sq: int, Sk: int, D: int,
                      block_q: int = 512, block_k: int = 512,
                      causal: bool = False, dtype_bytes: int = 2,
                      seg_bytes: int = 4,
                      mask_rows: int = 2) -> CostEstimate:
    """flash_sdpa + the column-sparse startend row-index mask
    (`mask_rows` int32 rows of length Sk, re-fetched per q-block)."""
    base = _c_flash_sdpa(B=B, H=H, Sq=Sq, Sk=Sk, D=D, block_q=block_q,
                         block_k=block_k, causal=causal,
                         dtype_bytes=dtype_bytes, seg_bytes=seg_bytes)
    bq, bk, nq, nk = _flash_blocks(Sq, Sk, block_q, block_k)
    mask = B * mask_rows * nq * nk * bk * 4
    bd = dict(base.breakdown or {})
    bd["stats"] = bd.get("stats", 0) + mask
    return CostEstimate(bytes_read=base.bytes_read + mask,
                        bytes_written=base.bytes_written,
                        flops=base.flops, breakdown=bd)


def _paged_bytes(B: int, H: int, KV: int, D: int, pages: int,
                 page_size: int, dtype_bytes: int):
    rep = H // KV
    q = B * KV * rep * D * dtype_bytes       # one (1,1,rep,D) block per (b,h)
    kv = 2 * B * KV * pages * page_size * D * dtype_bytes
    out = B * KV * rep * D * dtype_bytes
    return q, kv, out


def _paged_cost(B: int, H: int, KV: int, D: int, context: int,
                page_size: int, pages_per_seq: Optional[int],
                dtype_bytes: int) -> CostEstimate:
    pages = (pages_per_seq if pages_per_seq is not None
             else _ceil_div(context, page_size))
    q, kv, out = _paged_bytes(B, H, KV, D, pages, page_size, dtype_bytes)
    return CostEstimate(bytes_read=q + kv, bytes_written=out,
                        flops=4 * B * H * context * D
                        + 6 * B * H * context,
                        breakdown={"kv": kv, "activations": q + out})


@register_cost("paged_decode_attention_v2")
def _c_paged(*, B: int, H: int, KV: int, D: int, context: int,
                page_size: int, pages_per_seq: Optional[int] = None,
                dtype_bytes: int = 2) -> CostEstimate:
    """Paged decode: K/V stay in HBM and page groups are double-buffered
    by manual DMA; every page of the table crosses once per (batch row,
    kv head) — the whole allocated table unless pages_per_seq narrows
    it."""
    return _paged_cost(B, H, KV, D, context, page_size, pages_per_seq,
                       dtype_bytes)


def _ragged_tile_tokens(T: int, rep: int, dtype_bytes: int) -> int:
    """ops/pallas_ragged.ragged_tile_tokens, restated (this module is
    loaded without jax; tests/test_costmodel.py holds the two equal)."""
    pack = 32 // dtype_bytes
    unit = pack // math.gcd(rep, pack)
    tq = max(unit, 128 // rep // unit * unit)
    return min(tq, _ceil_div(T, unit) * unit)


def _ragged_tile_block(KV: int, T: int, rep: int, tq: int) -> int:
    """ops/pallas_ragged.ragged_tile_block where the cell's VMEM does
    not bind (tests/test_costmodel.py holds the two equal at the serving
    cells' shapes): a visit that serves ONE KV head serves the largest
    power of two of query tiles up to 8 that the launch has; a block of
    KV heads, one tile."""
    tb, tiles = 1, _ceil_div(T, tq)
    while KV == 1 and 2 * tb <= min(8, tiles):
        tb *= 2
    return tb


@register_cost("ragged_paged_attention")
def _c_ragged(*, T: int, H: int, KV: int, D: int, S: int,
              pages_per_seq: int, page_size: int,
              dtype_bytes: int = 2,
              window: Optional[int] = None) -> CostEstimate:
    """Ragged mixed prefill+decode, grid (KV / hb, cells of tb tiles of
    TQ tokens): each cell reads the [tb*TQ*rep, D] query rows of each
    of its hb KV heads and writes their output rows (the rows that pad
    T up to whole cells are not counted); the pools stay in HBM and a
    cell DMAs, for every sequence with rows in it, the pages up to the
    cell's causal limit, each page for its hb heads and tb tiles at
    once. The head block (`ops/pallas_ragged.ragged_head_block`) changes
    how many DMAs bring the bytes, not the bytes; the tile block
    (`ragged_tile_block`: several tiles a cell where there is ONE KV
    head) changes the bytes: a sequence's pages cross once for each
    CELL its rows span. Stated for the heaviest launch of these shapes:
    every table full and the T rows spread evenly over the S sequences
    (once for a decode batch, T == S). With a sliding `window` a cell
    walks only the pages between the oldest key its first row sees and
    its last row: at most those spanned by `window - 1` old positions
    and the cell's own, on any page grid."""
    rep = H // KV
    tq = _ragged_tile_tokens(T, rep, dtype_bytes)
    tq *= _ragged_tile_block(KV, T, rep, tq)
    per_seq = _ceil_div(T, S)
    spans = _ceil_div(per_seq, tq)
    pages, ctx = pages_per_seq, pages_per_seq * page_size
    if window is not None:
        pages = min(pages, _ceil_div(window - 1 + min(tq, per_seq),
                                     page_size) + 1)
        ctx = min(ctx, window)
    q = KV * T * rep * D * dtype_bytes
    kv = 2 * KV * S * spans * pages * page_size * D * dtype_bytes
    out = KV * T * rep * D * dtype_bytes
    return CostEstimate(bytes_read=q + kv, bytes_written=out,
                        flops=4 * T * H * ctx * D + 6 * T * H * ctx,
                        breakdown={"kv": kv, "activations": q + out})


@register_cost("mla_decode_attention")
def _c_mla(*, B: int, nh: int, r: int, dr: int, context: int,
           block_t: int = 128, dtype_bytes: int = 2) -> CostEstimate:
    """Absorbed latent-KV decode, grid (B, nj): q_eff [1,nh,r] + q_pe
    [1,nh,dr] resident, latent/rope cache tiles [block_t, r|dr] swept;
    output is the [1,nh,r] latent-space read-out. The single latent
    cache read IS the point — kv bytes = context*(r+dr), not 2*ctx*KV*D."""
    nj = _ceil_div(context, block_t)
    q = B * nh * (r + dr) * dtype_bytes
    kv = B * nj * block_t * (r + dr) * dtype_bytes
    out = B * nh * r * dtype_bytes
    return CostEstimate(bytes_read=q + kv, bytes_written=out,
                        flops=2 * B * nh * context * (r + dr)
                        + 2 * B * nh * context * r + 6 * B * nh * context,
                        breakdown={"kv": kv, "activations": q + out})


# ---------------------------------------------------------------------------
# matmul-family kernels
# ---------------------------------------------------------------------------

@register_cost("gmm")
def _c_gmm(*, M: int, K: int, N: int, G: int, block_m: int = 128,
           block_n: int = 128, dtype_bytes: int = 2) -> CostEstimate:
    """Grouped GEMM lhs [M,K] x rhs [G,K,N]: useful traffic — every
    expert's weight slab crosses once per n-block sweep, lhs rows once
    per n-block (pl.when elides the non-overlapping group blocks, so
    this is the dense-equivalent lower bound, not grid x block)."""
    nn = max(N // min(block_n, N), 1)
    lhs = M * K * nn * dtype_bytes
    rhs = G * K * N * dtype_bytes
    out = M * N * dtype_bytes
    return CostEstimate(bytes_read=lhs + rhs, bytes_written=out,
                        flops=2 * M * K * N,
                        breakdown={"weights": rhs,
                                   "activations": lhs + out})


@register_cost("int4_dequantize")
def _c_int4_dequantize(*, K: int, N: int) -> CostEstimate:
    """Packed int4 [K/2, N] + scale [N] -> f32 [K, N] in VMEM."""
    read = (K // 2) * N + N * 4
    return CostEstimate(bytes_read=read, bytes_written=K * N * 4,
                        flops=2 * K * N,
                        breakdown={"weights": read})


@register_cost("weight_only_linear")
def _c_weight_only_linear(*, M: int, K: int, N: int,
                          algo: str = "weight_only_int8",
                          dtype_bytes: int = 2) -> CostEstimate:
    """x [M,K] @ dequant(qw) [K,N]: the weight read stays quantized
    (int8: K*N bytes, int4: K*N/2) — the bandwidth win of the family."""
    if algo == "weight_only_int8":
        w = K * N
    elif algo == "weight_only_int4":
        w = (K // 2) * N
    else:
        raise ValueError(f"unknown algo: {algo}")
    w += N * 4                                 # per-channel f32 scales
    x = M * K * dtype_bytes
    out = M * N * dtype_bytes
    return CostEstimate(bytes_read=x + w, bytes_written=out,
                        flops=2 * M * K * N + 2 * K * N,
                        breakdown={"weights": w, "activations": x + out})


def _quant_payload(K: int, N: int, algo: Optional[str],
                   dtype_bytes: int) -> int:
    """HBM bytes of one [K, N] weight slab in its deploy layout: fp
    (dtype_bytes wide), int8 (1 byte) or packed int4 (half a byte —
    two nibbles share each stored byte)."""
    if algo is None:
        return K * N * dtype_bytes
    if algo == "weight_only_int8":
        return K * N
    if algo == "weight_only_int4":
        return (K // 2) * N
    raise ValueError(f"unknown algo: {algo}")


@register_cost("fused_oproj_norm")
def _c_fused_oproj_norm(*, T: int, Ko: int, H: int,
                        algo: Optional[str] = None,
                        dtype_bytes: int = 2) -> CostEstimate:
    """Mega-kernel 1 (ops/pallas_megadecode.py): o-proj + bias +
    residual add + rms/layer norm in one launch.  Reads the attention
    output [T, Ko], the residual [T, H], the weight slab in its deploy
    layout (+ f32 scale row) and the bias/norm rows; writes BOTH the
    new residual stream and the normed FFN input — the four
    intermediates of the unfused chain never cross HBM."""
    db = dtype_bytes
    w = _quant_payload(Ko, H, algo, db) + H * 4      # slab + f32 scale
    x = T * Ko * db + T * H * db                     # o + residual in
    rows = 3 * H * db                                # bias + nw + nb
    out = 2 * T * H * db                             # x_new + h
    return CostEstimate(
        bytes_read=x + w + rows, bytes_written=out,
        flops=2 * T * Ko * H + 8 * T * H,
        breakdown={"weights": w, "activations": x + out,
                   "rows": rows})


@register_cost("fused_ffn")
def _c_fused_ffn(*, T: int, H: int, I: int, algo: Optional[str] = None,
                 act: str = "swiglu",
                 dtype_bytes: int = 2) -> CostEstimate:
    """Mega-kernel 2 (ops/pallas_megadecode.py): gate/up matmul +
    activation (swiglu or gelu) + down-proj + residual add.  The
    [T, I] activation lives only in f32 VMEM scratch; gelu rides a
    sublane-minimal 8-row dummy up slab (launch arity stays fixed)."""
    db = dtype_bytes
    wg = _quant_payload(H, I, algo, db) + I * 4
    if act == "swiglu":
        wu = _quant_payload(H, I, algo, db) + I * 4
    else:
        wu = 8 * I * db + I * 4                      # the gelu dummy
    wd = _quant_payload(I, H, algo, db) + H * 4
    x = 2 * T * H * db                               # h + residual in
    rows = I * db + H * db                           # b1 + b2
    out = T * H * db
    n_mats = 3 if act == "swiglu" else 2
    return CostEstimate(
        bytes_read=x + wg + wu + wd + rows, bytes_written=out,
        flops=2 * T * H * I * (n_mats - 1) + 2 * T * I * H
        + 6 * T * I,
        breakdown={"weights": wg + wu + wd, "activations": x + out,
                   "rows": rows})


@register_cost("fused_qkv_rope_append")
def _c_fused_qkv_rope_append(*, T: int, H: int, Hq: int, KV: int = 0,
                             D: int = 0, page_size: int,
                             algo: Optional[str] = None,
                             dtype_bytes: int = 2, nope_dim: int = 0,
                             rope_dim: int = 0, lora_rank: int = 0
                             ) -> CostEstimate:
    """Front-half mega-kernel (ops/pallas_megafront.py): qkv projection
    (in-kernel dequant) + rope + paged K/V row scatter in one launch,
    grid (T,).  Reads the normed hidden rows, the concatenated qkv slab
    in its deploy layout (+ f32 scale row + bias row), the trig rows
    and the aliased page blocks; writes q at the attention consumer's
    one-token granularity plus the page blocks.  ``lora_rank > 0``
    models the MLA layout: the slab is [q | kv_a], the bias row becomes
    the latent-norm weight, and one [lora_rank + rope_dim] pool row
    lands per token."""
    db = dtype_bytes
    if lora_rank:
        dh = nope_dim + rope_dim
        nq = Hq * dh
        N = nq + lora_rank + rope_dim
        rows = lora_rank * db                # latent rms-norm weight
        trig = T * rope_dim * db
        pages = T * page_size * (lora_rank + rope_dim) * db
        out_q = T * nq * db
        flops = (2 * T * H * N + 3 * T * Hq * rope_dim
                 + 3 * T * rope_dim + 8 * T * lora_rank)
    else:
        N = (Hq + 2 * KV) * D
        rows = N * db                        # bias row
        trig = T * D * db
        pages = 2 * T * KV * page_size * D * db   # k_pages + v_pages
        out_q = T * Hq * D * db
        flops = 2 * T * H * N + 3 * T * (Hq + KV) * D
    w = _quant_payload(H, N, algo, db) + N * 4    # slab + f32 scale
    x = T * H * db
    return CostEstimate(
        bytes_read=x + w + rows + trig + pages,
        bytes_written=out_q + pages, flops=flops,
        breakdown={"weights": w,
                   "activations": x + rows + trig + out_q,
                   "kv": 2 * pages})


# ---------------------------------------------------------------------------
# composite budgets — the shared cost vocabulary
# ---------------------------------------------------------------------------

def ssm_state_bytes_per_seq_layer(*, heads: int, head_dim: int,
                                  state_size: int, conv_dim: int,
                                  conv_kernel: int,
                                  state_dtype_bytes: int = 4,
                                  conv_dtype_bytes: int = 2) -> int:
    """HBM bytes a SEQUENCE holds in one state-space (Mamba-2) layer,
    whatever its length: the recurrent state heads x head_dim x
    state_size and the convolution's tail, the last conv_kernel - 1
    rows of its input."""
    return (heads * head_dim * state_size * state_dtype_bytes
            + (conv_kernel - 1) * conv_dim * conv_dtype_bytes)


def ssm_state_stored_bytes(*, heads: int, head_dim: int, state_size: int,
                           layout: str = "heads_minor",
                           dtype_bytes: int = 4, lanes: int = 128) -> int:
    """HBM bytes ONE (slot, layer)'s recurrent state takes on a TPU as
    the pool STORES it: the pool's minor dimension — the heads
    (heads-minor [P, N, H]) or the state's columns (state-minor [H, P,
    N]) — lies along the lanes and is stored in whole rows of `lanes`.
    32 heads x 128 over a state of 256: 16,777,216 heads-minor (32 of
    128 lanes used), 4,194,304 state-minor; 128 heads x 64 over 128:
    4,194,304 either way. `ops.pallas_ssm.state_layout` picks by this."""
    minor, rest = (state_size, heads * head_dim) \
        if layout == "state_minor" else (heads, head_dim * state_size)
    return -(-minor // lanes) * lanes * rest * dtype_bytes


def kda_state_bytes_per_seq_layer(*, heads: int, head_dim: int,
                                  conv_kernel: int,
                                  state_dtype_bytes: int = 4,
                                  conv_dtype_bytes: int = 2) -> int:
    """HBM bytes a SEQUENCE holds in one KDA (gated delta rule) layer,
    whatever its length: the recurrent state heads x head_dim x head_dim
    and the tails of the q, k and v convolutions, the last conv_kernel -
    1 rows of each one's input."""
    return (heads * head_dim * head_dim * state_dtype_bytes
            + (conv_kernel - 1) * 3 * heads * head_dim * conv_dtype_bytes)


def kv_bytes_per_token_layer(family: str, *, kv_heads: int = 0,
                             head_dim: int = 0, kv_latent_dim: int = 0,
                             kv_dtype_bytes: int = 2,
                             passes: int = 1) -> int:
    """HBM bytes of cache READ per context token per layer at decode:
    K+V rows for the attention families, the single [latent|rope] row
    for mla (read once — the absorbed decode's whole advantage).
    ``passes``: a looped decoder applies a layer that many times a
    token and every pass keeps, and reads, its own rows."""
    if family == "mla":
        if not kv_latent_dim:
            raise ValueError("mla needs kv_latent_dim "
                             "(kv_lora_rank + qk_rope_head_dim)")
        return passes * kv_latent_dim * kv_dtype_bytes
    if not (kv_heads and head_dim):
        raise ValueError(f"{family} needs kv_heads and head_dim")
    return passes * 2 * kv_heads * head_dim * kv_dtype_bytes


def decode_step_budget(family: str = "llama", *, batch: int,
                       context: float, layers: int, weight_bytes: int,
                       kv_heads: int = 0, head_dim: int = 0,
                       kv_latent_dim: int = 0, kv_dtype_bytes: int = 2,
                       page_size: Optional[int] = None,
                       spec_rows: int = 1,
                       passes: int = 1) -> Dict[str, Any]:
    """HBM budget of ONE decode step (every weight byte + every live
    cache byte crosses once): the serving roofline's denominator.
    A looped decoder (``passes`` > 1) hands in as ``weight_bytes`` what
    a step READS: its layers once a pass.

    ``page_size=None`` counts cache rows exactly (the naive roofline
    SERVING_BENCH committed); an int rounds each sequence up to whole
    pages (what the paged kernels actually transfer).  ``spec_rows`` > 1
    scales the attention read for speculative-decode verify rows.
    """
    per_tok = kv_bytes_per_token_layer(
        family, kv_heads=kv_heads, head_dim=head_dim,
        kv_latent_dim=kv_latent_dim, kv_dtype_bytes=kv_dtype_bytes,
        passes=passes)
    if page_size is None:
        kv_seq = per_tok * float(context) * layers
    else:
        kv_seq = per_tok * _ceil_div(int(math.ceil(context)),
                                     page_size) * page_size * layers
    kv_step = batch * kv_seq * max(spec_rows, 1)
    total = weight_bytes + kv_step
    return {"family": family, "batch": batch, "context": float(context),
            "weight_bytes": int(weight_bytes),
            "kv_bytes_per_seq": kv_seq,
            "kv_bytes": kv_step,
            "bytes_per_step": total,
            "bytes_per_token": total / max(batch, 1),
            "kv_bytes_per_token_layer": per_tok}


def roofline_tokens_per_s(budget: Mapping[str, Any],
                          hbm_bw: float = HBM_BW["v5e"]) -> float:
    """Bandwidth-bound decode throughput for a `decode_step_budget`:
    batch tokens emerge per step, one step moves bytes_per_step."""
    return budget["batch"] * hbm_bw / budget["bytes_per_step"]


def decode_layer_kernels(family: str = "llama", *, batch: int,
                         context: int, hidden: int, heads: int,
                         kv_heads: int, head_dim: int,
                         intermediate: int, page_size: int,
                         kv_dtype_bytes: int = 2,
                         weight_bytes_per_layer: int = 0,
                         quant_algo: Optional[str] = None,
                         megadecode: bool = True,
                         megafront: bool = True) -> Dict[str, Any]:
    """Per-kernel decomposition of one decode layer body:
    {kernel: (launches_per_layer, CostEstimate at this shape)}.

    ``megadecode=True`` (NOT what ServingEngine runs: its one chain is
    ``megadecode=False, megafront=False``) models the
    mega-kernel back half: after attention only ``fused_oproj_norm``
    and ``fused_ffn`` launch (2 pallas_calls; their weight slabs are
    carved out of ``weight_bytes_per_layer``).  ``megafront=True``
    models the mega-kernel front
    half: the qkv matmuls, rope and paged K/V scatter collapse into
    one ``fused_qkv_rope_append`` launch, so with both flags on NO
    projection pseudo-kernel remains and the body is 5 launches
    (norm + front + attention + oproj + ffn).  ``megadecode=False,
    megafront=False`` models the pre-ISSUE-14 split chain (2 norms +
    swiglu + 6 projection matmuls, 11 launches).

    Projection matmuls left outside the fused kernels route through
    `weight_only_linear` when ``quant_algo`` is set; in bf16 they are
    XLA dots, reported under the pseudo-kernel ``xla_projections`` so
    the layer's weight traffic still lands in the ledger (pass
    ``weight_bytes_per_layer`` from the real weight tree).
    """
    B, D, KV, Hq = batch, head_dim, kv_heads, heads
    kernels: Dict[str, Any] = {
        "fused_rms_norm": (1 if megadecode else 2,
                           cost("fused_rms_norm", T=B, H=hidden)),
    }
    if megafront:
        front = cost("fused_qkv_rope_append", T=B, H=hidden, Hq=Hq,
                     KV=KV, D=D, page_size=page_size, algo=quant_algo,
                     dtype_bytes=kv_dtype_bytes)
        kernels["fused_qkv_rope_append"] = (1, front)
    else:
        front = None
        kernels["fused_rope_append"] = (1, cost(
            "fused_rope_append", T=B, Hq=Hq, KV=KV, D=D,
            page_size=page_size, dtype_bytes=kv_dtype_bytes))
    kernels["ragged_paged_attention"] = (1, cost(
        "ragged_paged_attention", T=B, H=Hq, KV=KV, D=D, S=B,
        pages_per_seq=_ceil_div(context, page_size),
        page_size=page_size, dtype_bytes=kv_dtype_bytes))
    if megadecode:
        oproj = cost("fused_oproj_norm", T=B, Ko=Hq * D, H=hidden,
                     algo=quant_algo)
        ffn = cost("fused_ffn", T=B, H=hidden, I=intermediate,
                   algo=quant_algo,
                   act="gelu" if family == "gpt" else "swiglu")
        kernels["fused_oproj_norm"] = (1, oproj)
        kernels["fused_ffn"] = (1, ffn)
        # whatever matmuls remain outside the fused kernels carry the
        # weight bytes the layer tree holds beyond the fused slabs
        # (both ledgers carve from the SAME real total)
        fused_w = (oproj.breakdown["weights"]
                   + ffn.breakdown["weights"])
        if front is not None:
            fused_w += front.breakdown["weights"]
            n_mats, mat_flops = 0, 0
        else:
            n_mats, mat_flops = 3, Hq * D + 2 * KV * D
        qkv_w = max(0, int(weight_bytes_per_layer) - fused_w)
    else:
        kernels["swiglu"] = (1, cost("swiglu", T=B, H=intermediate))
        if front is not None:
            qkv_w = max(0, int(weight_bytes_per_layer)
                        - front.breakdown["weights"])
            n_mats = 3
            mat_flops = hidden + 3 * intermediate
        else:
            qkv_w = int(weight_bytes_per_layer)
            n_mats = 6
            mat_flops = (Hq * D + 2 * KV * D + hidden
                         + 3 * intermediate)
    if n_mats:
        # per-LAUNCH projection traffic (consumers multiply by the
        # launch count, so the n_mats dispatches still sum to the
        # layer's full projection weight read — one crossing per step,
        # never n_mats)
        proj_flops = 2 * B * hidden * mat_flops // n_mats
        act = B * hidden * 2                # in/out rows of one matmul
        proj = CostEstimate(
            bytes_read=qkv_w // n_mats + act,
            bytes_written=act, flops=proj_flops,
            breakdown={"weights": qkv_w // n_mats,
                       "activations": 2 * act})
        if quant_algo is not None:
            kernels["weight_only_linear"] = (n_mats, proj)
        else:
            kernels["xla_projections"] = (n_mats, proj)
    return {"family": family, "kernels": kernels,
            "launches_per_layer": sum(n for n, _ in kernels.values())}


def pretrain_step_budget(*, n_params: int, tokens: int,
                         layers: int = 0, hidden: int = 0,
                         seq_len: int = 0, dtype_bytes: int = 2,
                         opt_state_bytes_per_param: int = 12
                         ) -> Dict[str, Any]:
    """6N FLOPs ledger + coarse HBM decomposition of one train step:
    weights cross ~3x (fwd read, bwd read, grad write), the AdamW state
    (f32 master + 2 moments = 12 B/param) crosses twice, activations ~
    2 * tokens * hidden * layers * dtype each way when the shape is
    given.  The FLOPs side is the MFU contract: 6 * n_params per token
    (+ the 12*L*s*H attention term when layers/seq/hidden are known)."""
    flops_tok = 6 * n_params
    if layers and hidden and seq_len:
        flops_tok += 12 * layers * seq_len * hidden
    weights = 3 * n_params * dtype_bytes
    opt = 2 * n_params * opt_state_bytes_per_param
    acts = (4 * tokens * hidden * layers * dtype_bytes
            if layers and hidden else 0)
    return {"flops_per_token": flops_tok,
            "flops_per_step": flops_tok * tokens,
            "weights_bytes": weights, "optimizer_bytes": opt,
            "activation_bytes": acts,
            "bytes_per_step": weights + opt + acts,
            "tokens": tokens}


def flops_per_sample(*, n_params: int, tokens_per_sample: int,
                     layers: int = 0, hidden: int = 0) -> float:
    """The trainer's MFU numerator when TrainingArguments doesn't pin
    flops_per_sample: 6N (+ attention term) per token, fwd+bwd."""
    b = pretrain_step_budget(n_params=n_params, tokens=tokens_per_sample,
                             layers=layers, hidden=hidden,
                             seq_len=tokens_per_sample)
    return float(b["flops_per_step"])


def train_mfu(*, tokens_per_s: float, n_params: int,
              peak_flops: float = PEAK_FLOPS["v5e"],
              flops_per_token: Optional[float] = None) -> float:
    """Model FLOPs utilization from the same 6N registry the serving
    roofline uses — train and serve share one cost vocabulary."""
    f = flops_per_token if flops_per_token is not None else 6 * n_params
    return tokens_per_s * f / peak_flops


# ---------------------------------------------------------------------------
# array-tree accounting
# ---------------------------------------------------------------------------

def tree_bytes(tree: Any) -> int:
    """Total storage bytes of every array leaf (duck-typed: anything
    with .size and .dtype.itemsize counts; config/str leaves don't)."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = getattr(leaf, "size", None)
        dt = getattr(leaf, "dtype", None)
        if size is not None and dt is not None:
            total += int(size) * int(getattr(dt, "itemsize", 0)
                                     or dt.itemsize)
    return total
