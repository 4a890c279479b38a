"""In-tree flash attention kernel (fwd + bwd), authored and tunable.

Reference capability: FlashAttention2 fwd/bwd —
paddle/phi/kernels/gpu/flash_attn_kernel.cu and
python/paddle/nn/functional/flash_attention.py (VERDICT r2 item 9: own
the kernel the serving/pretrain benches spend their time in, instead of
wrapping jax.experimental.pallas.ops.tpu.flash_attention).

Same machinery as ops/pallas_flashmask.py (that kernel proved the
pattern; this one drops the band encodings and adds what the bundled
kernel refuses):

  - causal with UNEQUAL Sq/Sk, bottom-right aligned: query row i sees
    key j iff j <= i + (Sk - Sq) — exactly sdpa_reference's
    jnp.tril(..., k=Sk-Sq) convention, so the composite stays the oracle;
  - optional q/kv segment ids (varlen packing, key-padding routing) as
    an elementwise block-local mask;
  - each sweep walks only the (query block, key block) pairs the causal
    mask leaves visible: a scalar-prefetched table, built at trace time
    from the static geometry (lengths, blocks, offset), names every grid
    step's pair, so a hidden pair is no grid step and no DMA, and says
    whether the diagonal cuts it: an interior pair runs with no iota, no
    compare and no `where` (with segment ids, which are data, every
    visible pair stays masked);
  - an optional static sliding WINDOW `W` on top of causal (query at
    position i sees keys j with i - W < j <= i): one more bound of the
    SAME table — a pair wholly left of the band is hidden (no grid step),
    a pair the band's left edge cuts is masked, a pair between the two
    edges stays interior — for the forward and both backward sweeps;
  - online-softmax forward emitting logsumexp; flash-style backward (dq
    sweep over a query block's key blocks; dk/dv sweep over a key block's
    query blocks on the TRANSPOSED scores); lse / di travel as dense
    [B, H, 1, Sq] rows;
  - caller-tunable block sizes (default 512x512, clamped to the sequence
    lengths; `sdpa` picks the largest 128-multiple <= 512 that divides),
    f32 accumulation, interpret mode off-TPU so the CPU suite covers the
    kernel logic.

Fully-hidden query rows (causal offset < 0 at the sequence head, or an
unmatched segment) produce zero output and a +1e30 lse sentinel, so the
backward underflows to zero instead of producing NaN.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.attribution import keeps as _keeps
from .flash_attention import count_block_pairs
from .lane_stat import lanes as _lanes
from .on_mesh import on_mesh

__all__ = ["flash_sdpa", "flash_kernel_eligible"]

_NEG = -1e30

# the pair axis carries the online-softmax / accumulator state of a run
# of pairs (one query block's, or in the dk / dv sweep one key block's)
# and is sequential; batch and heads are independent, and Mosaic may
# split them across TensorCores (megacore parts)
_CPARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))

# what the mask leaves of a (query block, key block) pair: nothing (the
# pair is no grid step), all of it (no mask arithmetic), or a part
_HIDDEN, _INTERIOR, _MASKED = "skipped", "interior", "masked"
# a visited pair's flags in the table: the first / last of its run (set
# the accumulators up / write the run's block out) and whether it pays
# the mask
_FIRST, _LAST, _MASK = 1, 2, 4


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pair_kind(qi, kj, bq, bk, off, causal, use_seg, window=None) -> str:
    """Static geometry of pair (qi, kj): row r sees column c iff
    c <= r + off and, under a window W, r + off - c < W. Segment ids are
    data, so with them no pair is interior."""
    if causal and kj * bk > qi * bq + (bq - 1) + off:
        return _HIDDEN              # above the diagonal of its LAST row
    if window is not None and \
            qi * bq + off - (kj * bk + bk - 1) >= window:
        return _HIDDEN              # left of the band of its FIRST row
    if use_seg or (causal and kj * bk + (bk - 1) > qi * bq + off):
        return _MASKED              # the diagonal of its FIRST row cuts it
    if window is not None and \
            qi * bq + (bq - 1) + off - kj * bk >= window:
        return _MASKED              # the band's edge of its LAST row does
    return _INTERIOR


@functools.lru_cache(maxsize=None)
def _visit_table(nq, nk, bq, bk, off, causal, use_seg, order, window=None):
    """The pairs a sweep visits, in its order, as (qi, kj, flags) columns
    and the sweep's {kind: pairs} counts. order 'qk': a run is a query
    block's visible key blocks (forward, dq); 'kq': a key block's visible
    query blocks (dk / dv). A run the mask leaves nothing of (the query
    blocks above a causal diagonal with Sq > Sk, the key blocks no query
    of a shorter Sq reaches back to under a window) still visits one
    masked pair, which writes its block: zeros and the lse sentinel."""
    runs, inner = (nq, nk) if order == "qk" else (nk, nq)
    rows, counts = [], {_INTERIOR: 0, _MASKED: 0}
    for r in range(runs):
        pairs = [(r, c) if order == "qk" else (c, r) for c in range(inner)]
        run = [(qi, kj, _pair_kind(qi, kj, bq, bk, off, causal, use_seg,
                                   window))
               for qi, kj in pairs]
        run = [pair for pair in run if pair[2] != _HIDDEN] \
            or [(*pairs[0], _MASKED)]
        for n, (qi, kj, kind) in enumerate(run):
            counts[kind] += 1
            rows.append((qi, kj, _FIRST * (n == 0)
                         + _LAST * (n == len(run) - 1)
                         + _MASK * (kind == _MASKED)))
    counts[_HIDDEN] = nq * nk - len(rows)
    return np.asarray(rows, np.int32).T, counts


def _mask_for_block(qi, kj, bq, bk, causal, off, use_seg, sq_ref, sk_ref,
                    transposed=False, window=None):
    """bool mask of HIDDEN entries for this block: [bq, bk], or [bk, bq]
    for the dk / dv sweep's transposed scores."""
    shape, qax = ((bk, bq), 1) if transposed else ((bq, bk), 0)
    masked = None
    if causal:
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, shape, qax)
        cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - qax)
        masked = cols > rows + off
        if window is not None:
            masked = jnp.logical_or(masked, rows + off - cols >= window)
    if use_seg:
        sq, sk = sq_ref[0, 0], sk_ref[0, 0]
        seg = (sk[:, None] != sq[None, :] if transposed
               else sq[:, None] != sk[None, :])
        masked = seg if masked is None else jnp.logical_or(masked, seg)
    return masked


def _sweep(tabs, init, pair, emit, *, bq, bk, causal, off, use_seg,
           window=None, transposed=False):
    """One grid step = one visited pair of the table: `init` on the first
    of its run, `pair(masked)` with no mask at all on an interior pair and
    with the block's mask on a masked one, `emit` on the run's last."""
    qi_tab, kj_tab, fl_tab, sq_ref, sk_ref = tabs
    t = pl.program_id(2)
    qi, kj, fl = qi_tab[t], kj_tab[t], fl_tab[t]
    pl.when(fl & _FIRST != 0)(init)
    if causal or use_seg:
        pl.when(fl & _MASK != 0)(lambda: pair(_mask_for_block(
            qi, kj, bq, bk, causal, off, use_seg, sq_ref, sk_ref,
            transposed, window)))
    if not use_seg:
        pl.when(fl & _MASK == 0)(lambda: pair(None))
    pl.when(fl & _LAST != 0)(emit)


def _fwd_kernel(qi_tab, kj_tab, fl_tab, sq_ref, sk_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, acc_ref, m_ref, l_ref, *, scale, **geometry):
    def init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    def pair(masked):
        q = q_ref[0, 0]                                       # [bq, D]
        k = k_ref[0, 0]                                       # [bk, D]
        # inputs stay bf16 on the MXU (full throughput); accumulation is
        # f32 via preferred_element_type — same contract as the bundled
        # kernel (casting inputs to f32 halves MXU throughput). The scores
        # and their running maximum stay UNSCALED: the scale rides in the
        # exponent's float32 constant, and rounds nothing
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, bk]
        if scale < 0:       # the largest SCALED score is the maximum
            s = -s
        if masked is not None:
            s = jnp.where(masked, _NEG, s)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp((s - _lanes(m_new, s.shape[1])) * abs(scale))
        if masked is not None:
            p = jnp.where(masked, 0.0, p)
        alpha = jnp.exp((m_prev - m_new) * abs(scale))
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, -1, keepdims=True)
        v = v_ref[0, 0]
        acc_ref[:] = acc_ref[:] * _lanes(alpha, v.shape[1]) \
            + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    def emit():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / _lanes(l_safe, acc_ref.shape[1])
                       ).astype(o_ref.dtype)
        # lse leaves as a dense [1, bq] row (a [.., bq, 1] array in HBM is
        # 128 lanes of padding a number)
        lse = jnp.where(l == 0.0, -_NEG,
                        m_ref[:] * abs(scale) + jnp.log(l_safe))
        lse_ref[0, 0] = lse.T[:1]

    _sweep((qi_tab, kj_tab, fl_tab, sq_ref, sk_ref), init, pair, emit,
           **geometry)


def _bwd_dq_kernel(qi_tab, kj_tab, fl_tab, sq_ref, sk_ref, q_ref, k_ref,
                   v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_acc, lse_col,
                   di_col, *, scale, **geometry):
    def init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        # the run's rows of lse / di, turned to columns once a query block
        lse_col[:] = lse_ref[0, 0, 0][:, None]
        di_col[:] = di_ref[0, 0, 0][:, None]

    def pair(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        p = jnp.exp(s * scale - lse_col[:])
        if masked is not None:
            p = jnp.where(masked, 0.0, p)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - di_col[:])).astype(k.dtype)
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def emit():
        # ds's scale, once a [bq, D] block and not once a score
        dq_ref[0, 0] = (dq_acc[:] * scale).astype(dq_ref.dtype)

    _sweep((qi_tab, kj_tab, fl_tab, sq_ref, sk_ref), init, pair, emit,
           **geometry)


def _bwd_dkv_kernel(qi_tab, kj_tab, fl_tab, sq_ref, sk_ref, q_ref, k_ref,
                    v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref, dk_acc,
                    dv_acc, *, scale, **geometry):
    def init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def pair(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        # the TRANSPOSED scores k q^T: the queries' lse / di broadcast
        # along sublanes as the dense rows they arrive as, and dv = p^T do,
        # dk = ds^T q are plain matmuls
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, bq]
        p = jnp.exp(s * scale - lse_ref[0, 0])
        if masked is not None:
            p = jnp.where(masked, 0.0, p)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, D]
        dp = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, bq]
        ds = (p * (dp - di_ref[0, 0])).astype(q.dtype)
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, D]

    def emit():
        dk_ref[0, 0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)

    _sweep((qi_tab, kj_tab, fl_tab, sq_ref, sk_ref), init, pair, emit,
           transposed=True, **geometry)


def _specs(bq, bk, D):
    """in_specs for (seg_q, seg_kv, q, k, v), the row spec of lse / di, and
    the query- and key-block maps: the same for every sweep, since a grid
    step's blocks are its pair's in the prefetched table."""
    qmap = lambda b, h, t, qi, kj, fl: (b, h, qi[t], 0)
    kmap = lambda b, h, t, qi, kj, fl: (b, h, kj[t], 0)
    # segment ids ride as [B, 1, S] and lse / di as [B, H, 1, Sq], so the
    # (.., 1, blk) block satisfies the Mosaic trailing-dims rule
    # (second-to-last block dim == full dim 1) and a row is dense in HBM
    row_spec = pl.BlockSpec((1, 1, 1, bq),
                            lambda b, h, t, qi, kj, fl: (b, h, 0, qi[t]))
    return ([pl.BlockSpec((1, 1, bq),
                          lambda b, h, t, qi, kj, fl: (b, 0, qi[t])),
             pl.BlockSpec((1, 1, bk),
                          lambda b, h, t, qi, kj, fl: (b, 0, kj[t])),
             pl.BlockSpec((1, 1, bq, D), qmap),
             pl.BlockSpec((1, 1, bk, D), kmap),
             pl.BlockSpec((1, 1, bk, D), kmap)], row_spec, qmap, kmap)


def _out(shape, dtype, *like):
    """out_shape entry varying over the manual mesh axes its inputs vary
    over — inside a vma-checked shard_map (on_mesh under the pipeline's
    pp region) pallas_call has to be told."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _pairs(kernel, order, q, k, *, bq, bk, **geometry):
    """The sweep's table as the three scalar-prefetch operands and its
    length, counted into pt_flash_block_pairs_total as the launch is
    traced."""
    B, H, Sq, _ = q.shape
    table, counts = _visit_table(Sq // bq, k.shape[2] // bk, bq, bk,
                                 order=order, **geometry)
    count_block_pairs(kernel, {kind: n * B * H for kind, n in counts.items()})
    return tuple(jnp.asarray(col) for col in table), table.shape[1]


# the kernel launches run per shard of the step's mesh, INSIDE the
# custom_vjp rules (see ops/on_mesh.py)
_QKV = ("bhsd", "bhsd", "bhsd", "b1s", "b1s")


def _fwd_on_mesh(q, k, v, seg_q, seg_kv, scale, causal, bq, bk, use_seg,
                 window):
    fwd = functools.partial(_flash_fwd_impl, scale=scale, causal=causal,
                            bq=bq, bk=bk, use_seg=use_seg, window=window)
    return on_mesh(fwd, (q, k, v, seg_q, seg_kv), _QKV, ("bhsd", "bh1s"))


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_core(q, k, v, seg_q, seg_kv, scale, causal, bq, bk, use_seg,
                kept=False, window=None):
    o, _ = _fwd_on_mesh(q, k, v, seg_q, seg_kv, scale, causal, bq, bk,
                        use_seg, window)
    return o


def _flash_fwd_impl(q, k, v, seg_q, seg_kv, scale, causal, bq, bk,
                    use_seg, window=None):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    geometry = dict(bq=bq, bk=bk, off=Sk - Sq, causal=causal,
                    use_seg=use_seg, window=window)
    table, n_pairs = _pairs("fwd", "qk", q, k, **geometry)
    in_specs, row_spec, qmap, _ = _specs(bq, bk, D)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, **geometry),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,          # the table's qi, kj, flags
            grid=(B, H, n_pairs),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, 1, bq, D), qmap), row_spec],
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                            pltpu.VMEM((bq, 128), jnp.float32),   # m
                            pltpu.VMEM((bq, 128), jnp.float32)]),  # l
        out_shape=[_out((B, H, Sq, D), q.dtype, q, k, v),
                   _out((B, H, 1, Sq), jnp.float32, q, k, v)],
        compiler_params=_CPARAMS,
        interpret=_interpret(),
    )(*table, seg_q, seg_kv, q, k, v)
    return o, lse


def _flash_vjp_fwd(q, k, v, seg_q, seg_kv, scale, causal, bq, bk,
                   use_seg, kept, window):
    o, lse = _fwd_on_mesh(q, k, v, seg_q, seg_kv, scale, causal, bq, bk,
                          use_seg, window)
    if kept:
        # a checkpoint around the caller keeps the kernel's output and
        # row log-sum-exp (`observability.attribution.RESIDUALS`), so
        # that the kernel does not run again for the backward.  Named
        # HERE, on the rule's own residuals: a name on the primal output
        # outside the custom_vjp does not reach them; and `kept` is an
        # argument because this rule is traced after the caller's
        # `attribution.keeping` has ended
        o = checkpoint_name(o, "flash_o")
        lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, seg_q, seg_kv, o, lse)


def _flash_vjp_bwd(scale, causal, bq, bk, use_seg, kept, window, res, do):
    bwd = functools.partial(_flash_bwd_impl, scale=scale, causal=causal,
                            bq=bq, bk=bk, use_seg=use_seg, window=window)
    dq, dk, dv = on_mesh(bwd, (*res, do),
                         _QKV + ("bhsd", "bh1s", "bhsd"), ("bhsd",) * 3)
    return dq, dk, dv, None, None


def _flash_bwd_impl(q, k, v, seg_q, seg_kv, o, lse, do, scale, causal,
                    bq, bk, use_seg, window=None):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    geometry = dict(bq=bq, bk=bk, off=Sk - Sq, causal=causal,
                    use_seg=use_seg, window=window)
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1)[:, :, None]                        # [B,H,1,Sq]
    in_specs, row_spec, qmap, kmap = _specs(bq, bk, D)
    in_specs = in_specs + [pl.BlockSpec((1, 1, bq, D), qmap),
                           row_spec, row_spec]

    table, n_pairs = _pairs("dq", "qk", q, k, **geometry)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, **geometry),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H, n_pairs),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, bq, D), qmap),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32)]),
        out_shape=_out((B, H, Sq, D), q.dtype, q, k, v, do),
        compiler_params=_CPARAMS,
        interpret=_interpret(),
    )(*table, seg_q, seg_kv, q, k, v, do, lse, di)

    table, n_pairs = _pairs("dkv", "kq", q, k, **geometry)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, **geometry),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H, n_pairs),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, 1, bk, D), kmap),
                       pl.BlockSpec((1, 1, bk, D), kmap)],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)]),
        out_shape=[_out((B, H, Sk, D), k.dtype, q, k, v, do),
                   _out((B, H, Sk, D), v.dtype, q, k, v, do)],
        compiler_params=_CPARAMS,
        interpret=_interpret(),
    )(*table, seg_q, seg_kv, q, k, v, do, lse, di)
    return dq, dk, dv


_flash_core.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_kernel_eligible(Sq: int, Sk: int, D: int, block_q: int = 128,
                          block_k: int = 128) -> bool:
    """Unlike the bundled kernel's gate, causal Sq != Sk IS eligible."""
    return (Sq % block_q == 0 and Sk % block_k == 0
            and (D % 128 == 0 or (D <= 128 and D % 64 == 0)))


def flash_sdpa(q, k, v, causal: bool = False, segment_ids_q=None,
               segment_ids_kv=None, scale: Optional[float] = None,
               block_q: int = 512, block_k: int = 512,
               window: Optional[int] = None):
    """[B,S,H,D] flash attention through the in-tree kernel. Causal is
    bottom-right aligned for Sq != Sk (sdpa_reference convention).
    `window` W (static, with `causal`): a query at position i sees the
    keys j with i - W < j <= i, itself included.
    Differentiable (flash-style bwd kernels). Default 512x512 blocks
    (tools/flash_bench.py sweep on the v5e: 512-class blocks beat 128 by
    ~1.2-1.7x at seq >= 4096); blocks clamp to the sequence lengths so
    short sequences still run."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"flash_sdpa: Sq={Sq}/Sk={Sk} not divisible by blocks "
            f"{block_q}x{block_k} (see flash_kernel_eligible)")
    if scale is None:
        scale = D ** -0.5
    if window is not None:
        if not causal or window < 1:
            raise ValueError("flash_sdpa: a window needs causal=True and "
                             f"W >= 1 (got causal={causal}, W={window})")
        window = int(window)
    use_seg = segment_ids_q is not None or segment_ids_kv is not None
    if use_seg:
        seg_q = (segment_ids_q if segment_ids_q is not None
                 else jnp.ones((B, Sq))).astype(jnp.int32)
        seg_kv = (segment_ids_kv if segment_ids_kv is not None
                  else jnp.ones((B, Sk))).astype(jnp.int32)
    else:
        # placeholders keep the kernel signature static; use_seg=False
        # compiles the masking out entirely
        seg_q = jnp.zeros((B, Sq), jnp.int32)
        seg_kv = jnp.zeros((B, Sk), jnp.int32)
    seg_q = seg_q[:, None, :]                 # [B, 1, S]: see _specs
    seg_kv = seg_kv[:, None, :]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    out = _flash_core(qh, kh, vh, seg_q, seg_kv, float(scale),
                      bool(causal), block_q, block_k, use_seg,
                      _keeps("flash_o") and _keeps("flash_lse"), window)
    return jnp.swapaxes(out, 1, 2)


# certification (ROADMAP item 5 / paddlelint PK105): the dense-softmax
# composite is the oracle; lazy string — flash_attention imports us
from .oracles import register_oracle  # noqa: E402

register_oracle(
    "flash_sdpa", kernel=flash_sdpa,
    reference="paddle_tpu.ops.flash_attention:sdpa_reference",
    parity_test="tests/test_flash_kernel.py::TestForwardParity")
