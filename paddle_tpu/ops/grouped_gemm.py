"""Grouped GEMM for MoE expert compute.

Reference capability: CUTLASS grouped-gemm fused MoE kernels
(paddle/phi/kernels/fusion/cutlass/ moe/weight-only gemm — SURVEY §2.3 P7).

TPU-native realization, fastest-first (v5e measurements in README /
tools-bench notes): `jax.lax.ragged_dot` (XLA's native ragged matmul —
fastest fwd, ties bwd), then the in-tree authored Pallas kernel
(ops/pallas_gmm.py — beats the bundled megablox kernel 1.5-1.6x on the
benched MoE shapes and runs everywhere incl. interpret-mode CPU), then
bundled megablox, then a pure-einsum fallback. FLAGS_gmm_impl pins one
('auto'/'xla'/'intree'/'bundled'/'einsum').
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _count_kernel

__all__ = ["grouped_gemm", "sort_by_group", "unsort_by_group", "group_order",
           "dispatch_pair_rows", "combine_pair_rows", "pair_rows_visited"]


def grouped_gemm(lhs, rhs, group_sizes, *, prefer_ragged: bool = True):
    """lhs [M, K] rows grouped contiguously; rhs [G, K, N]; group_sizes [G]
    (sum == M). Returns [M, N] where row m is multiplied by its group's rhs.

    Routing: FLAGS_gmm_impl 'auto' tries fastest-first and falls through
    on ANY kernel failure; a PINNED impl ('xla'/'intree'/'bundled'/
    'einsum') runs exactly that one and lets its errors surface —
    pinning exists to benchmark/validate a specific kernel, so silent
    degradation would defeat it. prefer_ragged=False (legacy knob) only
    applies in 'auto' mode, where it means einsum-only.
    """
    from ..flags import flag
    impl = flag("FLAGS_gmm_impl")
    G = rhs.shape[0]
    gs32 = group_sizes.astype(jnp.int32)
    if impl == "xla":
        _count_kernel("gmm_xla")
        return jax.lax.ragged_dot(lhs, rhs, gs32)
    if impl == "intree":
        from .pallas_gmm import gmm, gmm_kernel_eligible
        if not gmm_kernel_eligible(lhs.shape[0], lhs.shape[1],
                                   rhs.shape[2]):
            raise ValueError(
                f"FLAGS_gmm_impl='intree' pinned but shape M={lhs.shape[0]} "
                f"K={lhs.shape[1]} N={rhs.shape[2]} is not kernel-eligible "
                "(N and K must be 128-multiples)")
        _count_kernel("gmm_intree")
        return gmm(lhs, rhs, gs32)
    if impl == "bundled":
        from jax.experimental.pallas.ops.tpu.megablox import gmm as mb_gmm
        _count_kernel("gmm_bundled")
        return mb_gmm(lhs, rhs, gs32)
    if impl == "auto" and prefer_ragged:
        # NOTE: the try/excepts below only catch TRACE-time rejections
        # (unsupported primitive/shape raised while tracing). Failures that
        # surface at XLA/Mosaic compile time escape them, so the chain is
        # gated on static predicates first — kernel eligibility and a VMEM
        # block-footprint bound — and the excepts are just a second fence.
        try:
            out = jax.lax.ragged_dot(lhs, rhs, gs32)
            _count_kernel("gmm_xla")
            return out
        except Exception:  # pragma: no cover - backend-specific gaps
            pass
        from .pallas_gmm import gmm, gmm_kernel_eligible
        if (gmm_kernel_eligible(lhs.shape[0], lhs.shape[1], rhs.shape[2])
                and _gmm_vmem_ok(lhs.shape[1], rhs.shape[2], lhs.dtype)):
            try:
                out = gmm(lhs, rhs, gs32)
                _count_kernel("gmm_intree")
                return out
            except Exception:  # pragma: no cover - trace-time only
                pass
        if (jax.default_backend() == "tpu"
                and _gmm_vmem_ok(lhs.shape[1], rhs.shape[2], lhs.dtype)):
            try:
                # megablox gmm: the bundled Pallas TPU grouped-GEMM kernel
                from jax.experimental.pallas.ops.tpu.megablox import gmm \
                    as mb_gmm
                out = mb_gmm(lhs, rhs, gs32)
                _count_kernel("gmm_bundled")
                return out
            except Exception:  # pragma: no cover - kernel constraints
                pass
    # fallback: one-hot group membership -> batched einsum (static shapes)
    _count_kernel("gmm_einsum")
    M = lhs.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    rows = jnp.arange(M)
    member = (rows[None, :] >= starts[:, None]) & (rows[None, :] < ends[:, None])
    # [G, M] bool; project lhs per group, matmul, and sum (each row is in
    # exactly one group so the sum just selects)
    per_g = jnp.einsum("gm,mk->gmk", member.astype(lhs.dtype), lhs)
    out_g = jnp.einsum("gmk,gkn->gmn", per_g, rhs)
    return jnp.sum(out_g, axis=0)


def _gmm_vmem_ok(K: int, N: int, dtype, block_m: int = 128,
                 block_n: int = 128, budget_bytes: int = 64 << 20) -> bool:
    """Static VMEM bound for the Pallas grouped-GEMM kernels: one grid cell
    holds an lhs block [bm, K], an rhs block [K, bn] and the f32 accumulator
    [bm, bn]. Mosaic VMEM overflow is a COMPILE-time error the auto chain
    cannot catch, so shapes that would overflow are routed past the kernels
    up front (half the ~128MB v5 VMEM, leaving room for double-buffering)."""
    esize = jnp.dtype(dtype).itemsize
    need = (block_m * K + K * block_n) * esize + block_m * block_n * 4
    return need <= budget_bytes


def group_order(group_ids, num_groups: int):
    """(the stable order that sorts `group_ids`, its inverse permutation,
    the groups' sizes) — all static-shape, jit-safe."""
    order = jnp.argsort(group_ids, stable=True)
    inv = jnp.argsort(order, stable=True)
    return order, inv, jnp.bincount(group_ids, length=num_groups)


def sort_by_group(x, group_ids, num_groups: int):
    """Stable-sort rows of x by group id. Returns (sorted_x, group_sizes,
    inverse permutation) — all static-shape, jit-safe."""
    order, inv, sizes = group_order(group_ids, num_groups)
    return x[order], sizes.astype(jnp.int32), inv


def unsort_by_group(x_sorted, inverse_perm):
    return x_sorted[inverse_perm]


# ---------------------------------------------------------------------------
# the routed FFN's two permutations of (token, choice) pair rows
# ---------------------------------------------------------------------------
#
# `order` sorts the T * k pair rows by expert and `inv` is its inverse, but
# autodiff cannot know that: the transpose it writes for `x[order]` is a
# scatter-add into zeros (on a v5e ~143 ns a row of 4.6 kB where the gather
# takes ~40).  The two operations below carry their own rules: every pass
# is a gather, the combine's backward runs in SORTED space (the [T, k, H]
# cotangent is never made), and the rows no held expert owns — they sort
# behind the held groups — are neither read nor, past one chunk, visited.

#: rows a pass in sorted order visits at a time where it stops at the last
#: owned one; a call of at most this many pair rows is one plain gather
PAIR_ROW_CHUNK = 16384


def _chunked(n_rows: int, mine) -> bool:
    """Whether a pass in sorted order over `n_rows` pair rows walks the
    owned prefix chunk by chunk: read from the shape and from whether
    the caller holds only some experts (`mine` None: all)."""
    return mine is not None and n_rows > PAIR_ROW_CHUNK


def _owned_chunks(n_owned):
    """Chunks of the sorted rows that hold an owned row."""
    return -(-jnp.asarray(n_owned, jnp.int32) // PAIR_ROW_CHUNK)


def pair_rows_visited(n_rows: int, n_owned):
    """Pair rows a pass in sorted order visits: whole chunks up to the
    last owned row (`n_owned` None: every row is), or all `n_rows` of a
    call that is not chunked."""
    if not _chunked(n_rows, n_owned):
        return jnp.asarray(n_rows, jnp.int32)
    return jnp.minimum(_owned_chunks(n_owned) * PAIR_ROW_CHUNK, n_rows)


def _over_owned_chunks(n_rows: int, n_owned, body, init):
    """`body(start, live, carry) -> carry` for each chunk
    [start, start + PAIR_ROW_CHUNK) that holds an owned row, `live`
    [PAIR_ROW_CHUNK] naming the rows before `n_owned`.  The count is
    decided on the device; the last chunk of a length the chunk does not
    divide starts early and writes some rows twice, the same values."""
    C = PAIR_ROW_CHUNK

    def step(i, carry):
        start = jnp.minimum(i * C, n_rows - C)
        return body(start, start + jnp.arange(C) < n_owned, carry)

    return jax.lax.fori_loop(0, _owned_chunks(n_owned), step, init)


def _rows_where(live, rows):
    return rows if live is None else jnp.where(live[:, None], rows, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def dispatch_pair_rows(xt, group_ids, mine, num_groups: int):
    """`xt` [T, H] and the groups `group_ids` [T, k] of its (token,
    choice) pairs -> (the pair rows sorted by group [T * k, H]:
    `srt[r] = xt[order[r] // k]`; the `num_groups` groups' sizes;
    `order`; its inverse `inv`; the rows a group owns, `n_owned`).
    `mine` [T, k] names the pairs whose group is one of `num_groups`:
    the others carry the id `num_groups`, sort behind and are owned by
    no group (`mine` None: every pair is owned, `n_owned` None).  A
    chunked call (`_chunked`) leaves zeros past the last owned row.

    Backward `d_xt[t] = sum_j ct[inv[t k + j]]` over the owned pairs:
    one row gather and a sum over k.  The cotangent of a row no group
    owns is whatever the grouped GEMM's backward left there (on the
    chip, rubbish) and is dropped."""
    k, M = group_ids.shape[1], group_ids.size
    chunked = _chunked(M, mine)
    if not chunked:
        # before the sort, as it was written: a forward's text stays
        rows = jnp.repeat(xt, k, axis=0)
    order, inv, sizes = group_order(group_ids.reshape(-1),
                                    num_groups + (mine is not None))
    if not chunked:
        srt = rows[order]
    sizes = sizes.astype(jnp.int32)[:num_groups]
    n_owned = None if mine is None else jnp.sum(sizes)
    if chunked:
        def body(start, live, srt):
            at = jax.lax.dynamic_slice(order, (start,), (PAIR_ROW_CHUNK,))
            return jax.lax.dynamic_update_slice(
                srt, _rows_where(live, xt[at // k]), (start, 0))

        srt = _over_owned_chunks(M, n_owned, body,
                                 jnp.zeros((M, xt.shape[1]), xt.dtype))
    return srt, sizes, order, inv, n_owned


def _dispatch_fwd(xt, group_ids, mine, num_groups):
    out = dispatch_pair_rows(xt, group_ids, mine, num_groups)
    return out, (out[3].reshape(group_ids.shape), mine)


def _dispatch_bwd(num_groups, res, cts):
    inv, mine = res                                 # [T, k]
    g = cts[0][inv]                                 # [T, k, H]
    if mine is not None:
        g = jnp.where(mine[..., None], g, 0)
    return jax.lax.reduce_sum(g, (1,)), None, None


dispatch_pair_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine_pair_rows(down, gv, order, inv, mine, n_owned):
    """The sorted expert outputs `down` [T * k, H] -> `y` [T, H]:
    `y[t] = sum_j gv[t, j] * down[inv[t k + j]]` over the owned pairs,
    in rank order (the serving exact-match contract reads that order),
    the weights `gv` [T, k] taken in `down`'s dtype.  `order`, `inv`,
    `mine`, `n_owned`: `dispatch_pair_rows`'.

    Backward in sorted space: `dy[order[r] // k]` gathered once from
    [T, H]; `d_down[r] = gv_s[r] * dy_s[r]`; `d_gv` from the row-wise
    product <dy_s[r], down[r]>, un-sorted as T * k scalars.  Rows past
    `n_owned` are not read and get a zero cotangent."""
    T, k = gv.shape
    sel = unsort_by_group(down, inv).reshape(T, k, -1)
    if mine is not None:
        # whatever a kernel leaves in the rows it does not own
        sel = jnp.where(mine[..., None], sel, 0)
    return jnp.einsum("tk,tkh->th", gv.astype(sel.dtype), sel)


def _combine_fwd(down, gv, order, inv, mine, n_owned):
    return (combine_pair_rows(down, gv, order, inv, mine, n_owned),
            (down, gv, order, inv, mine, n_owned))


def _combine_bwd(res, dy):
    down, gv, order, inv, mine, n_owned = res
    M, k = order.shape[0], gv.shape[1]
    gv_flat = gv.astype(down.dtype).reshape(-1)

    def sorted_cts(at, live, down_rows):
        """(d_down, <dy_s, down>) of the sorted rows `at` names."""
        dy_s = dy[at // k]
        dots = jnp.einsum("rh,rh->r", dy_s, _rows_where(live, down_rows))
        return _rows_where(live, gv_flat[at][:, None] * dy_s), dots

    if not _chunked(M, mine):
        live = None if mine is None else jnp.arange(M) < n_owned
        d_down, dots = sorted_cts(order, live, down)
    else:
        C = PAIR_ROW_CHUNK

        def body(start, live, carry):
            d, p = sorted_cts(
                jax.lax.dynamic_slice(order, (start,), (C,)), live,
                jax.lax.dynamic_slice(down, (start, 0), (C, down.shape[1])))
            return (jax.lax.dynamic_update_slice(carry[0], d, (start, 0)),
                    jax.lax.dynamic_update_slice(carry[1], p, (start,)))

        d_down, dots = _over_owned_chunks(
            M, n_owned, body, (jnp.zeros_like(down),
                               jnp.zeros((M,), down.dtype)))
    d_gv = dots[inv].reshape(gv.shape)
    if mine is not None:
        d_gv = jnp.where(mine, d_gv, 0)
    return d_down, d_gv.astype(gv.dtype), None, None, None, None


combine_pair_rows.defvjp(_combine_fwd, _combine_bwd)
