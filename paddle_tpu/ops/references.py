"""Plain-XLA reference implementations for the fused Pallas kernels.

Each function here is the ``reference`` side of a ``register_oracle``
entry (see :mod:`paddle_tpu.ops.oracles`): same signature and dtype
contract as its kernel, written in straight-line jnp so a disagreement
in interpret mode localizes the bug to the kernel. All math runs in f32
and casts back to the input dtype — the same accumulation discipline the
kernels follow.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["rms_norm_reference", "layer_norm_reference",
           "bias_residual_layer_norm_reference",
           "moe_dispatch_combine_reference", "rope_reference",
           "rope_append_reference", "append_rows_reference",
           "chunk_pool_reference",
           "swiglu_reference", "mla_decode_reference", "gmm_reference",
           "oproj_norm_reference", "megadecode_ffn_reference",
           "qkv_rope_append_reference", "ssm_state_update_reference",
           "ssm_state_put_reference", "ssm_recurrence_reference",
           "ssm1_state_update_reference", "ssm1_recurrence_reference",
           "kda_state_update_reference", "kda_recurrence_reference",
           "mhc_layout", "mhc_pack", "sinkhorn_reference",
           "mhc_pre_reference", "mhc_post_reference", "MHC_COEF_LANES"]


def rms_norm_reference(x, weight, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm_reference(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def bias_residual_layer_norm_reference(x, residual, bias=None, weight=None,
                                       ln_bias=None, eps: float = 1e-5):
    H = x.shape[-1]
    b = jnp.zeros((H,), x.dtype) if bias is None else bias
    w = jnp.ones((H,), x.dtype) if weight is None else weight
    lb = jnp.zeros((H,), x.dtype) if ln_bias is None else ln_bias
    h = (x.astype(jnp.float32) + b.astype(jnp.float32)
         + residual.astype(jnp.float32))
    return layer_norm_reference(h, w, lb, eps).astype(x.dtype)


def moe_dispatch_combine_reference(keep, oh_loc, gv):
    kf = keep.astype(jnp.float32)
    of = oh_loc.astype(jnp.float32)
    gf = gv.astype(jnp.float32)
    disp = jnp.einsum("tke,tkc->tec", kf, of)
    comb = jnp.einsum("tke,tk,tkc->tec", kf, gf, of)
    return disp.astype(keep.dtype), comb.astype(keep.dtype)


def _rotate_half(x, c, s):
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def rope_reference(q, k, cos, sin):
    c = cos.astype(jnp.float32)[None, :, None, :]
    s = sin.astype(jnp.float32)[None, :, None, :]
    qr = _rotate_half(q.astype(jnp.float32), c, s).astype(q.dtype)
    kr = _rotate_half(k.astype(jnp.float32), c, s).astype(k.dtype)
    return qr, kr


def rope_append_reference(q, k, v, cos, sin, k_pages, v_pages,
                          page_idx, page_off, live):
    """Every row of q roped; the roped K row and the V row of each LIVE
    row t at (page_idx[t], page_off[t]) of every head. An idle row
    writes nothing (its page index is sent out of range and dropped).
    `fused_rope_append` takes the same rows as runs
    (`ops.fused.append_run_table`)."""
    c = cos.astype(jnp.float32)[:, None, :]           # [T, 1, D/2]
    s = sin.astype(jnp.float32)[:, None, :]
    qr = _rotate_half(q.astype(jnp.float32), c, s).astype(q.dtype)
    kr = _rotate_half(k.astype(jnp.float32), c, s)
    page = jnp.where(live, page_idx, k_pages.shape[1])
    kp = k_pages.at[:, page, page_off, :].set(
        kr.astype(k_pages.dtype).swapaxes(0, 1), mode="drop")
    vp = v_pages.at[:, page, page_off, :].set(
        v.astype(v_pages.dtype).swapaxes(0, 1), mode="drop")
    return qr, kp, vp


def append_rows_reference(pages, rows, runs):
    """Row t of rows [T, KV, D] at the page and offset the run that
    holds it says ([5 * G] of `ops.fused.append_run_table`: first row,
    rows, page, tile of the page, offset in the tile); a row no run
    holds writes nothing. A tuple of pools takes a tuple of rows."""
    from .fused import append_tile
    if isinstance(pages, (tuple, list)):
        return tuple(append_rows_reference(p, r, runs)
                     for p, r in zip(pages, rows))
    first, n, page, part, base = runs.reshape(5, -1)
    tile = append_tile(pages.dtype, pages.shape[2])
    t = jnp.arange(rows.shape[0])[:, None]
    mine = (t >= first) & (t < first + n)                # [T, G]

    def of_run(x):
        return jnp.sum(jnp.where(mine, x, 0), -1)

    page_idx = jnp.where(mine.any(-1), of_run(page), pages.shape[1])
    return pages.at[:, page_idx, of_run(part * tile + base + t - first),
                    :].set(rows.astype(pages.dtype).swapaxes(0, 1),
                           mode="drop")


def chunk_pool_reference(k_pages, v_pages, phi, mu, page_idx, chunk_idx, *,
                         chunk: int, scale: float):
    f32 = jnp.float32
    rows = chunk_idx[:, None] * chunk + jnp.arange(chunk)[None, :]
    k = k_pages[:, page_idx[:, None], rows].astype(f32)    # [KV, P, c, D]
    v = v_pages[:, page_idx[:, None], rows].astype(f32)
    a = jax.nn.softmax(
        scale * jnp.einsum("hpcd,hd->hpc", k, phi.astype(f32)), -1)
    kt = jnp.einsum("hpc,hpcd->phd", a, k) + mu.astype(f32)
    vt = jnp.einsum("hpc,hpcd->phd", a, v)
    return kt.astype(k_pages.dtype), vt.astype(v_pages.dtype)


def swiglu_reference(gate, up=None):
    if up is None:
        d = gate.shape[-1] // 2
        gate, up = gate[..., :d], gate[..., d:]
    gf = gate.astype(jnp.float32)
    return (gf * jax.lax.logistic(gf)
            * up.astype(jnp.float32)).astype(gate.dtype)


def _dequant_ref(w, scale, algo):
    """Whole-tensor dequant of a deploy-layout weight (fp passthrough)."""
    if algo is None:
        return w.astype(jnp.float32)
    from .quant import weight_dequantize
    return weight_dequantize(w, scale.reshape(-1).astype(jnp.float32),
                             algo)


def oproj_norm_reference(o, x, w, scale=None, bias=None, norm_weight=None,
                         norm_bias=None, *, eps: float = 1e-6,
                         norm: str = "rms", algo=None):
    """fused_oproj_norm oracle: dense dequant + f32 matmul + residual +
    rms/layer norm, returning (x_new, h)."""
    shape = x.shape
    H = shape[-1]
    x2 = x.reshape(-1, H).astype(jnp.float32)
    o2 = o.reshape(x2.shape[0], -1).astype(jnp.float32)
    p = o2 @ _dequant_ref(w, scale, algo)
    if bias is not None:
        p = p + bias.reshape(1, H).astype(jnp.float32)
    xn = x2 + p
    if norm == "rms":
        var = jnp.mean(xn * xn, axis=-1, keepdims=True)
        y = xn * jax.lax.rsqrt(var + eps)
    else:
        mu = jnp.mean(xn, axis=-1, keepdims=True)
        xc = xn - mu
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        y = xc * jax.lax.rsqrt(var + eps)
    h = y * (jnp.ones((H,), jnp.float32) if norm_weight is None
             else norm_weight.astype(jnp.float32))
    if norm_bias is not None:
        h = h + norm_bias.astype(jnp.float32)
    return (xn.astype(x.dtype).reshape(shape),
            h.astype(x.dtype).reshape(shape))


def megadecode_ffn_reference(h, x, wg, sg=None, wu=None, su=None,
                             wd=None, sd=None, b1=None, b2=None, *,
                             act: str = "swiglu", algo=None):
    """fused_ffn oracle: gate/up dots + activation + down-proj +
    residual, all in f32."""
    shape = x.shape
    H = shape[-1]
    x2 = x.reshape(-1, H).astype(jnp.float32)
    h2 = h.reshape(-1, H).astype(jnp.float32)
    g = h2 @ _dequant_ref(wg, sg, algo)
    if b1 is not None:
        g = g + b1.reshape(1, -1).astype(jnp.float32)
    if act == "swiglu":
        u = h2 @ _dequant_ref(wu, su, algo)
        t = g * jax.lax.logistic(g) * u
    else:
        t = jax.nn.gelu(g, approximate=True)
    d = t @ _dequant_ref(wd, sd, algo)
    if b2 is not None:
        d = d + b2.reshape(1, H).astype(jnp.float32)
    return (x2 + d).astype(x.dtype).reshape(shape)


def qkv_rope_append_reference(h, w, scale, bias, cos, sin, k_pages,
                              v_pages, page_idx, page_off, *,
                              heads: int, kv_heads: int = 0,
                              head_dim: int = 0, algo=None,
                              norm_weight=None, eps: float = 1e-6,
                              nope_dim: int = 0, rope_dim: int = 0,
                              lora_rank: int = 0):
    """fused_qkv_rope_append oracle: dense dequant + f32 qkv projection
    + rotate-half rope + at[].set paged row scatter.  Standard layout
    returns (q_roped, k_pages, v_pages); MLA (lora_rank > 0) returns
    (q with its rope tail rotated, pool) with the latent rms-normed by
    ``norm_weight`` before the [latent | rope-key] row lands."""
    T = h.shape[0]
    hf = h.astype(jnp.float32)
    p = hf @ _dequant_ref(w, scale, algo)
    c = cos.astype(jnp.float32)[:, None, :]           # [T, 1, d/2]
    s = sin.astype(jnp.float32)[:, None, :]
    if lora_rank:
        dh = nope_dim + rope_dim
        nq = heads * dh
        q = p[:, :nq].reshape(T, heads, dh)
        q = jnp.concatenate(
            [q[..., :nope_dim], _rotate_half(q[..., nope_dim:], c, s)],
            axis=-1)
        lat = p[:, nq:nq + lora_rank]
        var = jnp.mean(lat * lat, axis=-1, keepdims=True)
        lat = lat * jax.lax.rsqrt(var + eps) \
            * norm_weight.reshape(1, -1).astype(jnp.float32)
        k_pe = _rotate_half(p[:, None, nq + lora_rank:], c, s)[:, 0]
        rows = jnp.concatenate([lat, k_pe], axis=-1)[:, None, :]
        pool = k_pages.at[:, page_idx, page_off, :].set(
            rows.astype(k_pages.dtype).swapaxes(0, 1))
        return q.astype(h.dtype), pool
    if bias is not None:
        p = p + bias.reshape(1, -1).astype(jnp.float32)
    D = head_dim
    q = p[:, :heads * D].reshape(T, heads, D)
    k = p[:, heads * D:(heads + kv_heads) * D].reshape(T, kv_heads, D)
    v = p[:, (heads + kv_heads) * D:].reshape(T, kv_heads, D)
    qr = _rotate_half(q, c, s).astype(h.dtype)
    kr = _rotate_half(k, c, s)
    kp = k_pages.at[:, page_idx, page_off, :].set(
        kr.astype(k_pages.dtype).swapaxes(0, 1))
    vp = v_pages.at[:, page_idx, page_off, :].set(
        v.astype(v_pages.dtype).swapaxes(0, 1))
    return qr, kp, vp


def mla_decode_reference(q_eff, q_pe, c_lat, c_pe, lengths, *,
                         scale: float, block_t: int = 1024):
    del block_t  # tiling knob; irrelevant to the math
    s = (jnp.einsum("bhr,btr->bht", q_eff.astype(jnp.float32),
                    c_lat.astype(jnp.float32))
         + jnp.einsum("bhd,btd->bht", q_pe.astype(jnp.float32),
                      c_pe.astype(jnp.float32))) * scale
    T = c_lat.shape[1]
    dead = jnp.arange(T)[None, None, :] >= \
        lengths.astype(jnp.int32)[:, None, None]
    s = jnp.where(dead, -1e30, s)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(dead, 0.0, p)
    out = jnp.einsum("bht,btr->bhr", p, c_lat.astype(jnp.float32))
    return out.astype(c_lat.dtype)


def gmm_reference(lhs, rhs, group_sizes, block_m: int = 128,
                  block_n: int = 128):
    del block_m, block_n  # tiling knobs; irrelevant to the math
    M = lhs.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    rows = jnp.arange(M, dtype=jnp.int32)[:, None]
    member = ((rows >= starts[None, :])
              & (rows < ends[None, :])).astype(jnp.float32)   # [M, G]
    per_g = jnp.einsum("mk,gkn->mgn", lhs.astype(jnp.float32),
                       rhs.astype(jnp.float32))
    out = jnp.einsum("mgn,mg->mn", per_g, member)
    return out.astype(lhs.dtype)


def ssm_state_update_reference(pool, slots, n_live, xdt, dec, bh, ch, *,
                               layout: str = "heads_minor"):
    """One step of the Mamba-2 recurrence for the live slots (the first
    ``n_live`` of ``slots``); every other slot of the pool unchanged.
    ``layout="state_minor"``: pool [NS, H, P, N], ``bh`` / ``ch`` a
    group's rows [R, G, N] (`pallas_ssm`)."""
    NS = pool.shape[0]
    f32 = jnp.float32
    live = jnp.zeros(NS, bool).at[slots].max(
        jnp.arange(slots.shape[0]) < n_live[0])
    if layout == "state_minor":
        K = pool.shape[1] // bh.shape[1]
        bh, ch = (jnp.repeat(m[:NS].astype(f32), K, 1) for m in (bh, ch))
        new = (dec[:NS, 0][:, :, None, None] * pool
               + xdt[:NS].swapaxes(1, 2)[..., None] * bh[:, :, None])
        y = jnp.sum(new * ch[:, :, None], -1).swapaxes(1, 2)
        return (jnp.where(live[:, None, None], y, 0),
                jnp.where(live[:, None, None, None], new, pool))
    new = (dec[:NS, 0][:, None, None, :] * pool
           + xdt[:NS, :, None, :] * bh[:NS].astype(f32)[:, None])
    y = jnp.sum(new * ch[:NS].astype(f32)[:, None], axis=2)
    keep = live[:, None, None, None]
    return jnp.where(live[:, None, None], y, 0), jnp.where(keep, new, pool)


def ssm_state_put_reference(pool, slot, state):
    return jnp.where(slot[1] > 0,
                     pool.at[slot[0]].set(state.astype(pool.dtype)), pool)


def ssm_recurrence_reference(xdt, dA, bm, cm, state):
    """The Mamba-2 recurrence token by token (what
    `pallas_ssm.ssm_chunk_scan` and `ssm_state_update` are tested
    against): the operands of `ssm_chunk_scan`."""
    G = bm.shape[1]
    H = xdt.shape[1]
    K = H // G

    def step(s, row):
        x, a, b, c = row                    # [H, P], [H], [G, N], [G, N]
        bh, ch = jnp.repeat(b, K, 0), jnp.repeat(c, K, 0)   # [H, N]
        s = jnp.exp(a)[None, None, :] * s + jnp.einsum("hp,hn->pnh", x, bh)
        return s, jnp.einsum("pnh,hn->hp", s, ch,
                             precision=jax.lax.Precision.HIGHEST)

    f32 = jnp.float32
    state, y = jax.lax.scan(
        step, state.astype(f32),
        (xdt.astype(f32), dA.astype(f32), bm.astype(f32), cm.astype(f32)))
    return y, state


def ssm1_state_update_reference(pool, slots, n_live, dt, x, a, bm, cm):
    """One step of the Mamba-1 recurrence for the live slots (the first
    ``n_live`` of ``slots``) of the pool [NS, 1, N, C]; every other slot
    unchanged (`pallas_ssm.ssm1_state_update`'s operands)."""
    NS = pool.shape[0]
    f32 = jnp.float32
    live = jnp.zeros(NS, bool).at[slots].max(
        jnp.arange(slots.shape[0]) < n_live[0])
    dt, x = dt[:NS].astype(f32), x[:NS].astype(f32)
    new = (jnp.exp(dt[:, None, :] * a.astype(f32)[None]) * pool[:, 0]
           + (dt * x)[:, None, :] * bm[:NS].astype(f32)[:, :, None])
    y = jnp.sum(new * cm[:NS].astype(f32)[:, :, None], 1)
    return (jnp.where(live[:, None], y, 0),
            jnp.where(live[:, None, None, None], new[:, None], pool))


def ssm1_recurrence_reference(dt, x, a, bm, cm, state):
    """The Mamba-1 recurrence token by token (what
    `pallas_ssm.ssm1_chunk_scan` and `ssm1_state_update` are tested
    against): the operands of `ssm1_chunk_scan`."""
    f32 = jnp.float32
    a = a.astype(f32)

    def step(h, row):
        d, u, b, c = row                    # [C], [C], [N], [N]
        h = jnp.exp(d[None] * a) * h + (d * u)[None] * b[:, None]
        return h, jnp.sum(h * c[:, None], 0)

    h, y = jax.lax.scan(
        step, state.astype(f32).reshape(a.shape),
        (dt.astype(f32), x.astype(f32), bm.astype(f32), cm.astype(f32)))
    return y, h.reshape(state.shape)


def _kda_step(s, q, k, v, g, beta):
    """One token of the gated delta rule on states s [..., K, V]: q, k,
    g [..., K], v [..., V], beta [...] -> (o [..., V], the new state)."""
    hi = jax.lax.Precision.HIGHEST
    s = jnp.exp(g)[..., None] * s
    w = beta[..., None] * (v - jnp.einsum("...kv,...k->...v", s, k,
                                           precision=hi))
    s = s + k[..., None] * w[..., None, :]
    return jnp.einsum("...kv,...k->...v", s, q, precision=hi), s


def kda_state_update_reference(pool, slots, n_live, q, k, v, g, beta):
    """One step of the gated delta rule for the live slots (the first
    ``n_live`` of ``slots``); every other slot of the pool unchanged."""
    NS = pool.shape[0]
    live = jnp.zeros(NS, bool).at[slots].max(
        jnp.arange(slots.shape[0]) < n_live[0])
    f32 = jnp.float32
    o, new = _kda_step(pool, *(a[:NS].astype(f32) for a in (q, k, v, g)),
                       beta[:NS, :, 0].astype(f32))
    return (jnp.where(live[:, None, None], o, 0),
            jnp.where(live[:, None, None, None], new, pool))


def kda_recurrence_reference(q, k, v, g, beta, state):
    """The gated delta rule token by token (what
    `pallas_kda.kda_chunk_scan` and `kda_state_update` are tested
    against): the operands of `kda_chunk_scan`."""
    f32 = jnp.float32

    def step(s, row):
        o, s = _kda_step(s, *row)
        return s, o

    state, o = jax.lax.scan(step, state.astype(f32), tuple(
        a.astype(f32) for a in (q, k, v, g, beta)))
    return o, state


# -- manifold-constrained hyper-connections (`ops.pallas_mhc`) ---------
#: lanes of the coefficient rows `mhc_pre` hands to `mhc_post`
MHC_COEF_LANES = 128


def mhc_layout(n: int):
    """Rows of the packed mixing weights ``phi_t`` / ``ab`` for ``n``
    streams: (first row of the post group, first row of the residual
    group, rows in all). The pre group starts at row 0; each group
    starts at a multiple of 8, a whole sublane tile."""
    post0 = -(-n // 8) * 8
    res0 = 2 * post0
    return post0, res0, -(-(res0 + n * n) // 8) * 8


def mhc_pack(phi, b, a, n: int, dtype=None):
    """The published parameters of one sublayer's mixing — ``phi`` [n C,
    n^2 + 2 n] (columns: pre, post, residual row-major), ``b`` [n^2 +
    2 n], ``a`` [3] (a_pre, a_post, a_res) — as the kernels read them:
    ``phi_t`` [rows, n C] (``phi`` turned, a group a sublane tile,
    zeros between) in ``dtype`` and ``ab`` [rows, 128] float32 (lane 0
    the group's ``a``, lane 1 ``b``, the rest of the register zeros)."""
    post0, res0, rows = mhc_layout(n)
    at = {0: (0, n), post0: (n, 2 * n), res0: (2 * n, 2 * n + n * n)}
    phi_t = jnp.zeros((rows, phi.shape[0]), dtype or phi.dtype)
    ab = jnp.zeros((rows, MHC_COEF_LANES), jnp.float32)
    for g, (row, (c0, c1)) in enumerate(at.items()):
        phi_t = phi_t.at[row:row + c1 - c0].set(
            phi[:, c0:c1].T.astype(phi_t.dtype))
        ab = ab.at[row:row + c1 - c0, 0].set(a[g].astype(jnp.float32))
        ab = ab.at[row:row + c1 - c0, 1].set(b[c0:c1].astype(jnp.float32))
    return phi_t, ab


def sinkhorn_reference(z, iters: int, hc_eps: float):
    """exp(z) [..., n, n] projected towards the doubly stochastic
    matrices: ``iters`` times, columns normalised, then rows."""
    m = jnp.exp(z)
    for _ in range(iters):
        m = m / (jnp.sum(m, -2, keepdims=True) + hc_eps)
        m = m / (jnp.sum(m, -1, keepdims=True) + hc_eps)
    return m


def mhc_pre_reference(x, phi_t, ab, *, n: int, eps: float = 1e-6,
                      hc_eps: float = 1e-6, iters: int = 20,
                      clamp=(-30.0, 30.0)):
    """What a sublayer reads of the stream x [T, n C] (stream j in
    columns [j C, (j + 1) C)): ONE scalar norm a row over all n C
    columns, ``u = r (x phi)``, the three groups of coefficients, the
    Sinkhorn projection. -> (x_in [T, C] = sum_j Hpre_j x_j in x's
    type, coef [T, 128] float32: lanes [0, n) Hpost, [n, n + n^2) Hres
    row-major, then n lanes of Hpre)."""
    f32 = jnp.float32
    T = x.shape[0]
    C = x.shape[1] // n
    post0, res0, _ = mhc_layout(n)
    xf = x.astype(f32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    u = r * jnp.dot(xf, phi_t.astype(f32).T,
                    precision=jax.lax.Precision.HIGHEST)
    z = ab[:, 0] * u + ab[:, 1]
    hpre = jax.nn.sigmoid(z[:, :n])
    hpost = 2.0 * jax.nn.sigmoid(z[:, post0:post0 + n])
    hres = sinkhorn_reference(
        jnp.clip(z[:, res0:res0 + n * n], *clamp).reshape(T, n, n),
        iters, hc_eps)
    x_in = jnp.einsum("tj,tjc->tc", hpre, xf.reshape(T, n, C))
    coef = jnp.zeros((T, MHC_COEF_LANES), f32)
    coef = coef.at[:, :n].set(hpost)
    coef = coef.at[:, n:n + n * n].set(hres.reshape(T, n * n))
    coef = coef.at[:, n + n * n:2 * n + n * n].set(hpre)
    return x_in.astype(x.dtype), coef


def mhc_post_reference(x, y, coef, *, n: int):
    """The stream after a sublayer: x'_i = sum_j Hres[i, j] x_j +
    Hpost[i] y, accumulated in float32, in x's type."""
    f32 = jnp.float32
    T = x.shape[0]
    C = x.shape[1] // n
    hpost = coef[:, :n]
    hres = coef[:, n:n + n * n].reshape(T, n, n)
    out = jnp.einsum("tij,tjc->tic", hres, x.astype(f32).reshape(T, n, C)) \
        + hpost[:, :, None] * y.astype(f32)[:, None, :]
    return out.reshape(T, n * C).astype(x.dtype)
