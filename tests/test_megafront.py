"""Mega-kernel decode front half (ops/pallas_megafront.py, ISSUE 20).

Interpret-mode parity of fused_qkv_rope_append against its XLA oracle
(ops/references.py qkv_rope_append_reference) across fp / int8 /
packed-int4 and the MLA layout — including non-128 dims and
trash-page sentinel table rows — plus the paged-append seeding
contract (partial-page walk across launches) and the eligibility
gate's TPU tiling rules.  Kernel-level only: ServingEngine does not
call this kernel (ROADMAP D11 deletes it with these tests)."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas_megafront import (fused_qkv_rope_append,
                                             megafront_eligible)
from paddle_tpu.ops.quant import weight_quantize
from paddle_tpu.ops.references import qkv_rope_append_reference


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _q(rng, K, N, algo):
    w = _rand(rng, K, N)
    qw, s = weight_quantize(w, algo=algo)
    return qw, s.astype(jnp.float32)


def _setup(rng, T, H, heads, kv_heads, D, total=5, psz=4):
    """Standard-layout operands with an adjacency-contract page walk
    (tokens sharing a page adjacent in t, pages 1.. so the engine's
    trash page 0 stays free for the sentinel tests)."""
    h = _rand(rng, T, H)
    w = _rand(rng, H, (heads + 2 * kv_heads) * D)
    cos, sin = _rand(rng, T, D // 2), _rand(rng, T, D // 2)
    kp = _rand(rng, kv_heads, total, psz, D)
    vp = _rand(rng, kv_heads, total, psz, D)
    page_idx = jnp.asarray([1 + t // psz for t in range(T)], jnp.int32)
    page_off = jnp.asarray([t % psz for t in range(T)], jnp.int32)
    return h, w, cos, sin, kp, vp, page_idx, page_off


class TestQkvRopeAppendParity:
    """fused_qkv_rope_append vs qkv_rope_append_reference (the
    registered oracle): fused projection + rope + paged K/V scatter,
    all three outputs."""

    def _check(self, got, want, atol=2e-6):
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=atol, rtol=atol)

    # family geometries incl. non-128 lane widths (interpret mode
    # carries no lane constraint; TPU gates via megafront_eligible)
    @pytest.mark.parametrize("T,H,heads,kv,D", [(8, 64, 4, 2, 16),
                                                (8, 40, 3, 1, 12),
                                                (4, 24, 2, 2, 8)])
    def test_fp_exact(self, T, H, heads, kv, D):
        rng = np.random.default_rng(0)
        h, w, cos, sin, kp, vp, pg, off = _setup(rng, T, H, heads, kv, D)
        kw = dict(heads=heads, kv_heads=kv, head_dim=D)
        got = fused_qkv_rope_append(h, w, None, None, cos, sin, kp, vp,
                                    pg, off, **kw)
        want = qkv_rope_append_reference(h, w, None, None, cos, sin,
                                         kp, vp, pg, off, **kw)
        self._check(got, want)

    def test_fp_gpt_bias_identity_trig(self):
        # gpt geometry: heads == kv_heads, qkv bias, identity trig
        rng = np.random.default_rng(1)
        T, H, nh, D = 8, 32, 2, 16
        h, w, _, _, kp, vp, pg, off = _setup(rng, T, H, nh, nh, D)
        b = _rand(rng, 3 * nh * D)
        cos = jnp.ones((T, D // 2), jnp.float32)
        sin = jnp.zeros((T, D // 2), jnp.float32)
        kw = dict(heads=nh, kv_heads=nh, head_dim=D)
        got = fused_qkv_rope_append(h, w, None, b, cos, sin, kp, vp,
                                    pg, off, **kw)
        want = qkv_rope_append_reference(h, w, None, b, cos, sin,
                                         kp, vp, pg, off, **kw)
        self._check(got, want)

    def test_int8_exact(self):
        rng = np.random.default_rng(2)
        T, H, heads, kv, D = 8, 64, 4, 2, 16
        h, _, cos, sin, kp, vp, pg, off = _setup(rng, T, H, heads, kv, D)
        qw, s = _q(rng, H, (heads + 2 * kv) * D, "weight_only_int8")
        kw = dict(heads=heads, kv_heads=kv, head_dim=D,
                  algo="weight_only_int8")
        got = fused_qkv_rope_append(h, qw, s, None, cos, sin, kp, vp,
                                    pg, off, **kw)
        want = qkv_rope_append_reference(h, qw, s, None, cos, sin,
                                         kp, vp, pg, off, **kw)
        self._check(got, want)

    @pytest.mark.parametrize("H", [64, 40])     # incl. non-128 dims
    def test_int4_tracks_oracle(self, H):
        rng = np.random.default_rng(3)
        T, heads, kv, D = 8, 4, 2, 16
        h, _, cos, sin, kp, vp, pg, off = _setup(rng, T, H, heads, kv, D)
        qw, s = _q(rng, H, (heads + 2 * kv) * D, "weight_only_int4")
        kw = dict(heads=heads, kv_heads=kv, head_dim=D,
                  algo="weight_only_int4")
        got = fused_qkv_rope_append(h, qw, s, None, cos, sin, kp, vp,
                                    pg, off, **kw)
        want = qkv_rope_append_reference(h, qw, s, None, cos, sin,
                                         kp, vp, pg, off, **kw)
        # int4 contracts even/odd planes separately — summation-order
        # noise only vs the whole-dequant oracle
        self._check(got, want, atol=1e-5)

    def test_sentinel_trash_page_rows(self):
        # inactive ragged slots interleave trash-page-0 visits between
        # real pages (the engine's sentinel table rows). The trash page
        # re-seeds on every revisit — its content is garbage by
        # contract — but the REAL pages and every q row must still
        # match the oracle at 2e-6.
        rng = np.random.default_rng(4)
        T, H, heads, kv, D = 6, 32, 2, 1, 16
        h, w, cos, sin, kp, vp, _, _ = _setup(rng, T, H, heads, kv, D)
        pg = jnp.asarray([0, 2, 2, 0, 3, 0], jnp.int32)
        off = jnp.asarray([0, 0, 1, 1, 0, 2], jnp.int32)
        kw = dict(heads=heads, kv_heads=kv, head_dim=D)
        q, kp2, vp2 = fused_qkv_rope_append(h, w, None, None, cos, sin,
                                            kp, vp, pg, off, **kw)
        qr, kpr, vpr = qkv_rope_append_reference(h, w, None, None, cos,
                                                 sin, kp, vp, pg, off,
                                                 **kw)
        self._check([q], [qr])
        real = np.asarray([2, 3])
        self._check([np.asarray(kp2)[:, real], np.asarray(vp2)[:, real]],
                    [np.asarray(kpr)[:, real], np.asarray(vpr)[:, real]])

    def test_partial_page_seeding_walk(self):
        # decode fills a page one token per step across SEPARATE
        # launches: each launch must seed the resident block from the
        # aliased input pool so earlier rows survive. Walk offsets
        # 0..3 of one page in four chained calls and compare the final
        # pool against the sequentially-applied oracle.
        rng = np.random.default_rng(5)
        T, H, heads, kv, D = 1, 32, 2, 1, 16
        h4 = _rand(rng, 4, H)
        w = _rand(rng, H, (heads + 2 * kv) * D)
        cos, sin = _rand(rng, 4, D // 2), _rand(rng, 4, D // 2)
        kp = _rand(rng, kv, 3, 4, D)
        vp = _rand(rng, kv, 3, 4, D)
        kpr, vpr = kp, vp
        kw = dict(heads=heads, kv_heads=kv, head_dim=D)
        pg = jnp.asarray([1], jnp.int32)
        for step in range(4):
            off = jnp.asarray([step], jnp.int32)
            h = h4[step:step + 1]
            c, s = cos[step:step + 1], sin[step:step + 1]
            _, kp, vp = fused_qkv_rope_append(h, w, None, None, c, s,
                                              kp, vp, pg, off, **kw)
            _, kpr, vpr = qkv_rope_append_reference(h, w, None, None,
                                                    c, s, kpr, vpr,
                                                    pg, off, **kw)
        self._check([kp, vp], [kpr, vpr])


class TestMlaLayout:
    """The MLA front: q (+rope tail) + kv_a projection + in-launch
    latent rms norm + [latent | rope-key] row append, one pool."""

    def _setup(self, rng, T=8, H=40, heads=2, dn=16, dr=8, r=12,
               total=4, psz=4):
        h = _rand(rng, T, H)
        w = _rand(rng, H, heads * (dn + dr) + r + dr)
        g = _rand(rng, r)
        cos, sin = _rand(rng, T, dr // 2), _rand(rng, T, dr // 2)
        pool = _rand(rng, 1, total, psz, r + dr)
        pg = jnp.asarray([1 + t // psz for t in range(T)], jnp.int32)
        off = jnp.asarray([t % psz for t in range(T)], jnp.int32)
        kw = dict(heads=heads, norm_weight=g, eps=1e-6, nope_dim=dn,
                  rope_dim=dr, lora_rank=r)
        return h, w, cos, sin, pool, pg, off, kw

    def _check(self, got, want, atol=2e-6):
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=atol, rtol=atol)

    def test_fp_exact(self):
        rng = np.random.default_rng(6)
        h, w, cos, sin, pool, pg, off, kw = self._setup(rng)
        got = fused_qkv_rope_append(h, w, None, None, cos, sin, pool,
                                    None, pg, off, **kw)
        want = qkv_rope_append_reference(h, w, None, None, cos, sin,
                                         pool, None, pg, off, **kw)
        self._check(got, want)

    def test_int8_exact(self):
        rng = np.random.default_rng(7)
        h, w, cos, sin, pool, pg, off, kw = self._setup(rng)
        qw, s = weight_quantize(w, algo="weight_only_int8")
        kw["algo"] = "weight_only_int8"
        got = fused_qkv_rope_append(h, qw, s.astype(jnp.float32), None,
                                    cos, sin, pool, None, pg, off, **kw)
        want = qkv_rope_append_reference(h, qw, s.astype(jnp.float32),
                                         None, cos, sin, pool, None,
                                         pg, off, **kw)
        self._check(got, want)

    def test_v_pages_rejected(self):
        rng = np.random.default_rng(8)
        h, w, cos, sin, pool, pg, off, kw = self._setup(rng)
        with pytest.raises(ValueError):
            fused_qkv_rope_append(h, w, None, None, cos, sin, pool,
                                  pool, pg, off, **kw)


class TestEligibility:
    """megafront_eligible: always True in interpret mode; on TPU the
    128-lane / even-contraction / VMEM-budget rules gate the default
    and the engine falls back to the split front."""

    def test_interpret_mode_always_eligible(self):
        assert megafront_eligible(40, 152, 12)

    def test_tpu_rules(self, monkeypatch):
        import paddle_tpu.ops.pallas_megafront as mf
        monkeypatch.setattr(mf, "_interpret", lambda: False)
        # the llama3_8b 8-way shard geometry (SERVING_BENCH) tiles
        assert mf.megafront_eligible(512, 768, 128)
        assert mf.megafront_eligible(512, 768, 128, int4=True)
        # non-128 lane dims fall back (the mla deploy N=3648 case)
        assert not mf.megafront_eligible(520, 768, 128)
        assert not mf.megafront_eligible(512, 760, 128)
        assert not mf.megafront_eligible(640, 3648, 192)
        # unsharded llama3-8B qkv slab blows the VMEM weight budget
        assert not mf.megafront_eligible(4096, 6144, 128)
