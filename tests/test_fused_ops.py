"""Pallas fused kernels vs XLA references (SURVEY §4.1 OpTest triangle:
output parity + gradient parity; kernels run in interpret mode on CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.fused import (fused_layer_norm, fused_rms_norm,
                                  fused_rope, swiglu)
from paddle_tpu.ops.quant import (weight_only_linear, weight_quantize,
                                  weight_dequantize)
from paddle_tpu.ops.paged_attention import (paged_attention,
                                            paged_attention_reference)


def _r(*shape, seed=0, scale=1.0):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*shape).astype(np.float32) * scale)


class TestRmsNorm:
    def test_matches_reference(self):
        x = _r(4, 16, 64, seed=1)
        w = _r(64, seed=2) * 0.1 + 1.0
        out = fused_rms_norm(x, w, eps=1e-6)
        xf = x.astype(jnp.float32)
        ref = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6) * w
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_grad_matches_autodiff_reference(self):
        x = _r(8, 32, seed=3)
        w = _r(32, seed=4) * 0.1 + 1.0

        def f_fused(x, w):
            return jnp.sum(fused_rms_norm(x, w) ** 2)

        def f_ref(x, w):
            y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w
            return jnp.sum(y ** 2)
        gx1, gw1 = jax.grad(f_fused, argnums=(0, 1))(x, w)
        gx2, gw2 = jax.grad(f_ref, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw2),
                                   rtol=1e-4, atol=1e-5)

    def test_ulp_equal_to_inline_f32(self):
        # the serving engine's bodies route their inline rms through
        # this kernel: same op order (x * rsqrt(mean(x^2) + eps) * w),
        # so any difference is last-ulp reduction/FMA reassociation —
        # the engine-vs-solo exactness contract (greedy TOKEN equality)
        # is checked end-to-end in test_serving_engine.py
        x = _r(3, 7, 48, seed=5)
        w = _r(48, seed=6) * 0.1 + 1.0
        eps = 1e-6
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1,
                       keepdims=True)
        inline = (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * w
        np.testing.assert_allclose(
            np.asarray(fused_rms_norm(x, w, eps)), np.asarray(inline),
            rtol=1e-6, atol=1e-6)


class TestLayerNorm:
    def test_matches_reference(self):
        x = _r(6, 48, seed=5)
        w = _r(48, seed=6) * 0.1 + 1.0
        b = _r(48, seed=7) * 0.1
        out = fused_layer_norm(x, w, b)
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        ref = (x - mu) * jax.lax.rsqrt(var + 1e-5) * w + b
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_ulp_equal_to_inline_f32(self):
        # the serving engine's GPT bodies route their inline ln through
        # this kernel: same op order, so differences are last-ulp only
        # (greedy token-level exactness checked in test_serving_engine)
        x = _r(2, 5, 32, seed=8)
        w = _r(32, seed=9) * 0.1 + 1.0
        b = _r(32, seed=10) * 0.1
        eps = 1e-5
        h32 = x.astype(jnp.float32)
        mu = jnp.mean(h32, -1, keepdims=True)
        var = jnp.var(h32, -1, keepdims=True)
        inline = (((h32 - mu) * jax.lax.rsqrt(var + eps))
                  .astype(x.dtype) * w + b)
        np.testing.assert_allclose(
            np.asarray(fused_layer_norm(x, w, b, eps)),
            np.asarray(inline), rtol=1e-6, atol=1e-6)


class TestRope:
    def test_matches_model_reference(self):
        from paddle_tpu.models.llama import apply_rope, precompute_rope
        B, S, H, D = 2, 16, 4, 32
        q, k = _r(B, S, H, D, seed=8), _r(B, S, H, D, seed=9)
        cos, sin = precompute_rope(D, S, 10000.0)
        q2, k2 = fused_rope(q, k, cos, sin)
        np.testing.assert_allclose(np.asarray(q2),
                                   np.asarray(apply_rope(q, cos, sin)),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(k2),
                                   np.asarray(apply_rope(k, cos, sin)),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_is_inverse_rotation(self):
        from paddle_tpu.models.llama import precompute_rope
        B, S, H, D = 1, 8, 2, 16
        q = _r(B, S, H, D, seed=10)
        cos, sin = precompute_rope(D, S, 10000.0)

        def f(q):
            out, _ = fused_rope(q, q, cos, sin)
            return jnp.sum(out ** 2)
        g = jax.grad(f)(q)
        assert np.isfinite(np.asarray(g)).all()
        # rotation is orthogonal: |grad| == |2*rope(q)|
        out, _ = fused_rope(q, q, cos, sin)
        np.testing.assert_allclose(float(jnp.linalg.norm(g)),
                                   float(jnp.linalg.norm(2 * out)),
                                   rtol=1e-4)


class TestSwiglu:
    def test_matches_reference_both_signatures(self):
        g, u = _r(4, 32, seed=11), _r(4, 32, seed=12)
        ref = jax.nn.silu(g) * u
        np.testing.assert_allclose(np.asarray(swiglu(g, u)), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
        packed = jnp.concatenate([g, u], axis=-1)
        np.testing.assert_allclose(np.asarray(swiglu(packed)),
                                   np.asarray(ref), rtol=1e-5, atol=1e-6)

    def test_grad_matches(self):
        g, u = _r(4, 16, seed=13), _r(4, 16, seed=14)
        g1 = jax.grad(lambda a, b: jnp.sum(swiglu(a, b) ** 2),
                      argnums=(0, 1))(g, u)
        g2 = jax.grad(lambda a, b: jnp.sum((jax.nn.silu(a) * b) ** 2),
                      argnums=(0, 1))(g, u)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestWeightOnly:
    def test_int8_quant_roundtrip_small_error(self):
        w = _r(64, 32, seed=15)
        qw, scale = weight_quantize(w, "weight_only_int8")
        assert qw.dtype == jnp.int8
        deq = weight_dequantize(qw, scale, "weight_only_int8")
        err = float(jnp.abs(deq - w).max() / jnp.abs(w).max())
        assert err < 0.01

    def test_int8_linear_close_to_fp(self):
        x = _r(8, 64, seed=16, scale=0.5)
        w = _r(64, 32, seed=17, scale=0.5)
        b = _r(32, seed=18, scale=0.1)
        qw, scale = weight_quantize(w, "weight_only_int8")
        out = weight_only_linear(x, qw, scale, bias=b)
        ref = x @ w + b
        rel = float(jnp.abs(out - ref).max() /
                    (jnp.abs(ref).max() + 1e-6))
        assert rel < 0.02, rel

    def test_int4_linear_runs(self):
        x = _r(4, 16, seed=19, scale=0.5)
        w = _r(16, 8, seed=20, scale=0.5)
        qw, scale = weight_quantize(w, "weight_only_int4")
        assert qw.shape == (8, 8)  # packed
        out = weight_only_linear(x, qw, scale, algo="weight_only_int4")
        ref = x @ w
        rel = float(jnp.abs(out - ref).max() / (jnp.abs(ref).max() + 1e-6))
        assert rel < 0.2  # int4 tolerance


class TestPagedAttention:
    def _setup(self, B=2, H=4, KV=2, D=16, page_size=4, pages_per_seq=3,
               seed=21):
        rng = np.random.RandomState(seed)
        total_pages = B * pages_per_seq
        kp = jnp.asarray(rng.randn(KV, total_pages, page_size, D)
                         .astype(np.float32) * 0.3)
        vp = jnp.asarray(rng.randn(KV, total_pages, page_size, D)
                         .astype(np.float32) * 0.3)
        pi = jnp.asarray(
            rng.permutation(total_pages).reshape(B, pages_per_seq)
            .astype(np.int32))
        lengths = jnp.asarray([7, 10], jnp.int32)
        q = jnp.asarray(rng.randn(B, H, D).astype(np.float32) * 0.3)
        return q, kp, vp, lengths, pi

    def test_reference_matches_dense(self):
        q, kp, vp, lengths, pi = self._setup()
        out = paged_attention_reference(q, kp, vp, lengths, pi)
        # dense check for sequence 0
        B, H, D = q.shape
        KV, _, psize, _ = kp.shape
        L = int(lengths[0])
        k_seq = np.concatenate([np.asarray(kp[:, int(p)]) for p in pi[0]],
                               axis=1)[:, :L]     # [KV, L, D]
        v_seq = np.concatenate([np.asarray(vp[:, int(p)]) for p in pi[0]],
                               axis=1)[:, :L]
        rep = H // KV
        k_seq = np.repeat(k_seq, rep, axis=0)
        v_seq = np.repeat(v_seq, rep, axis=0)
        s = np.einsum("hd,hkd->hk", np.asarray(q[0]), k_seq) * D ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref0 = np.einsum("hk,hkd->hd", p, v_seq)
        np.testing.assert_allclose(np.asarray(out[0]), ref0, rtol=1e-4,
                                   atol=1e-5)

    def test_public_entry_runs(self):
        q, kp, vp, lengths, pi = self._setup(seed=22)
        out = paged_attention(q, kp, vp, lengths, pi)
        assert out.shape == q.shape
        assert np.isfinite(np.asarray(out)).all()
