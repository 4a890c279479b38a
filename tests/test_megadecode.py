"""Mega-kernel decode back half (ops/pallas_megadecode.py, ISSUE 14).

Interpret-mode parity of the two fused launches against their XLA
oracles (ops/references.py) across the four family geometries — fp
(bitwise), int8 and packed int4 (split-contraction reordering only) —
plus the eligibility gate's TPU tiling rules and the costmodel launch
accounting (5 launches/layer with both mega halves, 8 with either
alone, 11 split; 2 pallas_calls after attention).  Kernel-level only:
ServingEngine does not call these kernels (ROADMAP D11 deletes them
with these tests)."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas_megadecode import (fused_ffn, fused_oproj_norm,
                                              megadecode_eligible)
from paddle_tpu.ops.quant import weight_quantize
from paddle_tpu.ops.references import (megadecode_ffn_reference,
                                       oproj_norm_reference)


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _q(rng, K, N, algo):
    w = _rand(rng, K, N)
    qw, s = weight_quantize(w, algo=algo)
    return qw, s.astype(jnp.float32)


class TestOprojNormParity:
    """fused_oproj_norm vs oproj_norm_reference (the registered
    oracle): o-proj + bias + residual + rms/layer norm, both outputs."""

    # fp parity is ULP-level, not bitwise: the kernel body is one jitted
    # computation where XLA emits FMAs; the eager oracle runs op-by-op
    def _check(self, got, want, exact=True, atol=1e-4):
        for g, w in zip(got, want):
            if exact:
                np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                           atol=2e-6, rtol=2e-6)
            else:
                np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                           atol=atol, rtol=1e-5)

    # family geometries: (T, Ko, H) — llama-like (Ko == H), mla-like
    # (Ko = nh*dv != H), plus a non-128-multiple lane width (interpret
    # mode carries no lane constraint; TPU gates via megadecode_eligible)
    @pytest.mark.parametrize("T,Ko,H", [(8, 64, 64), (8, 48, 40),
                                        (16, 136, 24)])
    def test_fp_rms_exact(self, T, Ko, H):
        rng = np.random.default_rng(0)
        o, x = _rand(rng, T, Ko), _rand(rng, T, H)
        w, nw = _rand(rng, Ko, H), _rand(rng, H)
        got = fused_oproj_norm(o, x, w, norm_weight=nw, eps=1e-6)
        want = oproj_norm_reference(o, x, w, norm_weight=nw, eps=1e-6)
        self._check(got, want)

    def test_fp_layer_norm_bias_exact(self):
        # gpt geometry: o-proj bias + layer norm with weight AND bias
        rng = np.random.default_rng(1)
        T, Ko, H = 8, 64, 32
        o, x = _rand(rng, T, Ko), _rand(rng, T, H)
        w = _rand(rng, Ko, H)
        b, nw, nb = (_rand(rng, H) for _ in range(3))
        got = fused_oproj_norm(o, x, w, bias=b, norm_weight=nw,
                               norm_bias=nb, eps=1e-5, norm="layer")
        want = oproj_norm_reference(o, x, w, bias=b, norm_weight=nw,
                                    norm_bias=nb, eps=1e-5, norm="layer")
        self._check(got, want)

    @pytest.mark.parametrize("algo", ["weight_only_int8",
                                      "weight_only_int4"])
    def test_quantized_tracks_oracle(self, algo):
        rng = np.random.default_rng(2)
        T, Ko, H = 8, 64, 32
        o, x = _rand(rng, T, Ko), _rand(rng, T, H)
        qw, s = _q(rng, Ko, H, algo)
        nw = _rand(rng, H)
        got = fused_oproj_norm(o, x, qw, s, norm_weight=nw, algo=algo)
        want = oproj_norm_reference(o, x, qw, s, norm_weight=nw,
                                    algo=algo)
        # int4 contracts even/odd planes separately — summation-order
        # noise only vs the whole-dequant oracle
        self._check(got, want, exact=False)

    def test_batched_shape_roundtrip(self):
        # engine calls with flat [T, ...]; the public API also accepts
        # the [1, T, H] cached-body layout and returns it unchanged
        rng = np.random.default_rng(3)
        o, x = _rand(rng, 1, 8, 64), _rand(rng, 1, 8, 32)
        w, nw = _rand(rng, 64, 32), _rand(rng, 32)
        xn, h = fused_oproj_norm(o, x, w, norm_weight=nw)
        assert xn.shape == x.shape and h.shape == x.shape

    def test_zero_sentinel_rows_finite(self):
        # idle ragged slots feed all-zero rows (trash-page attention
        # output on a zeroed residual): the norm's eps must keep both
        # outputs finite and equal to the oracle's
        rng = np.random.default_rng(4)
        T, Ko, H = 8, 64, 32
        o, x = _rand(rng, T, Ko), _rand(rng, T, H)
        o = o.at[3:].set(0.0)
        x = x.at[3:].set(0.0)
        w, nw = _rand(rng, Ko, H), _rand(rng, H)
        got = fused_oproj_norm(o, x, w, norm_weight=nw)
        want = oproj_norm_reference(o, x, w, norm_weight=nw)
        assert all(bool(jnp.isfinite(g).all()) for g in got)
        self._check(got, want)

    def test_row_count_not_multiple_of_block(self):
        # T=5 falls through the whole block ladder to bt=1
        rng = np.random.default_rng(5)
        o, x = _rand(rng, 5, 16), _rand(rng, 5, 8)
        w, nw = _rand(rng, 16, 8), _rand(rng, 8)
        self._check(fused_oproj_norm(o, x, w, norm_weight=nw),
                    oproj_norm_reference(o, x, w, norm_weight=nw))


class TestFfnParity:
    """fused_ffn vs megadecode_ffn_reference: gate/up + activation +
    down-proj + residual in one launch."""

    # same ULP-level bar as TestOprojNormParity (FMA fusion drift only)
    def _check(self, got, want, exact=True, atol=1e-4):
        if exact:
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-6, rtol=2e-6)
        else:
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=atol, rtol=1e-5)

    # llama/mla swiglu geometry plus a non-128-multiple (even) ffn dim
    @pytest.mark.parametrize("T,H,I", [(8, 32, 64), (8, 40, 136)])
    def test_swiglu_fp_exact(self, T, H, I):
        rng = np.random.default_rng(10)
        h, x = _rand(rng, T, H), _rand(rng, T, H)
        wg, wu, wd = (_rand(rng, H, I), _rand(rng, H, I),
                      _rand(rng, I, H))
        got = fused_ffn(h, x, wg, None, wu, None, wd, None)
        want = megadecode_ffn_reference(h, x, wg, None, wu, None,
                                        wd, None)
        self._check(got, want)

    def test_gelu_bias_fp_exact(self):
        # gpt geometry: gelu(h @ wi + bi) @ wf + bf, both biases live
        rng = np.random.default_rng(11)
        T, H, I = 8, 32, 64
        h, x = _rand(rng, T, H), _rand(rng, T, H)
        wi, wf = _rand(rng, H, I), _rand(rng, I, H)
        bi, bf = _rand(rng, I), _rand(rng, H)
        got = fused_ffn(h, x, wi, None, None, None, wf, None, bi, bf,
                        act="gelu")
        want = megadecode_ffn_reference(h, x, wi, None, None, None,
                                        wf, None, bi, bf, act="gelu")
        self._check(got, want)

    @pytest.mark.parametrize("algo", ["weight_only_int8",
                                      "weight_only_int4"])
    def test_quantized_swiglu_tracks_oracle(self, algo):
        rng = np.random.default_rng(12)
        T, H, I = 8, 32, 64
        h, x = _rand(rng, T, H), _rand(rng, T, H)
        qg, sg = _q(rng, H, I, algo)
        qu, su = _q(rng, H, I, algo)
        qd, sd = _q(rng, I, H, algo)
        got = fused_ffn(h, x, qg, sg, qu, su, qd, sd, algo=algo)
        want = megadecode_ffn_reference(h, x, qg, sg, qu, su, qd, sd,
                                        algo=algo)
        self._check(got, want, exact=False)

    def test_int4_non_128_multiple_even_dims(self):
        # the packed layouts only need EVEN contraction dims off-TPU;
        # I=136 exercises the in-kernel scratch split at a non-128 lane
        rng = np.random.default_rng(13)
        T, H, I = 8, 24, 136
        h, x = _rand(rng, T, H), _rand(rng, T, H)
        qg, sg = _q(rng, H, I, "weight_only_int4")
        qu, su = _q(rng, H, I, "weight_only_int4")
        qd, sd = _q(rng, I, H, "weight_only_int4")
        got = fused_ffn(h, x, qg, sg, qu, su, qd, sd,
                        algo="weight_only_int4")
        want = megadecode_ffn_reference(h, x, qg, sg, qu, su, qd, sd,
                                        algo="weight_only_int4")
        self._check(got, want, exact=False)

    def test_int4_gelu_unsupported(self):
        rng = np.random.default_rng(14)
        h, x = _rand(rng, 8, 16), _rand(rng, 8, 16)
        qg, sg = _q(rng, 16, 32, "weight_only_int4")
        qd, sd = _q(rng, 32, 16, "weight_only_int4")
        with pytest.raises(NotImplementedError, match="swiglu"):
            fused_ffn(h, x, qg, sg, None, None, qd, sd,
                      algo="weight_only_int4", act="gelu")


class TestEligibility:
    """megadecode_eligible: always True in interpret mode; on TPU the
    128-lane / even-dim / VMEM-budget rules decide the fallback."""

    def test_interpret_mode_always_eligible(self):
        assert megadecode_eligible(24, 136, 40)

    def test_tpu_rules(self, monkeypatch):
        import paddle_tpu.ops.pallas_megadecode as md
        monkeypatch.setattr(md, "_interpret", lambda: False)
        # the llama3_8b 8-way shard geometry (SERVING_BENCH) tiles
        assert md.megadecode_eligible(512, 1792, 512)
        assert md.megadecode_eligible(512, 1792, 512, int4=True)
        # non-128 lane dims fall back
        assert not md.megadecode_eligible(520, 1792, 512)
        assert not md.megadecode_eligible(512, 1800, 512)
        assert not md.megadecode_eligible(512, 1792, 520)
        # unsharded llama3-8B blows the VMEM weight budget
        assert not md.megadecode_eligible(4096, 14336, 4096)


class TestLaunchAccounting:
    """costmodel.decode_layer_kernels fused modes: 5 launches per layer
    with both mega halves (the ISSUE 20 default), 8 with either half
    alone, 11 for the fully split chain — and the dual-ledger claim:
    the fused path's modeled HBM bytes are strictly below the split
    chain's at identical weights."""

    KW = dict(batch=8, context=256, hidden=512, heads=4, kv_heads=1,
              head_dim=128, intermediate=1792, page_size=32,
              weight_bytes_per_layer=8_000_000)

    @staticmethod
    def _total_bytes(decomp):
        return sum(n * (c.bytes_read + c.bytes_written)
                   for n, c in decomp["kernels"].values())

    def test_launch_counts(self):
        from paddle_tpu.observability import costmodel as cm
        both = cm.decode_layer_kernels(**self.KW)
        back = cm.decode_layer_kernels(megafront=False, **self.KW)
        front = cm.decode_layer_kernels(megadecode=False, **self.KW)
        old = cm.decode_layer_kernels(megadecode=False, megafront=False,
                                      **self.KW)
        # both halves: rms 1 + qkv_rope_append 1 + ragged 1 +
        # oproj_norm 1 + ffn 1 = 5; back only: rms 1 + qkv 3 + rope 1
        # + ragged 1 + oproj_norm 1 + ffn 1 = 8; front only: rms 2 +
        # qkv_rope_append 1 + ragged 1 + swiglu 1 + three back mats =
        # 8; split chain: rms 2 + six projections + rope 1 + ragged 1
        # + swiglu 1 = 11
        assert both["launches_per_layer"] == 5
        assert back["launches_per_layer"] == 8
        assert front["launches_per_layer"] == 8
        assert old["launches_per_layer"] == 11
        fused = {k: n for k, (n, _) in both["kernels"].items()
                 if k in ("fused_qkv_rope_append", "fused_oproj_norm",
                          "fused_ffn")}
        assert fused == {"fused_qkv_rope_append": 1,
                         "fused_oproj_norm": 1, "fused_ffn": 1}
        assert "swiglu" not in both["kernels"]
        assert "fused_rope_append" not in both["kernels"]
        assert "fused_rope_append" in back["kernels"]

    def test_fused_path_removes_intermediate_bytes(self):
        from paddle_tpu.observability import costmodel as cm
        both = cm.decode_layer_kernels(**self.KW)
        back = cm.decode_layer_kernels(megafront=False, **self.KW)
        old = cm.decode_layer_kernels(megadecode=False, megafront=False,
                                      **self.KW)
        # same real weight total crosses in every mode (the fused slabs
        # are carved out of weight_bytes_per_layer, not double-counted);
        # everything saved is intermediate activation traffic
        assert self._total_bytes(both) < self._total_bytes(back)
        assert self._total_bytes(back) < self._total_bytes(old)

    def test_quant_algo_shrinks_fused_weight_read(self):
        from paddle_tpu.observability import costmodel as cm
        kw = dict(self.KW)
        fp = cm.decode_layer_kernels(**kw)
        i4 = cm.decode_layer_kernels(quant_algo="weight_only_int4", **kw)
        wf = fp["kernels"]["fused_ffn"][1].breakdown["weights"]
        w4 = i4["kernels"]["fused_ffn"][1].breakdown["weights"]
        assert w4 < wf / 3       # packed nibbles: ~quarter of bf16
