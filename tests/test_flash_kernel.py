"""In-tree flash attention kernel (ops/pallas_flash.py — VERDICT r2
item 9; ref: paddle/phi/kernels/gpu/flash_attn_kernel.cu). The XLA
composite (sdpa_reference) is the correctness oracle per SURVEY §4.1.
Runs in Pallas interpret mode on CPU: same kernel logic as the TPU path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.flash_attention import sdpa_reference
from paddle_tpu.ops.pallas_flash import flash_sdpa, flash_kernel_eligible

B, H = 2, 4


def _qkv(Sq, Sk, D, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(B, Sq, H, D), dtype),
            jnp.asarray(rng.randn(B, Sk, H, D), dtype),
            jnp.asarray(rng.randn(B, Sk, H, D), dtype))


class TestForwardParity:
    @pytest.mark.parametrize("Sq,Sk,D,causal", [
        (256, 256, 128, False),
        (256, 256, 128, True),
        (256, 256, 64, True),      # D=64: MXU-eligible, bundled-refused D
        (128, 384, 128, True),     # causal Sq < Sk (bottom-right aligned)
        (384, 128, 128, True),     # causal Sq > Sk (head rows see nothing)
    ])
    def test_matches_composite(self, Sq, Sk, D, causal):
        q, k, v = _qkv(Sq, Sk, D)
        out = flash_sdpa(q, k, v, causal=causal)
        ref = sdpa_reference(q, k, v, causal=causal)
        out, ref = np.asarray(out), np.asarray(ref)
        if causal and Sk < Sq:
            # rows with no visible key are don't-care (composite yields a
            # uniform average; the kernel yields 0)
            valid = np.arange(Sq) + (Sk - Sq) >= 0
            out, ref = out[:, valid], ref[:, valid]
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_empty_rows_zero_not_nan(self):
        q, k, v = _qkv(384, 128, 128)
        out = np.asarray(flash_sdpa(q, k, v, causal=True))
        head = out[:, : 384 - 128]          # rows before the diagonal start
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(head, 0.0)

    def test_segment_ids_match_masked_composite(self):
        q, k, v = _qkv(256, 256, 128, seed=3)
        rng = np.random.RandomState(4)
        seg = jnp.asarray(rng.randint(0, 3, (B, 256)), jnp.int32)
        out = flash_sdpa(q, k, v, causal=True, segment_ids_q=seg,
                         segment_ids_kv=seg)
        mask = (seg[:, :, None] == seg[:, None, :])[:, None]
        ref = sdpa_reference(q, k, v, mask=mask, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_tunable_blocks_same_result(self):
        q, k, v = _qkv(512, 512, 64, seed=5)
        a = flash_sdpa(q, k, v, causal=True, block_q=128, block_k=128)
        b = flash_sdpa(q, k, v, causal=True, block_q=256, block_k=128)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


class TestBackwardParity:
    def test_grads_match_composite(self):
        q, k, v = _qkv(256, 256, 64, seed=7)

        def loss_kernel(q, k, v):
            return jnp.sum(flash_sdpa(q, k, v, causal=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(sdpa_reference(q, k, v, causal=True) ** 2)

        gk = jax.grad(loss_kernel, (0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_grads_unequal_causal(self):
        q, k, v = _qkv(128, 256, 128, seed=8)

        def loss_kernel(q, k, v):
            return jnp.sum(flash_sdpa(q, k, v, causal=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(sdpa_reference(q, k, v, causal=True) ** 2)

        gk = jax.grad(loss_kernel, (0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_segment_grads(self):
        q, k, v = _qkv(256, 256, 64, seed=9)
        rng = np.random.RandomState(10)
        seg = jnp.asarray(rng.randint(0, 2, (B, 256)), jnp.int32)
        mask = (seg[:, :, None] == seg[:, None, :])[:, None]

        def loss_kernel(q, k, v):
            return jnp.sum(flash_sdpa(q, k, v, segment_ids_q=seg,
                                      segment_ids_kv=seg) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(sdpa_reference(q, k, v, mask=mask) ** 2)

        gk = jax.grad(loss_kernel, (0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)


class TestEligibilityAndRouting:
    def test_eligibility_covers_bundled_refusals(self):
        # the whole point: causal Sq != Sk and D=64 are in
        assert flash_kernel_eligible(128, 384, 128)
        assert flash_kernel_eligible(256, 256, 64)
        assert not flash_kernel_eligible(200, 256, 128)   # not block-div
        assert not flash_kernel_eligible(256, 256, 96)    # bad head dim

    def test_flag_selects_impl(self):
        from paddle_tpu.flags import flag, flags_guard
        assert flag("FLAGS_flash_impl") == "intree"
        from paddle_tpu.ops.flash_attention import sdpa_path
        q, k, _ = _qkv(256, 256, 128)
        with flags_guard(flash_impl="composite"):
            assert sdpa_path(q, k, causal=True) == "composite"
        with flags_guard(flash_impl="bundled"):
            # bundled refuses unequal causal; intree (default) accepts
            qs, ks, _ = _qkv(128, 256, 128)
            assert sdpa_path(qs, ks, causal=True) == "composite"
        if jax.default_backend() == "tpu":
            qs, ks, _ = _qkv(128, 256, 128)
            assert sdpa_path(qs, ks, causal=True) == "flash"

    def test_bf16_inputs(self):
        q, k, v = _qkv(256, 256, 128, seed=11, dtype=jnp.bfloat16)
        out = flash_sdpa(q, k, v, causal=True)
        ref = sdpa_reference(q, k, v, causal=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)


# ---------------------------------------------------------------------------
# the visit table (ISSUE 49): hidden pairs are no grid step, interior pairs
# run unmasked, lse / di travel as dense rows
# ---------------------------------------------------------------------------

def _segments(S, cut):
    return jnp.broadcast_to((jnp.arange(S) >= cut).astype(jnp.int32), (B, S))


#: name -> (Sq, Sk, block_q, block_k, segment cut or None, dtype[, window])
GEOMETRIES = {
    # 3 x 3 blocks: hidden, interior and masked pairs all occur
    "causal_3x3": (192, 192, 64, 64, None, jnp.float32),
    # off = +112 / -112: a multiple of neither block
    "off_pos_unaligned": (128, 240, 64, 48, None, jnp.float32),
    "off_neg_unaligned": (240, 128, 48, 64, None, jnp.float32),
    "bq_lt_bk": (192, 192, 32, 64, None, jnp.float32),
    "bq_gt_bk": (192, 192, 64, 32, None, jnp.float32),
    # the boundary (32) falls inside key block 0, which query block 2
    # sees whole by the causal geometry: the pair must still be masked
    "segment_in_interior_pair": (192, 192, 64, 64, 32, jnp.float32),
    "bf16": (192, 192, 64, 64, None, jnp.bfloat16),
    # a sliding window (ISSUE 66): one more static bound of the SAME
    # table — shorter than a key block, a block exactly, longer than the
    # sequence (plain causal), across unequal blocks and lengths, and
    # under segment ids
    "window_lt_bk": (192, 192, 64, 64, None, jnp.float32, 24),
    "window_eq_bk": (192, 192, 64, 64, None, jnp.float32, 64),
    "window_gt_s": (192, 192, 64, 64, None, jnp.float32, 256),
    "window_two_blocks": (256, 256, 32, 64, None, jnp.float32, 100),
    "window_off_pos": (128, 240, 64, 48, None, jnp.float32, 70),
    "window_segments": (192, 192, 64, 64, 32, jnp.float32, 48),
    "window_bf16": (192, 192, 64, 64, None, jnp.bfloat16, 80),
}


def _case(name):
    Sq, Sk, bq, bk, cut, dtype, *window = GEOMETRIES[name]
    window = window[0] if window else None
    q, k, v = _qkv(Sq, Sk, 64, seed=13, dtype=dtype)
    kw = dict(causal=True, block_q=bq, block_k=bk, window=window)
    mask = None
    if cut is not None:
        sq, sk = _segments(Sq, cut), _segments(Sk, cut)
        kw.update(segment_ids_q=sq, segment_ids_kv=sk)
        mask = (sq[:, :, None] == sk[:, None, :])[:, None]
    # rows with no visible key are don't-care in the composite (a uniform
    # average) and zero in the kernel: compare and differentiate the rest
    valid = np.arange(Sq) + (Sk - Sq) >= 0
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4

    def kernel(q, k, v):
        return flash_sdpa(q, k, v, **kw)

    def reference(q, k, v):
        return sdpa_reference(q, k, v, mask=mask, causal=True,
                              window=window)
    return (q, k, v), kernel, reference, valid, tol


class TestVisitTable:
    @pytest.mark.parametrize("name", sorted(GEOMETRIES))
    def test_forward_matches_reference(self, name):
        qkv, kernel, reference, valid, tol = _case(name)
        out = np.asarray(kernel(*qkv), np.float32)
        ref = np.asarray(reference(*qkv), np.float32)
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(out[:, ~valid], 0.0)
        np.testing.assert_allclose(out[:, valid], ref[:, valid],
                                   atol=tol, rtol=tol)

    @pytest.mark.parametrize("name", sorted(GEOMETRIES))
    def test_gradients_match_reference(self, name):
        qkv, kernel, reference, valid, tol = _case(name)

        def loss(f):
            return lambda *a: jnp.sum(
                f(*a).astype(jnp.float32)[:, valid] ** 2)
        got = jax.grad(loss(kernel), (0, 1, 2))(*qkv)
        want = jax.grad(loss(reference), (0, 1, 2))(*qkv)
        for a, b in zip(got, want):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.isfinite(a).all()
            np.testing.assert_allclose(a, b, atol=10 * tol, rtol=10 * tol)
        # a row that sees nothing moves nothing
        np.testing.assert_array_equal(
            np.asarray(got[0], np.float32)[:, ~valid], 0.0)

    def test_hidden_rows_emit_the_lse_sentinel_as_dense_rows(self):
        from paddle_tpu.ops import pallas_flash
        q, k, v = (jnp.swapaxes(x, 1, 2) for x in _qkv(240, 128, 64))
        seg_q = jnp.zeros((B, 1, 240), jnp.int32)
        seg_kv = jnp.zeros((B, 1, 128), jnp.int32)
        o, lse = pallas_flash._flash_fwd_impl(
            q, k, v, seg_q, seg_kv, 0.125, True, 48, 64, False)
        assert lse.shape == (B, H, 1, 240)       # a row, not a column
        lse = np.asarray(lse)[:, :, 0]
        np.testing.assert_array_equal(lse[..., :112], np.float32(1e30))
        assert (np.abs(lse[..., 112:]) < 1e3).all()
        np.testing.assert_array_equal(np.asarray(o)[:, :, :112], 0.0)

    def test_kinds_follow_the_dense_mask_and_counts_add_up(self):
        from paddle_tpu.ops.pallas_flash import _pair_kind, _visit_table
        checked = 0
        for Sq, Sk, bq, bk in [(96, 96, 32, 32), (96, 96, 16, 48),
                               (96, 96, 48, 16), (64, 160, 32, 32),
                               (64, 176, 32, 16), (176, 64, 16, 32),
                               (144, 48, 48, 24), (48, 48, 48, 48)]:
            off, nq, nk = Sk - Sq, Sq // bq, Sk // bk
            sees = np.tril(np.ones((Sq, Sk), bool), k=off)
            kinds = {}
            for qi in range(nq):
                for kj in range(nk):
                    block = sees[qi * bq:(qi + 1) * bq,
                                 kj * bk:(kj + 1) * bk]
                    want = ("interior" if block.all() else
                            "masked" if block.any() else "skipped")
                    kinds[qi, kj] = _pair_kind(qi, kj, bq, bk, off, True,
                                               False)
                    assert kinds[qi, kj] == want, (Sq, Sk, bq, bk, qi, kj)
                    # segment ids are data: no visible pair is interior
                    assert _pair_kind(qi, kj, bq, bk, off, True, True) == (
                        "masked" if block.any() else "skipped")
                    # and without the causal mask every pair is interior
                    assert _pair_kind(qi, kj, bq, bk, off, False,
                                      False) == "interior"
                    checked += 1
            for order in ("qk", "kq"):
                (qi, kj, fl), counts = _visit_table(
                    nq, nk, bq, bk, off, True, False, order)
                assert sum(counts.values()) == nq * nk
                assert counts["skipped"] == nq * nk - len(fl)
                visible = {p for p, kind in kinds.items()
                           if kind != "skipped"}
                visited = set(zip(qi.tolist(), kj.tolist()))
                assert len(visited) == len(fl) and visible <= visited
                # a run = one output block: contiguous, opened by FIRST,
                # closed by LAST, and every output block has one
                run = qi if order == "qk" else kj
                starts = np.flatnonzero(fl & 1)
                ends = np.flatnonzero(fl & 2)
                assert len(starts) == len(ends) == (
                    nq if order == "qk" else nk)
                for a, b in zip(starts, ends):
                    assert a <= b and len(set(run[a:b + 1].tolist())) == 1
                assert sorted(run[starts].tolist()) == list(
                    range(len(starts)))
                # a forced pair (a run the mask leaves nothing of) is masked
                for n, pair in enumerate(zip(qi.tolist(), kj.tolist())):
                    if pair not in visible:
                        assert fl[n] == 1 | 2 | 4
                    else:
                        assert bool(fl[n] & 4) == (kinds[pair] == "masked")
        assert checked == 94

    @pytest.mark.parametrize("Sq,Sk,bq,bk,W", [
        (96, 96, 32, 32, 8), (96, 96, 32, 32, 32), (96, 96, 32, 32, 33),
        (96, 96, 16, 48, 40), (96, 96, 48, 16, 20), (64, 160, 32, 32, 50),
        (176, 64, 16, 32, 30), (96, 96, 32, 32, 500)])
    def test_window_kinds_follow_the_dense_band(self, Sq, Sk, bq, bk, W):
        from paddle_tpu.ops.pallas_flash import _pair_kind, _visit_table
        off, nq, nk = Sk - Sq, Sq // bq, Sk // bk
        sees = np.tril(np.ones((Sq, Sk), bool), k=off) \
            & ~np.tril(np.ones((Sq, Sk), bool), k=off - W)
        kinds = {}
        for qi in range(nq):
            for kj in range(nk):
                block = sees[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk]
                want = ("interior" if block.all() else
                        "masked" if block.any() else "skipped")
                kinds[qi, kj] = _pair_kind(qi, kj, bq, bk, off, True, False,
                                           W)
                assert kinds[qi, kj] == want, (qi, kj)
                assert _pair_kind(qi, kj, bq, bk, off, True, True, W) == (
                    "masked" if block.any() else "skipped")
        visible = {p for p, kind in kinds.items() if kind != "skipped"}
        for order in ("qk", "kq"):
            (qi, kj, fl), counts = _visit_table(
                nq, nk, bq, bk, off, True, False, order, W)
            visited = set(zip(qi.tolist(), kj.tolist()))
            assert sum(counts.values()) == nq * nk
            assert len(visited) == len(fl) and visible <= visited
            # a run the band leaves nothing of visits ONE forced pair
            assert len(visited - visible) <= (nq if order == "qk" else nk)
            for n, pair in enumerate(zip(qi.tolist(), kj.tolist())):
                if pair not in visible:
                    assert fl[n] == 1 | 2 | 4

    def test_window_counts_at_the_training_shape(self):
        """S = 8,192 in 512 x 512 blocks under W = 1,024 (Mellum2's
        sliding layers): a query block visits its own key block (the
        diagonal cuts it), the one before (whole) and the one before
        that (the band's edge cuts it) — 45 of 256 pairs against the
        causal table's 136, for both sweeps' orders."""
        from paddle_tpu.ops.pallas_flash import _visit_table
        for order in ("qk", "kq"):
            _, band = _visit_table(16, 16, 512, 512, 0, True, False, order,
                                   1024)
            assert band == {"interior": 15, "masked": 30, "skipped": 211}
            _, full = _visit_table(16, 16, 512, 512, 0, True, False, order)
            assert full == {"interior": 120, "masked": 16, "skipped": 120}

    def test_window_needs_causal(self):
        q, k, v = _qkv(64, 64, 64)
        with pytest.raises(ValueError, match="window"):
            flash_sdpa(q, k, v, causal=False, window=16)

    def test_counter_counts_a_launch_by_kind(self):
        from paddle_tpu.observability import registry, sample_values

        def read():
            flat = sample_values(registry())
            return {(k, kind): flat.get(
                'pt_flash_block_pairs_total{kernel="%s",kind="%s"}'
                % (k, kind), 0)
                for k in ("fwd", "dq", "dkv")
                for kind in ("interior", "masked", "skipped")}
        before = read()
        q, k, v = _qkv(256, 256, 64, seed=17)
        jax.grad(lambda q: flash_sdpa(q, k, v, causal=True, block_q=64,
                                      block_k=64).sum())(q)
        got = {key: val - before[key] for key, val in read().items()}
        # 4 x 4 pairs a (batch, head): 6 interior, 4 on the diagonal, 6
        # above it; the forward is traced twice (primal + the vjp's)
        for kernel in ("dq", "dkv"):
            assert got[kernel, "interior"] == 6 * B * H
            assert got[kernel, "masked"] == 4 * B * H
            assert got[kernel, "skipped"] == 6 * B * H
        assert got["fwd", "masked"] * 6 == got["fwd", "interior"] * 4 > 0
        assert got["fwd", "skipped"] == got["fwd", "interior"]

    def test_negative_scale_keeps_the_scaled_maximum(self):
        # the forward's running maximum is of the scores BEFORE the scale,
        # which rides in the exponent: a negative scale flips them first
        q, k, v = _qkv(128, 128, 64, seed=19)

        def loss(f):
            return lambda *a: jnp.sum(f(*a, causal=True, scale=-0.7) ** 2)
        got = jax.grad(loss(lambda *a, **kw: flash_sdpa(
            *a, block_q=32, block_k=32, **kw)), (0, 1, 2))(q, k, v)
        want = jax.grad(loss(sdpa_reference), (0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-3, rtol=1e-3)
