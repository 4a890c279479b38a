"""Long-tail batch 2 through the OpTest triangle (VERDICT r1 item 8;
ref: python/paddle/tensor math/manipulation/inplace surfaces +
paddle.linalg tail)."""

import numpy as np
import pytest
import scipy.special as sps

import paddle_tpu as paddle
from op_test import OpCase, run_case

R = np.random.RandomState(0)
A = R.standard_normal((4, 5)).astype(np.float32)
B = R.standard_normal((4, 5)).astype(np.float32)
POS = np.abs(A) + 0.5


CASES = [
    OpCase("copysign", paddle.copysign, np.copysign, [A, B],
           grad_inputs=[0]),
    OpCase("gammaln", paddle.gammaln, sps.gammaln, [POS]),
    OpCase("gammainc", paddle.gammainc, sps.gammainc, [POS, POS + 1],
           grad_rtol=0.1, check_grad=False),
    OpCase("gammaincc", paddle.gammaincc, sps.gammaincc, [POS, POS + 1],
           check_grad=False),
    OpCase("i0e", paddle.i0e, sps.i0e, [A]),
    OpCase("i1e", paddle.i1e, sps.i1e, [A], check_grad=False),
    OpCase("sigmoid", paddle.sigmoid,
           lambda x: 1 / (1 + np.exp(-x)), [A]),
    OpCase("baddbmm", paddle.baddbmm,
           lambda i, x, y, beta=1.0, alpha=1.0: beta * i + alpha * x @ y,
           [R.standard_normal((2, 3, 5)).astype(np.float32),
            R.standard_normal((2, 3, 4)).astype(np.float32),
            R.standard_normal((2, 4, 5)).astype(np.float32)],
           attrs=dict(beta=0.5, alpha=2.0)),
    OpCase("cumulative_trapezoid", paddle.cumulative_trapezoid,
           lambda y, dx=1.0, axis=-1:
           __import__("scipy.integrate", fromlist=["x"])
           .cumulative_trapezoid(y, dx=dx, axis=axis),
           [A], attrs=dict(dx=0.5)),
    OpCase("bitwise_left_shift", paddle.bitwise_left_shift,
           np.left_shift,
           [np.array([1, 2, 4], np.int32), np.array([2, 1, 3], np.int32)],
           check_grad=False),
    OpCase("bitwise_right_shift", paddle.bitwise_right_shift,
           np.right_shift,
           [np.array([8, 16, 4], np.int32), np.array([2, 1, 2], np.int32)],
           check_grad=False),
    OpCase("take_along_dim", paddle.take_along_dim,
           lambda x, i, dim=0: np.take_along_axis(x, i, dim),
           [A, np.argsort(A, 0)], attrs=dict(dim=0), check_grad=False),
    OpCase("multigammaln", paddle.multigammaln,
           lambda x, p: sps.multigammaln(x, p), [POS + 2],
           attrs=dict(p=3), check_grad=False),
]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_op_cases(case):
    run_case(case)


class TestStackFamily:
    def test_stacks_match_numpy(self):
        xs = [R.standard_normal((3, 4)).astype(np.float32)
              for _ in range(3)]
        ts = [paddle.to_tensor(x) for x in xs]
        np.testing.assert_allclose(paddle.hstack(ts).numpy(),
                                   np.hstack(xs))
        np.testing.assert_allclose(paddle.vstack(ts).numpy(),
                                   np.vstack(xs))
        np.testing.assert_allclose(paddle.dstack(ts).numpy(),
                                   np.dstack(xs))
        np.testing.assert_allclose(paddle.column_stack(ts).numpy(),
                                   np.column_stack(xs))
        np.testing.assert_allclose(paddle.row_stack(ts).numpy(),
                                   np.vstack(xs))

    def test_block_diag_and_combinations(self):
        import scipy.linalg as sl
        xs = [R.standard_normal((2, 2)).astype(np.float32),
              R.standard_normal((3, 1)).astype(np.float32)]
        got = paddle.block_diag([paddle.to_tensor(x) for x in xs]).numpy()
        np.testing.assert_allclose(got, sl.block_diag(*xs))
        c = paddle.combinations(paddle.to_tensor(
            np.asarray([5, 6, 7, 8], np.int32)), r=2).numpy()
        import itertools
        ref = np.asarray(list(itertools.combinations([5, 6, 7, 8], 2)))
        np.testing.assert_array_equal(c, ref)


class TestPredicatesAndMisc:
    def test_inf_predicates(self):
        x = paddle.to_tensor(np.array([1.0, -np.inf, np.inf, np.nan],
                                      np.float32))
        np.testing.assert_array_equal(paddle.isneginf(x).numpy(),
                                      [False, True, False, False])
        np.testing.assert_array_equal(paddle.isposinf(x).numpy(),
                                      [False, False, True, False])
        assert paddle.isreal(x).numpy().all()

    def test_isin_frexp_nanarg(self):
        x = paddle.to_tensor(np.array([1, 2, 3, 4], np.int32))
        np.testing.assert_array_equal(
            paddle.isin(x, paddle.to_tensor(
                np.array([2, 4], np.int32))).numpy(),
            [False, True, False, True])
        m, e = paddle.frexp(paddle.to_tensor(np.array([8.0, 0.5],
                                                      np.float32)))
        np.testing.assert_allclose(m.numpy() * 2.0 ** e.numpy(),
                                   [8.0, 0.5])
        y = paddle.to_tensor(np.array([[1.0, np.nan, 3.0]], np.float32))
        assert int(paddle.nanargmax(y, axis=1).numpy()[0]) == 2
        assert int(paddle.nanargmin(y, axis=1).numpy()[0]) == 0

    def test_histograms(self):
        x = paddle.to_tensor(R.standard_normal(100).astype(np.float32))
        edges = paddle.histogram_bin_edges(x, bins=10).numpy()
        assert edges.shape == (11,)
        pts = paddle.to_tensor(R.standard_normal((50, 2))
                               .astype(np.float32))
        hist, ed = paddle.histogramdd(pts, bins=4)
        assert hist.numpy().shape == (4, 4)
        assert float(hist.numpy().sum()) == 50.0

    def test_diagonal_scatter_and_fill_diagonal(self):
        x = paddle.to_tensor(np.zeros((3, 4), np.float32))
        y = paddle.to_tensor(np.array([1.0, 2.0, 3.0], np.float32))
        out = paddle.diagonal_scatter(x, y).numpy()
        np.testing.assert_allclose(np.diagonal(out), [1, 2, 3])
        z = paddle.to_tensor(np.zeros((3, 3), np.float32))
        paddle.fill_diagonal_(z, 7.0)
        np.testing.assert_allclose(np.diagonal(z.numpy()), 7.0)
        z2 = paddle.to_tensor(np.zeros((3, 3), np.float32))
        paddle.fill_diagonal_(z2, 5.0, offset=1)
        np.testing.assert_allclose(z2.numpy()[0, 1], 5.0)
        assert z2.numpy()[0, 0] == 0


class TestInplaceFamily:
    def test_unary_inplace_rebinds(self):
        x = paddle.to_tensor(np.array([1.0, 4.0, 9.0], np.float32))
        ret = paddle.sqrt_(x)
        assert ret is x
        np.testing.assert_allclose(x.numpy(), [1.0, 2.0, 3.0])
        paddle.exp_(x)
        np.testing.assert_allclose(x.numpy(), np.exp([1.0, 2.0, 3.0]),
                                   rtol=1e-6)
        paddle.zero_(x)
        np.testing.assert_allclose(x.numpy(), 0.0)
        paddle.fill_(x, 2.5)
        np.testing.assert_allclose(x.numpy(), 2.5)

    def test_structured_inplace(self):
        x = paddle.to_tensor(R.standard_normal((3, 3)).astype(np.float32))
        ref = np.tril(x.numpy(), -1)
        paddle.tril_(x, diagonal=-1)
        np.testing.assert_allclose(x.numpy(), ref)
        y = paddle.to_tensor(np.zeros((4,), np.float32))
        paddle.index_put_(y, [paddle.to_tensor(
            np.array([1, 3], np.int64))],
            paddle.to_tensor(np.array([5.0, 6.0], np.float32)))
        np.testing.assert_allclose(y.numpy(), [0, 5, 0, 6])
        paddle.index_put_(y, [paddle.to_tensor(
            np.array([1], np.int64))],
            paddle.to_tensor(np.array([1.0], np.float32)),
            accumulate=True)
        np.testing.assert_allclose(y.numpy(), [0, 6, 0, 6])

    def test_methods_mounted(self):
        x = paddle.to_tensor(np.array([4.0], np.float32))
        x.sqrt_()
        np.testing.assert_allclose(x.numpy(), [2.0])
        assert hasattr(x, "tanh_") and hasattr(x, "fill_diagonal_")

    def test_random_inplace(self):
        x = paddle.to_tensor(np.zeros((1000,), np.float32))
        paddle.cauchy_(x)
        v = x.numpy()
        assert np.isfinite(v).all() and np.abs(v).max() > 3  # heavy tails
        g = paddle.to_tensor(np.zeros((1000,), np.float32))
        paddle.geometric_(g, 0.3)
        gv = g.numpy()
        assert gv.min() >= 1 and 2.0 < gv.mean() < 5.0  # E=1/0.3


class TestLinalgTail:
    def test_vector_matrix_norms(self):
        import paddle_tpu.linalg as L
        x = paddle.to_tensor(A)
        np.testing.assert_allclose(
            float(L.vector_norm(x, 2).numpy()),
            np.linalg.norm(A.ravel()), rtol=1e-5)
        np.testing.assert_allclose(
            L.matrix_norm(x, "fro").numpy(), np.linalg.norm(A, "fro"),
            rtol=1e-5)
        np.testing.assert_allclose(
            L.matrix_norm(x, 2).numpy(), np.linalg.norm(A, 2), rtol=1e-5)
        np.testing.assert_allclose(
            L.matrix_norm(x, 1).numpy(), np.linalg.norm(A, 1), rtol=1e-5)
        np.testing.assert_allclose(
            L.matrix_norm(x, np.inf).numpy(),
            np.linalg.norm(A, np.inf), rtol=1e-5)

    def test_svdvals_matrix_exp_transpose_vecdot(self):
        import paddle_tpu.linalg as L
        import scipy.linalg as sl
        x = paddle.to_tensor(A)
        np.testing.assert_allclose(L.svdvals(x).numpy(),
                                   np.linalg.svd(A, compute_uv=False),
                                   rtol=1e-4)
        sq = A[:4, :4]
        np.testing.assert_allclose(
            L.matrix_exp(paddle.to_tensor(sq)).numpy(), sl.expm(sq),
            rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(L.matrix_transpose(x).numpy(), A.T)
        np.testing.assert_allclose(
            L.vecdot(x, paddle.to_tensor(B)).numpy(),
            (A * B).sum(-1), rtol=1e-5)

    def test_eig_and_cholesky_inverse(self):
        import paddle_tpu.linalg as L
        sq = (A[:4, :4] + A[:4, :4].T) / 2 + 4 * np.eye(4, dtype=np.float32)
        w, v = L.eig(paddle.to_tensor(sq))
        wr = np.sort(np.real(w.numpy()))
        np.testing.assert_allclose(wr, np.sort(np.linalg.eigvalsh(sq)),
                                   rtol=1e-4)
        ch = np.linalg.cholesky(sq)
        np.testing.assert_allclose(
            L.cholesky_inverse(paddle.to_tensor(ch)).numpy(),
            np.linalg.inv(sq), rtol=1e-3, atol=1e-4)

    def test_ormqr_and_svd_lowrank(self):
        import paddle_tpu.linalg as L
        import scipy.linalg as sl
        sq = A[:4, :4]
        (h, tau), _ = sl.qr(sq, mode="raw")
        h = np.asarray(h, np.float32)
        tau = np.asarray(tau, np.float32)
        other = paddle.to_tensor(B[:4, :4])
        got = L.ormqr(paddle.to_tensor(h), paddle.to_tensor(tau),
                      other).numpy()
        import jax
        import jax.numpy as jnp
        qfull = np.asarray(jax.lax.linalg.householder_product(
            jnp.asarray(h), jnp.asarray(tau)))
        np.testing.assert_allclose(got, qfull @ B[:4, :4], rtol=1e-4,
                                   atol=1e-4)
        big = R.standard_normal((20, 8)).astype(np.float32)
        # the sketch comes from the global generator: one draw in ten
        # loses the smallest direction to float32 (error over 1e-3), and
        # which draw a worker makes here depends on the files before it
        paddle.seed(7)
        u, s, v = L.svd_lowrank(paddle.to_tensor(big), q=8)
        np.testing.assert_allclose(
            u.numpy() @ np.diag(s.numpy()) @ v.numpy().T, big,
            rtol=1e-3, atol=1e-3)

    def test_lu_unpack(self):
        import paddle_tpu.linalg as L
        sq = A[:4, :4] + 3 * np.eye(4, dtype=np.float32)
        lu, piv = L.lu(paddle.to_tensor(sq))
        P, Lm, U = L.lu_unpack(lu, piv)
        np.testing.assert_allclose(
            P.numpy() @ Lm.numpy() @ U.numpy(), sq, rtol=1e-4, atol=1e-4)


class TestReviewRegressions:
    def test_ormqr_nonsquare(self):
        import scipy.linalg as sl
        import paddle_tpu.linalg as L
        tall = R.standard_normal((5, 3)).astype(np.float32)
        (h, tau), _ = sl.qr(tall, mode="raw")
        h = np.asarray(h, np.float32)
        tau = np.asarray(tau, np.float32)
        other = R.standard_normal((5, 2)).astype(np.float32)
        qfull, _ = sl.qr(tall)  # full 5x5 Q
        got = L.ormqr(paddle.to_tensor(h), paddle.to_tensor(tau),
                      paddle.to_tensor(other)).numpy()
        # LAPACK's raw-h reflections reproduce Q up to its construction;
        # check the defining property instead: result == Q_full @ other
        np.testing.assert_allclose(got, qfull @ other, rtol=1e-4,
                                   atol=1e-4)
        gotT = L.ormqr(paddle.to_tensor(h), paddle.to_tensor(tau),
                       paddle.to_tensor(other), transpose=True).numpy()
        np.testing.assert_allclose(gotT, qfull.T @ other, rtol=1e-4,
                                   atol=1e-4)
        right = L.ormqr(paddle.to_tensor(h), paddle.to_tensor(tau),
                        paddle.to_tensor(other.T), left=False).numpy()
        np.testing.assert_allclose(right, other.T @ qfull, rtol=1e-4,
                                   atol=1e-4)

    def test_matrix_norm_keepdim_axis_positions(self):
        import paddle_tpu.linalg as L
        x = R.standard_normal((3, 4, 5)).astype(np.float32)
        out = L.matrix_norm(paddle.to_tensor(x), "nuc", axis=(0, 1),
                            keepdim=True)
        assert tuple(out.shape) == (1, 1, 5), out.shape
        out2 = L.matrix_norm(paddle.to_tensor(x), 2, axis=(0, 1),
                             keepdim=True)
        assert tuple(out2.shape) == (1, 1, 5), out2.shape

    def test_svd_lowrank_differentiable(self):
        import paddle_tpu.linalg as L
        x = paddle.to_tensor(R.standard_normal((8, 5)).astype(np.float32))
        x.stop_gradient = False
        u, s, v = L.svd_lowrank(x, q=5)
        s.sum().backward()
        assert x.grad is not None
        assert float(np.abs(np.asarray(x.grad._data)).sum()) > 0

    def test_inplace_batch2_methods_mounted(self):
        x = paddle.to_tensor(np.array([-1.0, 2.0], np.float32))
        x.abs_()
        np.testing.assert_allclose(x.numpy(), [1.0, 2.0])
        x.log_()
        np.testing.assert_allclose(x.numpy(), np.log([1.0, 2.0]),
                                   rtol=1e-6)

    def test_fill_diagonal_wrap(self):
        x = paddle.to_tensor(np.zeros((7, 3), np.float32))
        paddle.fill_diagonal_(x, 1.0, wrap=True)
        ref = np.zeros((7, 3), np.float32)
        np.fill_diagonal(ref, 1.0, wrap=True)
        np.testing.assert_allclose(x.numpy(), ref)

    def test_inplace_on_grad_tensor_raises(self):
        # silently-corrupted gradients are worse than an error: in-place
        # on a grad-requiring tensor must refuse
        w = paddle.to_tensor(np.array([4.0], np.float32))
        w.stop_gradient = False
        x = w * 2
        with pytest.raises(RuntimeError, match="in-place"):
            x.sqrt_()
        from paddle_tpu.core import autograd as ag
        with ag.no_grad():
            x.sqrt_()  # fine under no_grad
        np.testing.assert_allclose(x.numpy(), [np.sqrt(8.0)], rtol=1e-6)

    def test_sdpa_reference_float_sq_sk_mask_keeps_broadcast(self):
        from paddle_tpu.ops.flash_attention import sdpa_reference
        import jax.numpy as jnp
        S = 4  # B == Sq == Sk: the ambiguous case
        q = jnp.asarray(R.standard_normal((S, S, 2, 8)), jnp.float32)
        add = np.zeros((S, S), np.float32)
        add[0, 1] = -1e9  # row 0 cannot see key 1
        out = np.asarray(sdpa_reference(q, q, q, mask=jnp.asarray(add)))
        ref = np.asarray(sdpa_reference(
            q, q, q, mask=jnp.asarray(add)[None, None]))
        np.testing.assert_allclose(out, ref, rtol=1e-6)
