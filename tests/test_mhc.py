"""The two hyper-connection kernels of `paddle_tpu.ops.pallas_mhc`
(interpreted here) against the plain `jnp` forms of `ops.references`:
the coefficients and the sublayer's input from ONE pass over the
stream, the stream's update in place; what 20 Sinkhorn iterations give
and one does not; and the seam's plain-add corner — with Hpre one-hot,
Hres the identity and Hpost 1 the wide stream's update IS `x + y`, bit
for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas_mhc import (mhc_enter, mhc_exit, mhc_post,
                                       mhc_pre, mhc_tileable)
from paddle_tpu.ops.references import (MHC_COEF_LANES, mhc_layout, mhc_pack,
                                       mhc_post_reference, mhc_pre_reference,
                                       sinkhorn_reference)

N, C = 4, 64


def _weights(seed, dtype=jnp.float32, diag=4.0):
    """The benchmark's draw: phi ~ N(0, 1 / (n C)), a = 1, b = 0 but
    `diag` on the residual matrix's diagonal."""
    rng = np.random.default_rng(seed)
    phi = rng.normal(size=(N * C, N * N + 2 * N)) / np.sqrt(N * C)
    b = np.zeros(N * N + 2 * N)
    b[2 * N:] = (diag * np.eye(N)).reshape(-1)
    return mhc_pack(jnp.asarray(phi, jnp.float32), jnp.asarray(b),
                    jnp.ones(3), N, dtype)


def _stream(seed, T, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(T, N * C)) * 3.0, dtype)


class TestPre:
    @pytest.mark.parametrize("T, dtype, tol", [
        (10, jnp.float32, 2e-5), (256, jnp.bfloat16, 2e-2)])
    def test_one_pass_matches_the_plain_form(self, T, dtype, tol):
        phi_t, ab = _weights(T, dtype)
        x = _stream(T + 1, T, dtype)
        x_in, coef = mhc_pre(x, phi_t, ab, n=N)
        x_ref, coef_ref = mhc_pre_reference(x, phi_t, ab, n=N)
        assert x_in.dtype == dtype and coef.shape == (T, MHC_COEF_LANES)
        np.testing.assert_allclose(coef, coef_ref, atol=tol, rtol=tol)
        np.testing.assert_allclose(
            np.asarray(x_in, np.float32), np.asarray(x_ref, np.float32),
            atol=tol * 10, rtol=tol)

    def test_the_packed_rows_sit_on_sublane_tiles(self):
        assert mhc_layout(4) == (8, 16, 32)
        phi_t, ab = _weights(0)
        assert phi_t.shape == (32, N * C) and ab.shape == (32, 128)
        assert not np.asarray(phi_t[4:8]).any()
        assert np.asarray(ab[16:32, 1]).reshape(4, 4).trace() == 16.0

    def test_twenty_iterations_are_doubly_stochastic_and_one_is_not(self):
        phi_t, ab = _weights(3, diag=1.0)
        x = _stream(4, 32)
        for iters, ok in ((20, True), (1, False)):
            _, coef = mhc_pre(x, phi_t, ab, n=N, iters=iters)
            hres = np.asarray(coef[:, N:N + N * N]).reshape(-1, N, N)
            np.testing.assert_allclose(hres.sum(2), 1.0, atol=1e-5)
            err = np.abs(hres.sum(1) - 1.0).max()
            assert (err < 1e-3) == ok, (iters, err)

    def test_the_clamp_holds_what_exp_would_overflow(self):
        phi_t, ab = _weights(5)
        ab = ab.at[16, 1].set(500.0)         # exp(500) is inf in float32
        _, coef = mhc_pre(_stream(6, 16), phi_t, ab, n=N)
        assert np.isfinite(np.asarray(coef)).all()

    def test_what_tiles_on_the_chip(self):
        assert mhc_tileable(384, 4, 3584)
        assert not mhc_tileable(100, 4, 3584)
        assert not mhc_tileable(384, 4, 64)


class TestPost:
    @pytest.mark.parametrize("T, dtype, tol", [
        (10, jnp.float32, 1e-6), (128, jnp.bfloat16, 2e-2)])
    def test_in_place_update_matches_the_plain_form(self, T, dtype, tol):
        phi_t, ab = _weights(T, dtype)
        x = _stream(T + 1, T, dtype)
        y = _stream(T + 2, T, dtype)[:, :C]
        _, coef = mhc_pre_reference(x, phi_t, ab, n=N)
        want = mhc_post_reference(x, y, coef, n=N)
        got = mhc_post(x, y, coef, n=N)
        assert got.dtype == dtype and got.shape == x.shape
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=tol, rtol=tol)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_the_plain_add_is_a_corner_of_the_seam(self, dtype):
        """Hpre one-hot on stream 0, Hres = I, Hpost = 1: stream 0 of
        the wide residual runs `x + y`, bit for bit."""
        T = 16
        x0 = _stream(7, T, dtype)[:, :C]
        y = _stream(8, T, dtype)[:, :C]
        coef = jnp.zeros((T, MHC_COEF_LANES), jnp.float32)
        coef = coef.at[:, :N].set(1.0)
        coef = coef.at[:, N:N + N * N].set(jnp.eye(N).reshape(-1))
        coef = coef.at[:, N + N * N].set(1.0)           # Hpre = e_0
        x = mhc_enter(x0, N)
        for form in (mhc_post, mhc_post_reference):
            out = form(x, y, coef, n=N)
            assert bool((out[:, :C] == x0 + y).all()), form.__name__
        # and the pre side hands stream 0 through untouched
        hpre = coef[:, N + N * N:N + N * N + N]
        x_in = jnp.einsum("tj,tjc->tc", hpre,
                          x.astype(jnp.float32).reshape(T, N, C))
        assert bool((x_in.astype(dtype) == x0).all())


def test_entry_copies_and_exit_sums():
    h = _stream(9, 6)[:, :C]
    x = mhc_enter(h, N)
    assert x.shape == (6, N * C)
    for j in range(N):
        assert bool((x[:, j * C:(j + 1) * C] == h).all())
    np.testing.assert_allclose(mhc_exit(x, N), N * h, rtol=1e-6)


def test_sinkhorn_normalises_columns_first_and_rows_last():
    z = jnp.asarray(np.random.default_rng(1).normal(size=(3, N, N)))
    m = np.asarray(sinkhorn_reference(z, 1, 1e-6))
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-5)    # rows last
    assert np.abs(m.sum(-2) - 1.0).max() > 1e-3
