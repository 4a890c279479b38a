"""The two KDA (gated delta rule) kernels of `paddle_tpu.ops.pallas_kda`
against the token-by-token oracle (`ops.references`): the decode rows'
one step in place over a slot-indexed pool — live slots updated, every
other slot bit-equal, nothing live at all — and the prefill chunk's
WY-form scan across chunk and sub-chunk borders, from a started slot's
zero state, with padding rows as the identity, and with the log gates at
both ends of (-5, 0), where a sub-chunk spans e^-320 and nothing may
come out inf or nan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas_kda import (_unit_lower_inverse, kda_chunk_scan,
                                       kda_state_update, kda_tileable)
from paddle_tpu.ops.references import (kda_recurrence_reference,
                                       kda_state_update_reference)

H, K, V = 4, 16, 8


def _rows(rng, L, g_lo=-5.0, g_hi=0.0):
    """q, k (unit, q scaled), v, g in (g_lo, g_hi), beta in (0, 1)."""
    q, k = rng.normal(size=(2, L, H, K))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * K ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(L, H, V))
    g = rng.uniform(g_lo, g_hi, size=(L, H, K))
    beta = rng.uniform(0, 1, size=(L, H))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)]


def _state(rng, *lead):
    return jnp.asarray(rng.normal(size=lead + (H, K, V)), jnp.float32)


class TestStateUpdate:
    def _case(self, seed, NS=5):
        rng = np.random.default_rng(seed)
        q, k, v, g, beta = _rows(rng, NS + 2)
        return _state(rng, NS), (q, k, v, g, beta[..., None])

    @pytest.mark.parametrize("slots, n", [([2, 0, 4, 4], 2),
                                          ([3, 4, 4, 4], 1),
                                          ([0, 1, 2, 3], 4)])
    def test_live_slots_match_and_the_others_are_bit_equal(self, slots, n):
        pool, ops = self._case(len(slots) + n)
        sl, nl = jnp.asarray(slots, jnp.int32), jnp.asarray([n], jnp.int32)
        o, new = kda_state_update(pool, sl, nl, *ops)
        o_ref, new_ref = kda_state_update_reference(pool, sl, nl, *ops)
        live = slots[:n]
        np.testing.assert_allclose(np.asarray(o)[live],
                                   np.asarray(o_ref)[live], atol=1e-6)
        np.testing.assert_allclose(new, new_ref, atol=1e-6)
        for s in set(range(5)) - set(live):
            assert bool((new[s] == pool[s]).all()), f"idle slot {s}"

    def test_nothing_live_leaves_the_pool_as_it_was(self):
        pool, ops = self._case(3)
        _, new = kda_state_update(pool, jnp.full(4, 4, jnp.int32),
                                  jnp.asarray([0], jnp.int32), *ops)
        assert bool((new == pool).all())

    @pytest.mark.parametrize("g", [-5.0, -1e-7])
    def test_the_gates_ends_give_no_inf_or_nan(self, g):
        pool, (q, k, v, _, beta) = self._case(4)
        o, new = kda_state_update(
            pool, jnp.arange(4, dtype=jnp.int32), jnp.asarray([4]), q, k, v,
            jnp.full(q.shape, g, jnp.float32), beta)
        assert bool(jnp.isfinite(o[:4]).all() & jnp.isfinite(new).all())

    def test_what_tiles_on_the_chip(self):
        assert kda_tileable(32, 128, 128)
        assert not kda_tileable(4, 16, 16) and not kda_tileable(32, 64, 128)


class TestChunkScan:
    #: sub-chunks of 8: less than one, one, two, two and a part, four
    @pytest.mark.parametrize("L", [5, 8, 16, 21, 32])
    def test_the_scan_is_the_recurrence(self, L):
        rng = np.random.default_rng(L)
        rows, s0 = _rows(rng, L), _state(rng)
        o, s = kda_chunk_scan(*rows, s0, chunk=8)
        o_ref, s_ref = kda_recurrence_reference(*rows, s0)
        np.testing.assert_allclose(o, o_ref, atol=2e-6)
        np.testing.assert_allclose(s, s_ref, atol=2e-6)

    def test_a_started_slot_scans_from_zero(self):
        rng = np.random.default_rng(1)
        rows = _rows(rng, 19)
        zero = jnp.zeros((H, K, V), jnp.float32)
        o, s = kda_chunk_scan(*rows, zero, chunk=8)
        o_ref, s_ref = kda_recurrence_reference(*rows, zero)
        np.testing.assert_allclose(o, o_ref, atol=2e-6)
        np.testing.assert_allclose(s, s_ref, atol=2e-6)

    @pytest.mark.parametrize("n", [0, 3, 11, 16])
    def test_padding_rows_are_the_identity(self, n):
        """Rows past the chunk's length carry g 0 and beta 0: the state
        that comes out is the state after row n - 1."""
        rng = np.random.default_rng(n)
        q, k, v, g, beta = _rows(rng, 16)
        s0 = _state(rng)
        valid = jnp.arange(16) < n
        o, s = kda_chunk_scan(q, k, v, jnp.where(valid[:, None, None], g, 0),
                              jnp.where(valid[:, None], beta, 0), s0,
                              chunk=8)
        o_ref, s_ref = kda_recurrence_reference(
            q[:n], k[:n], v[:n], g[:n], beta[:n], s0)
        np.testing.assert_allclose(s, s_ref, atol=2e-6)
        if n:
            np.testing.assert_allclose(o[:n], o_ref, atol=2e-6)

    @pytest.mark.parametrize("g_lo, g_hi", [(-5.0, -4.999), (-1e-6, 0.0),
                                            (-5.0, 0.0)])
    def test_the_gates_ends_across_a_sub_chunk_of_64(self, g_lo, g_hi):
        """At -5 a token a sub-chunk of 64 spans e^-320: the decays are
        differences of cumulative log gates, so nothing overflows, and
        the result is still the recurrence's."""
        rng = np.random.default_rng(7)
        rows, s0 = _rows(rng, 128, g_lo, g_hi), _state(rng)
        o, s = kda_chunk_scan(*rows, s0, chunk=64)
        assert bool(jnp.isfinite(o).all() & jnp.isfinite(s).all())
        o_ref, s_ref = kda_recurrence_reference(*rows, s0)
        np.testing.assert_allclose(o, o_ref, atol=5e-6)
        np.testing.assert_allclose(s, s_ref, atol=5e-6)

    @pytest.mark.parametrize("zero", [False, True],
                             ids=["from_a_state", "from_zero"])
    @pytest.mark.parametrize("g_lo, g_hi", [(-5.0, -4.999), (-1e-6, 0.0),
                                            (-5.0, 0.0)])
    @pytest.mark.parametrize("L", [15, 16, 17, 33, 48, 100, 256])
    def test_rows_cross_the_blocks_of_a_sub_chunk_of_64(self, L, g_lo, g_hi,
                                                        zero):
        """Inside a sub-chunk of 64 the decays between two blocks of 16
        rows are two matmul operands, each exp of a number <= 0: at -5
        a token the factor over 48 earlier rows underflows to 0, as the
        decay it stands for does, and the result is the recurrence's
        whether a run ends before, on or after a block's border."""
        rng = np.random.default_rng(L)
        rows, s0 = _rows(rng, L, g_lo, g_hi), _state(rng)
        if zero:
            s0 = jnp.zeros_like(s0)
        o, s = kda_chunk_scan(*rows, s0, chunk=64)
        assert bool(jnp.isfinite(o).all() & jnp.isfinite(s).all())
        o_ref, s_ref = kda_recurrence_reference(*rows, s0)
        np.testing.assert_allclose(o, o_ref, atol=5e-6)
        np.testing.assert_allclose(s, s_ref, atol=5e-6)

    @pytest.mark.parametrize("n", [0, 1, 15, 16, 17, 40, 63, 64, 65, 100])
    def test_padding_rows_end_inside_a_block(self, n):
        """A chunk of 128 rows in sub-chunks of 64 of which n are live:
        the identity rows (g 0, beta 0) start inside a block of 16, on
        its border, or fill whole sub-chunks."""
        rng = np.random.default_rng(100 + n)
        q, k, v, g, beta = _rows(rng, 128)
        s0 = _state(rng)
        valid = jnp.arange(128) < n
        o, s = kda_chunk_scan(q, k, v, jnp.where(valid[:, None, None], g, 0),
                              jnp.where(valid[:, None], beta, 0), s0,
                              chunk=64)
        o_ref, s_ref = kda_recurrence_reference(
            q[:n], k[:n], v[:n], g[:n], beta[:n], s0)
        np.testing.assert_allclose(s, s_ref, atol=5e-6)
        if n:
            np.testing.assert_allclose(o[:n], o_ref, atol=5e-6)

    def test_the_gradient_is_the_recurrences(self):
        """The eager model runs the scan under `jax.vjp`: the cotangents
        of k and g (and of the state it starts from) are those of the
        token-by-token recurrence."""
        rng = np.random.default_rng(11)
        (q, k, v, g, beta), s0 = _rows(rng, 40, -2.0, 0.0), _state(rng)
        w_o = jnp.asarray(rng.normal(size=(40, H, V)), jnp.float32)
        w_s = jnp.asarray(rng.normal(size=(H, K, V)), jnp.float32)

        def through(scan):
            def f(k, g, s0):
                return scan(q, k, v, g, beta, s0)
            out, pull = jax.vjp(f, k, g, s0)
            return out, pull((w_o, w_s))

        (o, s), got = through(
            lambda *a: kda_chunk_scan(*a, chunk=16))
        (o_ref, s_ref), want = through(kda_recurrence_reference)
        np.testing.assert_allclose(o, o_ref, atol=5e-6)
        for name, a, b in zip(("k", "g", "state"), got, want):
            assert bool(jnp.isfinite(a).all()), name
            np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)

    def test_the_cell_s_chunk_lowers_to_no_loop(self):
        """The sub-chunks of a chunk are known at trace time: at the
        Ling cell's shapes (256 rows, 32 heads x 128 x 128, sub-chunks
        of 64) the scan's lowered text holds no `while`."""
        f32 = jnp.float32
        row = jax.ShapeDtypeStruct((256, 32, 128), f32)
        text = jax.jit(
            lambda *a: kda_chunk_scan(*a, chunk=64)).lower(
            row, row, row, row, jax.ShapeDtypeStruct((256, 32), f32),
            jax.ShapeDtypeStruct((32, 128, 128), f32)).as_text()
        assert "dot_general" in text
        assert "while" not in text

    def test_chunk_then_update_is_one_sequence(self):
        """A chunk's last state put in a slot, then one decode step of
        the update kernel: rows 0..n of ONE recurrence."""
        rng = np.random.default_rng(9)
        q, k, v, g, beta = _rows(rng, 12)
        zero = jnp.zeros((H, K, V), jnp.float32)
        _, s = kda_chunk_scan(q[:11], k[:11], v[:11], g[:11], beta[:11],
                              zero, chunk=8)
        pool = jnp.zeros((3, H, K, V), jnp.float32).at[1].set(s)
        last = [jnp.broadcast_to(a[11], (3,) + a.shape[1:])
                for a in (q, k, v, g, beta[..., None])]
        o, pool = kda_state_update(pool, jnp.asarray([1, 2], jnp.int32),
                                   jnp.asarray([1]), *last)
        o_ref, s_ref = kda_recurrence_reference(q, k, v, g, beta, zero)
        np.testing.assert_allclose(o[1], o_ref[11], atol=2e-6)
        np.testing.assert_allclose(pool[1], s_ref, atol=2e-6)

    def test_the_unit_lower_inverse(self):
        rng = np.random.default_rng(2)
        a = jnp.asarray(np.tril(rng.normal(size=(3, 16, 16)), -1),
                        jnp.float32)
        inv = _unit_lower_inverse(a)
        np.testing.assert_allclose(
            jnp.matmul(jnp.eye(16) + a, inv), np.broadcast_to(
                np.eye(16), (3, 16, 16)), atol=1e-4)
        with pytest.raises(ValueError, match="power of two"):
            kda_chunk_scan(*_rows(rng, 12), _state(rng), chunk=12)
