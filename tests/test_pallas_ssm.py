"""The state-space kernels (`paddle_tpu.ops.pallas_ssm`), interpreted,
against the recurrence token by token: one step for the live slots in
place (idle slots bit-unchanged), the chunk's scan at chunk lengths
around the scan chunk (1, 127, 128, 129, 256 rows), `dt = 0` padding as
the identity, and one slot put in place; the same over the
state-minor pool [slots, H, P, N] that a model of fewer than 128 heads
over a state of whole registers takes (`TestStateMinor`); and Mamba-1's
two kernels — a decay a (channel, column), the pool [slots, 1, N, C] —
against ITS recurrence token by token (`TestMamba1`)."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.oracles import oracles, resolve_reference
from paddle_tpu.ops.pallas_ssm import (HEADS_MINOR, STATE_MINOR,
                                       ssm1_chunk_scan, ssm1_state_update,
                                       ssm_chunk_scan, ssm_state_put,
                                       ssm_state_update, state_layout,
                                       state_pool_shape)
from paddle_tpu.ops.references import (ssm1_recurrence_reference,
                                       ssm1_state_update_reference)
from paddle_tpu.ops.references import \
    ssm_recurrence_reference as ssm_recurrence

H, P, G, N = 8, 16, 2, 16

#: (heads, head width, groups, state, layout) the chunk's scan is held
#: to: the two layouts, one head a group and sixteen, and a head width
#: under the 128 lanes (Nemotron's 64)
GEOMETRIES = {
    "heads_minor": (H, P, G, N, HEADS_MINOR),
    "state_minor": (4, 8, 2, 128, STATE_MINOR),
    "one_head_a_group": (8, 16, 8, 16, HEADS_MINOR),
    "sixteen_heads_a_group": (32, 8, 2, 16, HEADS_MINOR),
    "heads_of_64": (4, 64, 2, 128, STATE_MINOR),
}


def _rows(rng, L, geometry="heads_minor"):
    """(xdt [L, H, P], dA [L, H] <= 0, B, C [L, G, N])."""
    H, P, G, N, _ = GEOMETRIES[geometry]
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (L, H)))
    A = -rng.uniform(1, 16, H)
    return (jnp.asarray(rng.normal(0, 1, (L, H, P)) * dt[..., None],
                        jnp.float32),
            jnp.asarray(dt * A, jnp.float32),
            jnp.asarray(rng.normal(0, 1, (L, G, N)), jnp.float32),
            jnp.asarray(rng.normal(0, 1, (L, G, N)), jnp.float32))


def _state(rng, geometry, zeros=False):
    """A state in the recurrence's [P, N, H] and as the geometry's
    layout stores it."""
    H, P, _, N, layout = GEOMETRIES[geometry]
    s = jnp.asarray(np.zeros((P, N, H)) if zeros
                    else rng.normal(0, 1, (P, N, H)), jnp.float32)
    return s, (jnp.transpose(s, (2, 0, 1)) if layout == STATE_MINOR else s)


def _scan(rows, stored, geometry, chunk):
    """`ssm_chunk_scan` in the geometry's layout -> (y, the state in the
    recurrence's [P, N, H], the state as stored)."""
    layout = GEOMETRIES[geometry][-1]
    y, s = ssm_chunk_scan(*rows, stored, chunk=chunk, layout=layout)
    assert s.shape == stored.shape
    return y, (jnp.transpose(s, (1, 2, 0)) if layout == STATE_MINOR
               else s), s


class TestChunkScan:
    @pytest.mark.parametrize("L", [1, 127, 128, 129, 256])
    def test_the_scan_is_the_recurrence(self, L):
        rng = np.random.default_rng(L)
        rows = _rows(rng, L)
        s0 = jnp.asarray(rng.normal(0, 1, (P, N, H)), jnp.float32)
        want_y, want_s = ssm_recurrence(*rows, s0)
        got_y, got_s = ssm_chunk_scan(*rows, s0, chunk=128)
        assert got_y.shape == (L, H, P) and got_s.shape == (P, N, H)
        np.testing.assert_allclose(got_y, want_y, atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(got_s, want_s, atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("start", ["zeros", "a_state"])
    @pytest.mark.parametrize("geometry", list(GEOMETRIES))
    def test_every_geometry_is_the_recurrence(self, geometry, start):
        """40 rows in scan chunks of 16 (not a whole number of them),
        from zeros and from a state."""
        rng = np.random.default_rng(len(geometry))
        rows = _rows(rng, 40, geometry)
        s0, stored = _state(rng, geometry, zeros=start == "zeros")
        want_y, want_s = ssm_recurrence(*rows, s0)
        got_y, got_s, _ = _scan(rows, stored, geometry, 16)
        assert got_y.shape == want_y.shape
        np.testing.assert_allclose(got_y, want_y, atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(got_s, want_s, atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("geometry", ["heads_minor", "state_minor"])
    @pytest.mark.parametrize("where", ["at_the_end", "in_the_middle"])
    def test_rows_with_dt_zero_change_nothing(self, where, geometry):
        """A chunk of 64 rows of which 37 are valid — the first 37, or
        with rows 20 to 46 idle in the middle: the idle rows' xdt and dA
        are 0, the state and the valid rows' y are those of the 37 rows
        alone."""
        rng = np.random.default_rng(5)
        xdt, dA, bm, cm = _rows(rng, 64, geometry)
        valid = np.arange(64) < 37 if where == "at_the_end" \
            else (np.arange(64) < 20) | (np.arange(64) >= 47)
        pad = (jnp.where(valid[:, None, None], xdt, 0),
               jnp.where(valid[:, None], dA, 0), bm, cm)
        s0, stored = _state(rng, geometry)
        y, s, _ = _scan(pad, stored, geometry, 16)
        want_y, want_s = ssm_recurrence(*(a[valid] for a in pad), s0)
        np.testing.assert_allclose(s, want_s, atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(y[valid], want_y, atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("geometry", ["heads_minor", "state_minor"])
    def test_two_chunks_carry_the_state(self, geometry):
        """Two calls, the second from the first one's state, are ONE
        call over all the rows, and the recurrence."""
        rng = np.random.default_rng(6)
        rows = _rows(rng, 48, geometry)
        s0, stored = _state(rng, geometry, zeros=True)
        y1, _, kept = _scan([a[:24] for a in rows], stored, geometry, 8)
        y2, s2, _ = _scan([a[24:] for a in rows], kept, geometry, 8)
        one_y, one_s, _ = _scan(rows, stored, geometry, 8)
        want_y, want_s = ssm_recurrence(*rows, s0)
        for got_y, got_s in ((jnp.concatenate([y1, y2]), s2),
                             (one_y, one_s)):
            np.testing.assert_allclose(got_y, want_y, atol=2e-4, rtol=2e-4)
            np.testing.assert_allclose(got_s, want_s, atol=2e-4, rtol=2e-4)

    def test_the_scan_has_the_recurrences_gradient(self):
        """The models' eager forwards run under `jax.vjp`: the scan is
        plain XLA and differentiable, and the gradient of its outputs in
        x' is the token-by-token recurrence's."""
        import jax
        rng = np.random.default_rng(7)
        rows = _rows(rng, 24)
        s0 = jnp.asarray(rng.normal(0, 1, (P, N, H)), jnp.float32)
        weight = jnp.asarray(rng.normal(0, 1, (24, H, P)), jnp.float32)

        def loss(scan, x):
            y, s = scan(x, *rows[1:], s0)
            return jnp.sum(y * weight) + jnp.sum(s)

        got = jax.grad(lambda x: loss(
            lambda *a: ssm_chunk_scan(*a, chunk=8), x))(rows[0])
        want = jax.grad(lambda x: loss(ssm_recurrence, x))(rows[0])
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def _update_operands(rng, NS):
    xdt, dA, bm, cm = _rows(rng, NS)
    expand = lambda m: jnp.repeat(m, H // G, axis=1).swapaxes(1, 2)  # noqa
    return (xdt.swapaxes(1, 2), jnp.exp(dA)[:, None, :], expand(bm),
            expand(cm)), (xdt, dA, bm, cm)


class TestStateUpdate:
    @pytest.mark.parametrize("live", [[], [2], [0, 3, 1], [4, 0, 1, 2, 3]])
    def test_one_step_for_the_live_slots_in_place(self, live):
        """5 slots + the spare; P = 32 so that a slot is two grid steps
        of 16."""
        rng = np.random.default_rng(len(live))
        NS, B = 6, 5
        pool = jnp.asarray(rng.normal(0, 1, (NS, 32, N, H)), jnp.float32)
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (NS, H)))
        xdt = jnp.asarray(rng.normal(0, 1, (NS, 32, H)), jnp.float32)
        dec = jnp.asarray(np.exp(-dt * 3.0)[:, None, :], jnp.float32)
        bh = jnp.asarray(rng.normal(0, 1, (NS, N, H)), jnp.float32)
        ch = jnp.asarray(rng.normal(0, 1, (NS, N, H)), jnp.float32)
        slots = np.full(B, NS - 1, np.int32)
        slots[:len(live)] = live
        args = (pool, jnp.asarray(slots),
                jnp.asarray([len(live)], jnp.int32), xdt, dec, bh, ch)
        entry = oracles()["ssm_state_update"]
        want_y, want_pool = resolve_reference(entry)(*args)
        got_y, got_pool = ssm_state_update(*args)
        # idle slots (and the spare) come back bit for bit
        idle = [s for s in range(NS) if s not in live]
        np.testing.assert_array_equal(np.asarray(got_pool)[idle],
                                      np.asarray(pool)[idle])
        np.testing.assert_allclose(got_pool, want_pool, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_y)[live],
                                   np.asarray(want_y)[live], atol=1e-4)

    def test_a_step_is_the_recurrence(self):
        rng = np.random.default_rng(9)
        NS = 4
        ops, (xdt, dA, bm, cm) = _update_operands(rng, NS)
        pool = jnp.asarray(rng.normal(0, 1, (NS, P, N, H)), jnp.float32)
        y, new = ssm_state_update(
            pool, jnp.asarray([1, 2, 3], jnp.int32),
            jnp.asarray([2], jnp.int32), *ops)
        for s in (1, 2):
            want_y, want_s = ssm_recurrence(
                xdt[s:s + 1], dA[s:s + 1], bm[s:s + 1], cm[s:s + 1], pool[s])
            np.testing.assert_allclose(new[s], want_s, atol=1e-5)
            np.testing.assert_allclose(y[s].T, want_y[0], atol=1e-4)
        np.testing.assert_array_equal(new[3], pool[3])
        np.testing.assert_array_equal(new[0], pool[0])


class TestStatePut:
    @pytest.mark.parametrize("slot, go", [(0, 1), (2, 1), (1, 0)])
    def test_one_slot_in_place(self, slot, go):
        rng = np.random.default_rng(slot)
        pool = jnp.asarray(rng.normal(0, 1, (3, 32, N, H)), jnp.float32)
        state = jnp.asarray(rng.normal(0, 1, (32, N, H)), jnp.float32)
        args = (pool, jnp.asarray([slot, go], jnp.int32), state)
        want = resolve_reference(oracles()["ssm_state_put"])(*args)
        np.testing.assert_array_equal(ssm_state_put(*args), want)
        assert bool((want[slot] == state).all()) == bool(go)


# ------------------------------------------- the state-minor layout
#: fewer heads than lanes over a state of whole registers: 4 heads of 8
#: in 2 groups over a state of 128 (Falcon-H1-34B's 32 x 128 over 256)
SH, SP, SG, SN = 4, 8, 2, 128


def _sm_rows(rng, L):
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (L, SH)))
    A = -rng.uniform(1, 16, SH)
    return (jnp.asarray(rng.normal(0, 1, (L, SH, SP)) * dt[..., None],
                        jnp.float32),
            jnp.asarray(dt * A, jnp.float32),
            jnp.asarray(rng.normal(0, 1, (L, SG, SN)), jnp.float32),
            jnp.asarray(rng.normal(0, 1, (L, SG, SN)), jnp.float32))


def _turned(s):
    """[H, P, N] -> the heads-minor [P, N, H] the recurrence is in."""
    return jnp.transpose(s, (1, 2, 0))


class TestStateMinor:
    @pytest.mark.parametrize("H, N, want", [
        (128, 128, HEADS_MINOR), (32, 256, STATE_MINOR),
        (8, 16, HEADS_MINOR), (4, 128, STATE_MINOR),
        (256, 128, HEADS_MINOR), (32, 64, HEADS_MINOR)])
    def test_the_lanes_pick_the_layout(self, H, N, want):
        assert state_layout(H, N) == want
        shape = state_pool_shape(3, H, 64, N, want)
        assert shape == ((3, H, 64, N) if want == STATE_MINOR
                         else (3, 64, N, H))

    @pytest.mark.parametrize("live", [[], [2], [0, 3, 1], [4, 0, 1, 2, 3]])
    def test_one_step_for_the_live_slots_in_place(self, live):
        """5 slots + the spare: an idle slot comes back bit for bit."""
        rng = np.random.default_rng(len(live) + 20)
        NS, B = 6, 5
        pool = jnp.asarray(rng.normal(0, 1, (NS, SH, SP, SN)), jnp.float32)
        xdt, dA, bm, cm = _sm_rows(rng, NS)
        slots = np.full(B, NS - 1, np.int32)
        slots[:len(live)] = live
        args = (pool, jnp.asarray(slots),
                jnp.asarray([len(live)], jnp.int32), xdt.swapaxes(1, 2),
                jnp.exp(dA)[:, None, :], bm, cm)
        want_y, want_pool = resolve_reference(
            oracles()["ssm_state_update"])(*args, layout=STATE_MINOR)
        got_y, got_pool = ssm_state_update(*args, layout=STATE_MINOR)
        idle = [s for s in range(NS) if s not in live]
        np.testing.assert_array_equal(np.asarray(got_pool)[idle],
                                      np.asarray(pool)[idle])
        np.testing.assert_allclose(got_pool, want_pool, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_y)[live],
                                   np.asarray(want_y)[live], atol=1e-4)
        # ... and a live slot's step is the recurrence's
        for s in live[:2]:
            y1, s1 = ssm_recurrence(xdt[s:s + 1], dA[s:s + 1], bm[s:s + 1],
                                    cm[s:s + 1], _turned(pool[s]))
            np.testing.assert_allclose(_turned(got_pool[s]), s1, atol=1e-5)
            np.testing.assert_allclose(got_y[s].T, y1[0], atol=1e-4)

    @pytest.mark.parametrize("L", [1, 15, 16, 17, 40])
    def test_the_scan_hands_its_state_to_the_update(self, L):
        """A chunk's scan from a slot's state, its last state put back,
        then one decode step of that slot: the recurrence over L + 1
        rows."""
        rng = np.random.default_rng(L)
        rows = _sm_rows(rng, L + 1)
        pool = jnp.asarray(rng.normal(0, 1, (3, SH, SP, SN)), jnp.float32)
        want_y, want_s = ssm_recurrence(*rows, _turned(pool[1]))
        y, s1 = ssm_chunk_scan(*(a[:L] for a in rows), pool[1], chunk=16,
                               layout=STATE_MINOR)
        assert s1.shape == (SH, SP, SN)
        put = ssm_state_put(pool, jnp.asarray([1, 1], jnp.int32), s1)
        np.testing.assert_array_equal(put[0], pool[0])
        np.testing.assert_array_equal(put[2], pool[2])
        xdt, dA, bm, cm = (jnp.broadcast_to(a[L:], (3,) + a.shape[1:])
                           for a in rows)
        y2, new = ssm_state_update(
            put, jnp.asarray([1, 2], jnp.int32), jnp.asarray([1], jnp.int32),
            xdt.swapaxes(1, 2), jnp.exp(dA)[:, None, :], bm, cm,
            layout=STATE_MINOR)
        np.testing.assert_allclose(y, want_y[:L], atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(y2[1].T, want_y[L], atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(_turned(new[1]), want_s, atol=2e-4,
                                   rtol=2e-4)
        np.testing.assert_array_equal(new[0], pool[0])
        np.testing.assert_array_equal(new[2], pool[2])

    @pytest.mark.parametrize("slot, go", [(0, 1), (2, 1), (1, 0)])
    def test_one_slot_put_in_place(self, slot, go):
        rng = np.random.default_rng(slot + 30)
        pool = jnp.asarray(rng.normal(0, 1, (3, 32, SP, SN)), jnp.float32)
        state = jnp.asarray(rng.normal(0, 1, (32, SP, SN)), jnp.float32)
        args = (pool, jnp.asarray([slot, go], jnp.int32), state)
        want = resolve_reference(oracles()["ssm_state_put"])(*args)
        np.testing.assert_array_equal(ssm_state_put(*args), want)


# ---------------------------------------------------------------------------
# Mamba-1: a decay for every (channel, state column)
# ---------------------------------------------------------------------------

C1, N1 = 256, 16


def _rows1(rng, L):
    """(dt [L, C] > 0, x [L, C], A [N, C] < 0, B, C [L, N])."""
    f32 = jnp.float32
    return (jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3),
                                           (L, C1))), f32),
            jnp.asarray(rng.normal(0, 1, (L, C1)), f32),
            -jnp.asarray(rng.uniform(1, 16, (N1, C1)), f32),
            jnp.asarray(rng.normal(0, 1, (L, N1)), f32),
            jnp.asarray(rng.normal(0, 1, (L, N1)), f32))


class TestMamba1:
    @pytest.mark.parametrize("L", [1, 7, 8, 21, 64])
    def test_the_scan_is_the_recurrence(self, L):
        rng = np.random.default_rng(L)
        dt, x, a, bm, cm = _rows1(rng, L)
        s0 = jnp.asarray(rng.normal(0, 1, (1, N1, C1)), jnp.float32)
        y, s1 = ssm1_chunk_scan(dt, x, a, bm, cm, s0)
        yr, sr = ssm1_recurrence_reference(dt, x, a, bm, cm, s0)
        assert y.shape == (L, C1) and s1.shape == s0.shape
        np.testing.assert_allclose(y, yr, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(s1, sr, atol=2e-5, rtol=1e-5)

    def test_rows_with_dt_zero_change_nothing(self):
        """Padding a chunk: rows past its length carry dt 0."""
        rng = np.random.default_rng(0)
        dt, x, a, bm, cm = _rows1(rng, 24)
        s0 = jnp.asarray(rng.normal(0, 1, (1, N1, C1)), jnp.float32)
        valid = (jnp.arange(24) < 13)[:, None]
        y, s1 = ssm1_chunk_scan(jnp.where(valid, dt, 0), x, a, bm, cm, s0)
        yr, sr = ssm1_recurrence_reference(dt[:13], x[:13], a, bm[:13],
                                           cm[:13], s0)
        np.testing.assert_allclose(y[:13], yr, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(s1, sr, atol=2e-5, rtol=1e-5)

    @pytest.mark.parametrize("live", [(), (2,), (3, 0, 1)])
    def test_one_step_for_the_live_slots_in_place(self, live):
        """An idle slot is neither read nor written: bit for bit."""
        rng = np.random.default_rng(len(live))
        NS, B = 5, 4
        dt, x, a, bm, cm = _rows1(rng, NS)
        pool = jnp.asarray(rng.normal(0, 1, (NS, 1, N1, C1)), jnp.float32)
        slots = jnp.asarray(list(live) + [NS - 1] * (B - len(live)),
                            jnp.int32)
        n = jnp.asarray([len(live)], jnp.int32)
        before = np.asarray(pool)
        y, new = ssm1_state_update(pool, slots, n, dt, x, a, bm, cm)
        yr, want = ssm1_state_update_reference(pool, slots, n, dt, x, a, bm,
                                               cm)
        rows = list(live)
        np.testing.assert_allclose(np.asarray(y)[rows], np.asarray(yr)[rows],
                                   atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(new)[rows],
                                   np.asarray(want)[rows], atol=2e-5,
                                   rtol=1e-5)
        idle = [s for s in range(NS) if s not in live]
        np.testing.assert_array_equal(np.asarray(new)[idle], before[idle])

    def test_the_scan_hands_its_state_to_the_update(self):
        """A chunk's scan, its state put in a slot, then one decode step
        from it: the recurrence over all the rows."""
        rng = np.random.default_rng(5)
        L, NS = 19, 3
        dt, x, a, bm, cm = _rows1(rng, L + 1)
        zero = jnp.zeros((1, N1, C1), jnp.float32)
        _, s1 = ssm1_chunk_scan(dt[:L], x[:L], a, bm[:L], cm[:L], zero)
        pool = ssm_state_put(jnp.ones((NS, 1, N1, C1), jnp.float32),
                             jnp.asarray([1, 1], jnp.int32), s1)
        row = lambda m: jnp.zeros((NS,) + m.shape[1:]).at[1].set(m[L])  # noqa
        y, pool = ssm1_state_update(
            pool, jnp.asarray([1, NS - 1], jnp.int32),
            jnp.asarray([1], jnp.int32), row(dt), row(x), a, row(bm),
            row(cm))
        yr, sr = ssm1_recurrence_reference(dt, x, a, bm, cm, zero)
        np.testing.assert_allclose(y[1], yr[L], atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(pool[1], sr, atol=2e-5, rtol=1e-5)
        np.testing.assert_array_equal(pool[0], 1.0)

    def test_the_oracles_are_registered(self):
        for name in ("ssm1_state_update", "ssm1_chunk_scan"):
            assert name in oracles()
            assert callable(resolve_reference(oracles()[name]))
