"""MoE / expert-parallel tests (SURVEY §2.3 P7; §4.2 simulated-mesh method)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.moe import (MoELayer, SwitchMoELayer, top_k_gating,
                                     router_z_loss)
from paddle_tpu.ops.grouped_gemm import grouped_gemm, sort_by_group, \
    unsort_by_group


def _rand(*shape, seed=0, scale=0.1):
    rng = np.random.RandomState(seed)
    return (rng.randn(*shape) * scale).astype(np.float32)


class TestGating:
    def test_topk_dispatch_shapes_and_capacity(self):
        T, E, k, C = 16, 4, 2, 8
        gates = jax.nn.softmax(jnp.asarray(_rand(T, E, seed=1, scale=1.0)))
        dispatch, combine, aux = top_k_gating(gates, k, C)
        assert dispatch.shape == (T, E, C)
        # no expert bucket slot used twice
        per_slot = np.asarray(dispatch).sum(axis=0)  # [E, C]
        assert per_slot.max() <= 1.0 + 1e-6
        # every token goes to at most k slots
        per_tok = np.asarray(dispatch).sum(axis=(1, 2))
        assert per_tok.max() <= k + 1e-6
        assert float(aux) > 0

    def test_combine_renormalized_sums_to_one(self):
        T, E, k = 8, 4, 2
        C = T  # no drops
        gates = jax.nn.softmax(jnp.asarray(_rand(T, E, seed=2, scale=1.0)))
        _, combine, _ = top_k_gating(gates, k, C, renormalize=True)
        s = np.asarray(combine).sum(axis=(1, 2))
        np.testing.assert_allclose(s, np.ones(T), rtol=1e-5)

    def test_z_loss_positive(self):
        logits = jnp.asarray(_rand(8, 4, scale=2.0))
        assert float(router_z_loss(logits)) > 0


class TestGroupedGemm:
    def test_matches_dense_loop(self):
        M, K, N, G = 12, 8, 6, 3
        lhs = jnp.asarray(_rand(M, K, seed=3))
        rhs = jnp.asarray(_rand(G, K, N, seed=4))
        sizes = jnp.asarray([5, 4, 3], jnp.int32)
        out = grouped_gemm(lhs, rhs, sizes)
        ref = np.zeros((M, N), np.float32)
        start = 0
        for g, s in enumerate([5, 4, 3]):
            ref[start:start + s] = np.asarray(lhs)[start:start + s] @ \
                np.asarray(rhs)[g]
            start += s
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)

    def test_fallback_matches_ragged(self):
        M, K, N, G = 10, 4, 4, 2
        lhs = jnp.asarray(_rand(M, K, seed=5))
        rhs = jnp.asarray(_rand(G, K, N, seed=6))
        sizes = jnp.asarray([7, 3], jnp.int32)
        a = grouped_gemm(lhs, rhs, sizes, prefer_ragged=True)
        b = grouped_gemm(lhs, rhs, sizes, prefer_ragged=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)

    def test_sort_unsort_roundtrip(self):
        x = jnp.asarray(_rand(9, 3, seed=7))
        gid = jnp.asarray([2, 0, 1, 1, 0, 2, 2, 0, 1])
        srt, sizes, inv = sort_by_group(x, gid, 3)
        assert list(np.asarray(sizes)) == [3, 3, 3]
        np.testing.assert_allclose(np.asarray(unsort_by_group(srt, inv)),
                                   np.asarray(x))


class TestMoELayer:
    def test_single_expert_equals_dense_ffn(self):
        """E=1, k=1, ample capacity → exactly a dense swiglu FFN."""
        H, I = 16, 32
        layer = MoELayer(H, I, num_experts=1, top_k=1, capacity_factor=64.0)
        x = Tensor(jnp.asarray(_rand(2, 6, H, seed=8)))
        out = layer(x)
        wg = layer.w_gate._data[0]
        wu = layer.w_up._data[0]
        wd = layer.w_down._data[0]
        xa = x._data
        ref = (jax.nn.silu(xa @ wg) * (xa @ wu)) @ wd
        np.testing.assert_allclose(np.asarray(out._data), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        assert layer.l_aux is not None


class TestMoELayer2:
    def _mk(self, dropless, seed=11):
        H, I, E = 8, 16, 4
        rng = np.random.RandomState(seed)
        # capacity_factor=E/k makes capacity == T (provably no drops)
        layer = MoELayer(H, I, E, top_k=2, capacity_factor=E / 2.0,
                         dropless=dropless, renormalize=True)
        # deterministic weights shared between instances
        for p, nm in ((layer.gate_weight, "g"), (layer.w_gate, "wg"),
                      (layer.w_up, "wu"), (layer.w_down, "wd")):
            p._data = jnp.asarray(
                np.random.RandomState(abs(hash(nm)) % 2**31)
                .randn(*p.shape).astype(np.float32) * 0.1)
        return layer

    def test_dropless_matches_capacity_when_no_drops(self):
        a = self._mk(dropless=False)
        b = self._mk(dropless=True)
        x = Tensor(jnp.asarray(_rand(2, 4, 8, seed=12)))
        oa, ob = a(x), b(x)
        np.testing.assert_allclose(np.asarray(oa._data), np.asarray(ob._data),
                                   rtol=1e-3, atol=1e-4)

    def test_gradients_flow_to_experts(self):
        layer = self._mk(dropless=False)
        for p in layer.parameters():
            p.stop_gradient = False
        x = Tensor(jnp.asarray(_rand(2, 4, 8, seed=13)))
        out = layer(x)
        loss = (out * out).mean() + layer.l_aux * 0.01
        loss.backward()
        g = layer.w_up.grad
        assert g is not None and float(jnp.abs(g._data).max()) > 0
        assert layer.gate_weight.grad is not None

    def test_switch_layer_runs(self):
        layer = SwitchMoELayer(8, 16, 4)
        x = Tensor(jnp.asarray(_rand(2, 4, 8, seed=14)))
        out = layer(x)
        assert tuple(out.shape) == (2, 4, 8)
        assert np.isfinite(np.asarray(out._data)).all()


class TestExpertParallel:
    def test_ep_sharded_forward_matches_single_device(self):
        from paddle_tpu.distributed.mesh import build_hybrid_mesh, \
            mesh_context
        from paddle_tpu.distributed import fleet
        layer = TestMoELayer2()._mk(dropless=False)
        x = Tensor(jnp.asarray(_rand(2, 8, 8, seed=15)))
        ref = np.asarray(layer(x)._data)

        mesh = build_hybrid_mesh(dp_degree=2, ep_degree=4)
        with mesh_context(mesh):
            from jax.sharding import NamedSharding, PartitionSpec as P
            from paddle_tpu.distributed.mesh import sanitize_spec
            for p in layer.parameters():
                spec = sanitize_spec(mesh,
                                     getattr(p, "_sharding_spec", None))
                p._data = jax.device_put(p._data, NamedSharding(mesh, spec))
            out = layer(x)
        np.testing.assert_allclose(np.asarray(out._data), ref,
                                   rtol=1e-3, atol=1e-4)

    def test_moe_lm_loss_and_aux(self):
        from paddle_tpu.models.moe_llm import (MoEForCausalLM,
                                               qwen2_moe_tiny_config)
        cfg = qwen2_moe_tiny_config(sequence_parallel=False)
        model = MoEForCausalLM(cfg)
        rng = np.random.RandomState(0)
        ids = Tensor(jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 16)),
                                 jnp.int32))
        labels = Tensor(jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 16)),
                                    jnp.int32))
        loss, logits = model(ids, labels=labels)
        assert np.isfinite(float(loss))
        aux = model.model.aux_loss()
        assert aux is not None and float(aux) > 0


# ---------------------------------------------------------------------------
# the routed FFN's two permutations (ISSUE 67): hand-written backward,
# gathers alone, the owned rows only
# ---------------------------------------------------------------------------
from paddle_tpu.incubate import moe as moe_mod             # noqa: E402
from paddle_tpu.ops import grouped_gemm as gg               # noqa: E402

_T, _K, _H, _E = 32, 4, 16, 4          # 128 pair rows, 4 owning groups
_CHUNK = 32                            # the chunked cases' chunk


@jax.custom_vjp
def _drop_unowned_ct(rows, n_owned):
    """PR 66's `_owned_rows`, as autodiff needed it."""
    return rows


_drop_unowned_ct.defvjp(
    lambda rows, n: (rows, n),
    lambda n, ct: (jnp.where((jnp.arange(ct.shape[0]) < n)[:, None], ct, 0),
                   None))


def _autodiff_dispatch(xt, ids, mine, k, E):
    """Dispatch as it stood before ISSUE 67: repeat, sort, gather, and
    autodiff's own transposes (a scatter-add and the repeat's sum)."""
    rows = jnp.repeat(xt, k, axis=0)
    srt, sizes, inv = gg.sort_by_group(rows, ids.reshape(-1),
                                       E + (mine is not None))
    sizes = sizes[:E]
    if mine is not None:
        srt = _drop_unowned_ct(srt, jnp.sum(sizes))
    return srt, sizes, inv


def _autodiff_combine(down, gv, inv, mine):
    sel = gg.unsort_by_group(down, inv).reshape(gv.shape + (-1,))
    if mine is not None:
        sel = jnp.where(mine[..., None], sel, 0)
    return jnp.einsum("tk,tkh->th", gv.astype(sel.dtype), sel)


def _pairs(n_owned, seed, rows=_T * _K):
    """`rows` pair ids of which exactly `n_owned` (None: all) name one of
    the `_E` groups and the others the id `_E`, shuffled; the mask."""
    rng = np.random.RandomState(seed)
    n = rows if n_owned is None else n_owned
    ids = np.concatenate([rng.randint(0, _E, n), np.full(rows - n, _E)])
    ids = jnp.asarray(rng.permutation(ids), jnp.int32)
    return ids, None if n_owned is None else (ids < _E).reshape(-1, _K)


def _same(got, want, exact=True):
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    if exact:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-6)


#: rows owned: none, one, a chunk's edge - 1 / exact / + 1, two chunks
#: and a row, all T k (with a mask), and `held=None` (None)
OWNED = [0, 1, _CHUNK - 1, _CHUNK, _CHUNK + 1, 2 * _CHUNK + 1, _T * _K,
         None]


@pytest.fixture(params=[_CHUNK, None], ids=["chunked", "one-chunk"])
def chunk(request, monkeypatch):
    """The passes in sorted order walk chunks of 32 rows (part 3), or
    the code's own chunk, which holds all 128 rows (parts 1 + 2)."""
    if request.param is not None:
        monkeypatch.setattr(gg, "PAIR_ROW_CHUNK", request.param)
    return request.param


class TestPairRowPermutations:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("n_owned", OWNED)
    def test_dispatch_equals_autodiff(self, chunk, n_owned, dtype):
        """Values (zeros past the last owned row where chunked) and the
        gradient, bit for bit; rubbish in the un-owned rows' cotangent
        reaches nothing."""
        ids, mine = _pairs(n_owned, 3)
        keys = jax.random.split(jax.random.key(n_owned or 0), 2)
        xt = jax.random.normal(keys[0], (_T, _H), dtype)
        ct = jax.random.normal(keys[1], (_T * _K, _H), dtype)
        owned = jnp.arange(_T * _K) < (_T * _K if n_owned is None
                                       else n_owned)
        want, pull_w = jax.vjp(
            lambda x: _autodiff_dispatch(x, ids, mine, _K, _E)[0], xt)
        (got, sizes, order, inv, n), pull = jax.vjp(
            lambda x: gg.dispatch_pair_rows(x, ids.reshape(_T, _K), mine,
                                            _E), xt)
        chunked = chunk is not None and mine is not None
        _same(got, jnp.where(owned[:, None], want, 0) if chunked else want)
        assert (n is None) == (n_owned is None)
        if n_owned is not None:
            assert int(n) == n_owned == int(jnp.sum(sizes))
        np.testing.assert_array_equal(np.asarray(ids)[np.asarray(order)],
                                      np.sort(np.asarray(ids)))
        np.testing.assert_array_equal(np.asarray(order)[np.asarray(inv)],
                                      np.arange(_T * _K))
        # the integer outputs' cotangents are float0; rubbish where no
        # group owns the row, as the chip's grouped GEMM leaves it
        cts = (jnp.where(owned[:, None], ct, jnp.nan),) + tuple(
            None if a is None else np.zeros(a.shape, jax.dtypes.float0)
            for a in (sizes, order, inv, n))
        _same(pull(cts)[0], pull_w(cts[0])[0])
        assert int(gg.pair_rows_visited(_T * _K, n)) == (
            min(-(-n_owned // _CHUNK) * _CHUNK, _T * _K) if chunked
            else _T * _K)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("n_owned", OWNED)
    def test_combine_equals_autodiff(self, chunk, n_owned, dtype):
        """`y` and `d_down` bit for bit; `d_gv` element for element in
        bfloat16 and to the last bits in float32 (the same products
        accumulated in float32 as the einsum's, summed row by row in
        sorted space: the CPU's dot takes another order there); rubbish
        in the un-owned rows of `down` reaches nothing."""
        ids, mine = _pairs(n_owned, 5)
        order, inv, _ = gg.group_order(ids, _E + 1)
        keys = jax.random.split(jax.random.key(7 + (n_owned or 0)), 3)
        owned = jnp.arange(_T * _K) < (_T * _K if n_owned is None
                                       else n_owned)
        down = jnp.where(owned[:, None], jax.random.normal(
            keys[0], (_T * _K, _H), dtype), jnp.nan)
        gv = jax.random.uniform(keys[1], (_T, _K), jnp.float32)
        if mine is not None:
            gv = jnp.where(mine, gv, 0.0)               # as `_route` does
        dy = jax.random.normal(keys[2], (_T, _H), dtype)
        n = None if n_owned is None else jnp.asarray(n_owned, jnp.int32)
        want, pull_w = jax.vjp(
            lambda d, g: _autodiff_combine(d, g, inv, mine), down, gv)
        got, pull = jax.vjp(
            lambda d, g: gg.combine_pair_rows(d, g, order, inv, mine, n),
            down, gv)
        _same(got, want)
        (d_down, d_gv), (w_down, w_gv) = pull(dy), pull_w(dy)
        assert d_gv.dtype == gv.dtype and d_down.dtype == down.dtype
        _same(d_down, w_down)
        _same(d_gv, w_gv, exact=dtype == jnp.bfloat16)

    @staticmethod
    def _autodiff_ffn(xt, gates, wg, wu, wd, *, top_k, renormalize, held):
        """`dropless_expert_ffn` as it stood before ISSUE 67."""
        gv, topi, local, mine = moe_mod._route(gates, top_k, renormalize,
                                               held, 1.0)
        srt, sizes, inv = _autodiff_dispatch(xt, local, mine, top_k,
                                             wu.shape[0])
        up = grouped_gemm(srt, wu, sizes)
        act = jax.nn.silu(grouped_gemm(srt, wg, sizes)) * up
        return _autodiff_combine(grouped_gemm(act, wd, sizes), gv, inv,
                                 mine), topi

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("held, shy", [
        (None, False), ((2, 4), False), ((0, 8), False), ((2, 4), True)],
        ids=["all", "held-2..5", "held-all-masked", "held-none-chosen"])
    def test_the_routed_ffn_equals_autodiff(self, chunk, held, shy, dtype):
        """`y` and the gradients of the input, the router and the three
        stacks through `dropless_expert_ffn` against jax's own autodiff
        of the expression it replaced: the stacks' bit for bit; what
        `d_gv` feeds (the router, the input) element for element in
        bfloat16, to the last bits in float32.  `shy`: the router never
        chooses a held expert (no row is owned)."""
        E, W = 8, 8
        keys = jax.random.split(jax.random.key(1), 6)
        x = jax.random.normal(keys[0], (_T, _H), dtype)
        wr = jax.random.normal(keys[1], (_H, E), jnp.float32) * 0.5
        sl = slice(None) if held is None else slice(held[0], sum(held))
        wg, wu = (jax.random.normal(kk, (E, _H, W), dtype)[sl] * 0.2
                  for kk in keys[2:4])
        wd = (jax.random.normal(keys[4], (E, W, _H), dtype) * 0.2)[sl]
        ct = jax.random.normal(keys[5], (_T, _H), dtype)
        away = jnp.where((jnp.arange(E) >= 2) & (jnp.arange(E) < 6),
                         -1e4, 0.0) if shy else 0.0

        def through(ffn):
            def f(x, wr, wg, wu, wd):
                gates = jax.nn.softmax(x.astype(jnp.float32) @ wr + away,
                                       -1)
                return ffn(x, gates, wg, wu, wd, top_k=_K,
                           renormalize=True, held=held)[0]
            y, pull = jax.vjp(f, x, wr, wg, wu, wd)
            return (y,) + pull(ct)

        got = through(moe_mod.dropless_expert_ffn)
        want = through(self._autodiff_ffn)
        if shy:
            assert not np.asarray(got[0].astype(jnp.float32)).any()
        for name, a, b in zip(("y", "dx", "router", "gate", "up", "down"),
                              got, want):
            _same(a, b, exact=dtype == jnp.bfloat16
                  or name not in ("dx", "router"))

    @pytest.mark.parametrize("held", [None, (2, 4)], ids=["all", "held"])
    def test_a_forward_lowers_to_the_text_it_replaced(self, held):
        """A call that is not differentiated and holds one chunk (every
        serving launch) lowers to the replaced expression's text, word
        for word: the rules add nothing to a forward."""
        E = 8 if held is None else held[1]
        sh = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
        args = (sh(64, _H), jax.ShapeDtypeStruct((64, 8), jnp.float32),
                sh(E, _H, 8), sh(E, _H, 8), sh(E, 8, _H))
        text = [jax.jit(lambda *a, f=ffn: f(
            *a, top_k=2, renormalize=True, held=held)).lower(*args).as_text()
            for ffn in (moe_mod.dropless_expert_ffn, self._autodiff_ffn)]
        assert text[0] == text[1]
