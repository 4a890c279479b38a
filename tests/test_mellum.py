"""The Mellum family (JetBrains Mellum2 pattern: sliding / full layers,
a routed FFN in every layer) through the TRAINER's step, held to the
plain reference ``benchmarks/lib/reference_mellum.py`` on seeded weights
at toy widths: loss, load-balance term and gradients in float32; and the
share test the model-configs guide asks for — the shares of one
expert-parallel layer add up to the uncut reference, forward and
backward."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.lib import reference_mellum as ref
from benchmarks.systems.mellum_pretrain import LAYER_WEIGHTS, _layers
from paddle_tpu.incubate.moe import dropless_expert_ffn
from paddle_tpu.ops import grouped_gemm
from paddle_tpu.models.mellum import (KINDS, MellumForCausalLM,
                                      mellum_tiny_config)
from paddle_tpu.trainer.pretrain import (PretrainConfig,
                                         build_llama_pretrain_step,
                                         flops_per_token,
                                         flops_per_token_hw,
                                         make_hybrid_mesh_for)

B, S = 2, 64


def _ref_kw(mc, seq):
    tables = {k: ref.rope_tables(mc.rope_parameters[k], mc.head_dim, seq)
              for k in KINDS}
    return dict(kinds=mc.layer_types, tables=tables,
                nq=mc.num_attention_heads, nkv=mc.num_key_value_heads,
                d=mc.head_dim, eps=mc.rms_norm_eps,
                sliding_window=mc.sliding_window,
                top_k=mc.num_experts_per_tok, held=mc.experts_held,
                c_aux=mc.router_aux_loss_coef, q_block=32, expert_block=2,
                head_block=32)


@pytest.fixture(scope="module")
def stepped():
    """One float32 trainer step of a tiny Mellum holding experts 2..5 of
    8, unclipped, beside the reference's loss and gradients at the same
    weights on the same batch."""
    paddle.seed(11)
    mc = mellum_tiny_config(experts_held=(2, 4))
    cfg = PretrainConfig(mc, global_batch=B, seq_len=S, scan_layers=False,
                         remat="full", param_dtype="float32", grad_clip=1e9,
                         ce_chunks=2)
    mesh = make_hybrid_mesh_for(cfg, devices=jax.devices()[:1])
    state, jstep, meta = build_llama_pretrain_step(cfg, mesh)
    # q_proj at 4 x its Xavier draw: a query attends to a few keys, so a
    # lost band or rotary table shows in every number below
    sharper = lambda tree: {**tree, "stacked": {  # noqa: E731
        **tree["stacked"], "self_attn.q_proj.weight":
        tree["stacked"]["self_attn.q_proj.weight"] * 4.0}}
    state = state._replace(params=sharper(state.params),
                           master=sharper(state.master))
    rng = np.random.RandomState(5)
    ids = jnp.asarray(rng.randint(0, mc.vocab_size, (B, S)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, mc.vocab_size, (B, S)), jnp.int32)
    master = jax.tree.map(jnp.array, state.master)      # the step donates

    def f(p):
        outer = p["outer"]
        x = jnp.take(outer["model.embed_tokens.weight"], ids, 0)
        return ref.loss(x, _layers(p["stacked"], mc.num_hidden_layers),
                        outer["model.norm.weight"],
                        outer["lm_head.weight"], labels, **_ref_kw(mc, S))
    with ref.highest():
        (loss, aux), grads = jax.value_and_grad(f, has_aux=True)(master)
    state, m = jstep(state, ids, labels)
    return mc, m, state, float(loss), float(aux), grads


def test_loss_and_aux_match_the_reference(stepped):
    mc, m, _, loss, aux, _ = stepped
    assert float(m["loss"]) == pytest.approx(loss, rel=2e-5)
    assert float(m["aux_loss"]) == pytest.approx(aux, rel=2e-5)
    # all k choices: a uniform router reads k a layer (first-only: 1)
    assert 0.9 * mc.num_experts_per_tok * mc.num_hidden_layers < aux
    assert float(m["moe_pairs_routed"]) == \
        B * S * mc.num_experts_per_tok * mc.num_hidden_layers
    assert 0 < float(m["moe_pairs_held"]) < float(m["moe_pairs_routed"])
    assert float(m["moe_expert_rows_max"]) >= float(
        m["moe_expert_rows_mean"])


@pytest.mark.parametrize("group,key", [
    ("outer", "model.embed_tokens.weight"),
    ("outer", "model.norm.weight"),
    ("outer", "lm_head.weight"),
    ("stacked", "mlp.gate_weight"),          # the routers
    ("stacked", "mlp.w_down"),               # the held experts
    ("stacked", "mlp.w_gate"),
    ("stacked", "self_attn.q_proj.weight"),
    ("stacked", "self_attn.k_proj.weight"),
    ("stacked", "input_layernorm.weight"),   # a norm of both layer kinds
    ("stacked", "post_attention_layernorm.weight"),
])
def test_gradients_match_the_reference(stepped, group, key):
    """The first Adam moment after one unclipped step is 0.1 x the
    gradient: every layer of both kinds, through the sort, the grouped
    GEMM and the combine, against `jax.grad` of the plain reference."""
    _, _, state, _, _, grads = stepped
    got = np.asarray(state.opt_state.moment1[group][key], np.float64) / 0.1
    want = np.asarray(grads[group][key], np.float64)
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-4 * np.abs(want).max())


def test_the_model_file_agrees_with_the_trainers_step():
    """`MellumForCausalLM`'s own forward (the eager path) gives the
    reference's loss too: the layer kinds in order, each its window and
    its rotary table."""
    paddle.seed(4)
    mc = mellum_tiny_config(experts_held=(0, 8))
    model = MellumForCausalLM(mc)
    rng = np.random.RandomState(2)
    ids = rng.randint(0, mc.vocab_size, (B, S))
    labels = rng.randint(0, mc.vocab_size, (B, S))
    loss, _ = model(paddle.to_tensor(ids), paddle.to_tensor(labels))
    sd = {k: v._data for k, v in model.state_dict().items()}
    layers = [{k: sd[f"model.layers.{i}.{n}"]
               for k, n in LAYER_WEIGHTS.items()}
              for i in range(mc.num_hidden_layers)]
    with ref.highest():
        want, _ = ref.loss(
            jnp.take(sd["model.embed_tokens.weight"], jnp.asarray(ids), 0),
            layers, sd["model.norm.weight"], sd["lm_head.weight"],
            jnp.asarray(labels), **_ref_kw(mc, S))
    assert float(loss._data) == pytest.approx(float(want), rel=2e-5)


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Four chips' shares [0, 4) ... [12, 16) of ONE routed layer:
    their outputs, and their gradients at the layer's input, add up to
    what the uncut reference gives for the whole layer."""
    T, H, W, E, k = 96, 32, 16, 16, 4
    keys = jax.random.split(jax.random.key(3), 6)
    x = jax.random.normal(keys[0], (T, H), jnp.float32)
    wr = jax.random.normal(keys[1], (H, E), jnp.float32) * 0.5
    wg, wu = (jax.random.normal(kk, (E, H, W), jnp.float32) * 0.2
              for kk in keys[2:4])
    wd = jax.random.normal(keys[4], (E, W, H), jnp.float32) * 0.2
    ct = jax.random.normal(keys[5], (T, H), jnp.float32)

    def share(x, first, count):
        sl = slice(first, first + count)
        gates = jax.nn.softmax(x @ wr, -1)
        y, _ = dropless_expert_ffn(x, gates, wg[sl], wu[sl], wd[sl],
                                   top_k=k, renormalize=True,
                                   held=(first, count))
        return y

    def whole(x):
        y, _ = ref.routed_ffn(x, {"wr": wr, "wg": wg, "wu": wu, "wd": wd},
                              top_k=k, held=(0, E), expert_block=4)
        return y

    with ref.highest():
        want, pull = jax.vjp(whole, x)
        want_dx, = pull(ct)
        got = got_dx = 0.0
        for first in range(0, E, 4):
            y, pull = jax.vjp(lambda a: share(a, first, 4), x)
            got, got_dx = got + y, got_dx + pull(ct)[0]
            assert float(jnp.abs(y).max()) > 0      # every share works
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got_dx, want_dx, rtol=1e-4, atol=1e-5)


def test_flops_count_the_pairs_held_and_the_keys_visible():
    mc = mellum_tiny_config(experts_held=(0, 4))
    h, w = mc.hidden_size, mc.moe_intermediate_size
    attn = h * mc.head_dim * (mc.num_attention_heads
                              + 2 * mc.num_key_value_heads) \
        + mc.num_attention_heads * mc.head_dim * h
    outside = attn + h * mc.num_experts + 2 * h
    n = lambda pairs: (2 * mc.vocab_size * h + h  # noqa: E731
                       + 4 * (outside + pairs * 3 * h * w))
    # a uniform router: top-2 x 4 of 8 held = one pair a token
    assert flops_per_token(mc) == 6.0 * n(1.0)
    assert flops_per_token(mc, pairs_held=0.75) == 6.0 * n(0.75)
    # window 24 over 64 positions: query i sees min(i + 1, 24) keys
    band = sum(min(i + 1, 24) for i in range(64)) / 64
    full = 65 / 2
    assert flops_per_token_hw(mc, 64) == pytest.approx(
        6.0 * n(1.0) + 12.0 * mc.num_attention_heads * mc.head_dim
        * (3 * band + full))


@pytest.mark.parametrize("chunk", [None, 32], ids=["one-chunk", "chunked"])
def test_rows_no_held_expert_owns_give_no_gradient(monkeypatch, chunk):
    """On the chip the grouped GEMM leaves whatever it finds in the rows
    past its last group, forward AND backward (the CPU's writes zeros);
    `dropless_expert_ffn`'s dispatch and combine use neither those
    rows' values nor their cotangents (`ops.grouped_gemm`'s two
    hand-written rules; before ISSUE 67, `_owned_rows`).  Through a
    grouped GEMM that fills them with rubbish both ways, the default
    call's output and gradients are still the plain reference's — with
    every pass walking all 128 pair rows, and with the passes in sorted
    order walking chunks of 32 up to the last owned row."""
    if chunk is not None:
        monkeypatch.setattr(grouped_gemm, "PAIR_ROW_CHUNK", chunk)
    from paddle_tpu.incubate import moe

    @jax.custom_vjp
    def rubbish(x, n):
        rows = jnp.arange(x.shape[0])[:, None]
        return jnp.where(rows < n, x, 1e3)

    rubbish.defvjp(
        lambda x, n: (rubbish(x, n), n),
        lambda n, ct: (jnp.where(jnp.arange(ct.shape[0])[:, None] < n, ct,
                                 -1e3), None))
    real = moe.grouped_gemm
    T, H, W, E, k = 64, 32, 16, 8, 2
    keys = jax.random.split(jax.random.key(9), 5)
    x = jax.random.normal(keys[0], (T, H), jnp.float32)
    wr = jax.random.normal(keys[1], (H, E), jnp.float32) * 0.5
    wg, wu = (jax.random.normal(kk, (4, H, W), jnp.float32) * 0.2
              for kk in keys[2:4])
    wd = jax.random.normal(keys[4], (4, W, H), jnp.float32) * 0.2

    def loss(x, wg, wu, wd):
        # the DEFAULT call: what every caller with `held` differentiates
        y, _ = moe.dropless_expert_ffn(
            x, jax.nn.softmax(x @ wr, -1), wg, wu, wd, top_k=k,
            renormalize=True, held=(2, 4))
        return jnp.sum(jnp.sin(y))

    def plain(x, wg, wu, wd):
        y, _ = ref.routed_ffn(x, {"wr": wr, "wg": wg, "wu": wu, "wd": wd},
                              top_k=k, held=(2, 4), expert_block=2)
        return jnp.sum(jnp.sin(y))

    with ref.highest():
        want = jax.value_and_grad(plain, (0, 1, 2, 3))(x, wg, wu, wd)
        monkeypatch.setattr(
            moe, "grouped_gemm", lambda lhs, rhs, sizes: rubbish(
                real(rubbish(lhs, jnp.sum(sizes)), rhs, sizes),
                jnp.sum(sizes)))
        got = jax.value_and_grad(loss, (0, 1, 2, 3))(x, wg, wu, wd)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# ------------------------------------------- the permutations' backward
@pytest.fixture(scope="module", params=[None, 64],
                ids=["one-chunk", "chunked"])
def toy_step(request):
    """The toy trainer step (256 pair rows a layer, experts 2..5 of 8
    held) with every pass walking all the pair rows, and with the passes
    in sorted order walking chunks of 64: its compiled text, one step's
    metrics, the chunk."""
    mp = pytest.MonkeyPatch()
    chunk = request.param or grouped_gemm.PAIR_ROW_CHUNK
    mp.setattr(grouped_gemm, "PAIR_ROW_CHUNK", chunk)
    try:
        paddle.seed(11)
        mc = mellum_tiny_config(experts_held=(2, 4))
        cfg = PretrainConfig(mc, global_batch=B, seq_len=S, remat="full",
                             param_dtype="float32", ce_chunks=2)
        mesh = make_hybrid_mesh_for(cfg, devices=jax.devices()[:1])
        state, jstep, _ = build_llama_pretrain_step(cfg, mesh)
        rng = np.random.RandomState(5)
        ids = jnp.asarray(rng.randint(0, mc.vocab_size, (B, S)), jnp.int32)
        text = jstep.lower(state, ids, ids).compile().as_text()
        _, m = jstep(state, ids, ids)
        return mc, text, {k: float(v) for k, v in m.items()}, chunk
    finally:
        mp.undo()


def _moe_ops(text):
    """(scope as written, primitive, direction, result type) of every
    instruction of a compiled text named under the dispatch or the
    combine."""
    from paddle_tpu.observability import attribution
    out = []
    for m in re.finditer(r'= (\S+) [\w-]+\(.*op_name="([^"]*)"', text):
        own = attribution._path_own(m.group(2))
        if own in ("moe_dispatch", "moe_combine"):
            out.append((own, m.group(2).rsplit("/", 1)[-1],
                        attribution._path_scope(m.group(2))[1],
                        m.group(1)))
    return out


def test_the_permutations_backward_holds_no_scatter(toy_step):
    """The backward of dispatch and combine is gathers: no scatter runs
    under either name in the backward pass, and the only ones left at
    all are the forward's (and its recomputation's) `bincount` of the
    groups' sizes, five integers here."""
    ops = _moe_ops(toy_step[1])
    scatters = [o for o in ops if o[1].startswith("scatter")]
    assert not [o for o in scatters if o[2] == "bwd"], scatters
    assert scatters and all(
        o[0] == "moe_dispatch" and o[3].startswith("s32[5]")
        for o in scatters), scatters


def test_the_permutations_gathers_carry_the_call_sites_names(toy_step):
    """The hand-written rules open no scope of their own: their gathers
    read `fwd` / `remat` / `bwd` under the call site's names
    (`observability.attribution`), so the benchmark's readers find them.
    The combine's forward is not recomputed: its backward reads the
    sorted rows."""
    gathers = {(o[0], o[2]) for o in _moe_ops(toy_step[1])
               if o[1] == "gather"}
    assert gathers == {("moe_dispatch", "fwd"), ("moe_dispatch", "remat"),
                       ("moe_dispatch", "bwd"), ("moe_combine", "fwd"),
                       ("moe_combine", "bwd")}


def test_the_step_says_the_pair_rows_it_moved(toy_step):
    """`moe_pair_rows_moved`: all the pair rows of a step whose calls
    hold one chunk; whole chunks up to a layer's last owned row where
    the passes in sorted order stop there."""
    mc, _, m, chunk = toy_step
    rows = B * S * mc.num_experts_per_tok
    assert m["moe_pairs_routed"] == rows * mc.num_hidden_layers
    if rows <= chunk:
        assert m["moe_pair_rows_moved"] == m["moe_pairs_routed"]
    else:
        assert m["moe_pair_rows_moved"] % chunk == 0
        assert m["moe_pairs_held"] <= m["moe_pair_rows_moved"] \
            < m["moe_pairs_held"] + chunk * mc.num_hidden_layers
        assert m["moe_pair_rows_moved"] < m["moe_pairs_routed"]


@pytest.mark.parametrize("held, chunk", [
    (None, 64), ((2, 4), None), ((2, 4), 64), ((0, 8), 64), ((6, 2), 48)])
def test_a_layer_counts_the_rows_its_dispatch_visits(monkeypatch, held,
                                                     chunk):
    """`MoELayer` leaves, behind its `routing_stats`, the rows its
    dispatch's forward visited: the sorted rows up to there hold the
    owned pairs' tokens and zeros follow (256 pair rows; chunks of 64,
    and of 48, which does not divide them)."""
    from paddle_tpu.incubate.moe import MoELayer, _route
    if chunk is not None:
        monkeypatch.setattr(grouped_gemm, "PAIR_ROW_CHUNK", chunk)
    paddle.seed(3)
    T, H, E, k = 128, 16, 8, 2
    layer = MoELayer(H, 8, E, top_k=k, dropless=True, experts_held=held)
    x = jax.random.normal(jax.random.key(4), (1, T, H), jnp.float32)
    layer(paddle.to_tensor(x))
    stats = np.asarray(layer.l_stats._data)
    assert stats.shape == (6,) and stats[0] == T * k
    gates = jax.nn.softmax(x[0] @ layer.gate_weight._data, -1)
    _, _, local, mine = _route(gates, k, layer.renormalize, held, 1.0)
    srt, _, _, _, n_owned = grouped_gemm.dispatch_pair_rows(
        x[0], local, mine, E if held is None else held[1])
    if held is None or chunk is None:
        assert stats[5] == T * k
        return
    assert int(n_owned) == stats[1]
    assert stats[5] == min(-(-stats[1] // chunk) * chunk, T * k)
    live = np.abs(np.asarray(srt)).sum(-1) > 0
    assert live[:int(n_owned)].all() and not live[int(n_owned):].any()
