"""Kernel certification registry (paddle_tpu.ops.oracles).

Importing the ops modules populates the registry as a side effect; this
file checks the certification contract end to end: every authored kernel
is registered, every reference resolves to a callable, every named
parity-test node exists in the tree, and the entries whose parity_test
points HERE are re-run against their XLA reference (interpret mode on
CPU). paddlelint rule PK105 enforces the same contract statically.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# registration side effects                                  # noqa: F401
from paddle_tpu.ops import (fused, pallas_flash, pallas_flashmask,
                            pallas_gmm, pallas_megadecode,
                            pallas_megafront, pallas_mla,
                            pallas_paged, pallas_ragged, quant)
from paddle_tpu.ops.oracles import oracles, resolve_reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXPECTED = {
    "fused_rms_norm", "fused_layer_norm",
    "fused_bias_residual_layer_norm", "fused_moe_dispatch_combine",
    "fused_rope", "fused_rope_append", "fused_append_rows", "swiglu",
    "mla_decode_attention", "gmm", "int4_dequantize",
    "weight_only_linear", "flash_sdpa", "flashmask_sdpa",
    "paged_decode_attention_v2", "ragged_paged_attention",
    "fused_oproj_norm", "fused_ffn", "fused_qkv_rope_append",
}


class TestRegistry:
    def test_every_authored_kernel_registered(self):
        assert EXPECTED <= set(oracles())

    def test_references_resolve_to_callables(self):
        for name, entry in sorted(oracles().items()):
            assert callable(resolve_reference(entry)), name

    def test_parity_test_nodes_exist(self):
        for name, entry in sorted(oracles().items()):
            path, sep, node = entry.parity_test.partition("::")
            assert sep, (name, entry.parity_test)
            full = os.path.join(REPO, path)
            assert os.path.isfile(full), (name, path)
            first = node.split("::")[0]
            with open(full) as f:
                text = f.read()
            assert re.search(rf"(class|def)\s+{re.escape(first)}\b",
                             text), (name, entry.parity_test)


class TestOracleParity:
    """Runtime side of the entries registered with
    parity_test=tests/test_oracles.py::TestOracleParity (the kernels
    whose pre-existing suites pin behavior but not a named oracle)."""

    def _check(self, name, *args, atol=2e-5):
        entry = oracles()[name]
        want = resolve_reference(entry)(*args)   # pure: runs first
        got = entry.kernel(*args)
        if not isinstance(got, tuple):
            got, want = (got,), (want,)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=atol, rtol=atol)

    def test_bias_residual_layer_norm(self):
        rng = np.random.default_rng(0)
        T, H = 8, 256
        x, r = (jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
                for _ in range(2))
        b, w, lb = (jnp.asarray(rng.standard_normal(H), jnp.float32)
                    for _ in range(3))
        self._check("fused_bias_residual_layer_norm", x, r, b, w, lb)

    def test_moe_dispatch_combine(self):
        rng = np.random.default_rng(1)
        T, K, E, C = 8, 2, 8, 128
        keep = jnp.asarray(rng.integers(0, 2, (T, K, E)), jnp.float32)
        oh = jnp.asarray(rng.integers(0, 2, (T, K, C)), jnp.float32)
        gv = jnp.asarray(rng.random((T, K)), jnp.float32)
        self._check("fused_moe_dispatch_combine", keep, oh, gv)

    @pytest.mark.parametrize("pair", [False, True], ids=["one", "pair"])
    def test_append_rows(self, pair):
        from paddle_tpu.ops.fused import append_run_table, append_tile
        rng = np.random.default_rng(2)
        KV, total, psz, D, T = 2, 4, 16, 128, 12
        pages, rows = (tuple(
            jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in range(2)) for shape in ((KV, total, psz, D), (T, KV, D)))
        # a decode row, an idle one, a chunk across a tile and a page
        page_idx = jnp.asarray([1, 0] + [2] * 6 + [3] * 2 + [0] * 2,
                               jnp.int32)
        page_off = jnp.asarray([5, 0] + list(range(10, 16)) + [0, 1, 0, 0],
                               jnp.int32)
        runs = jax.jit(append_run_table, static_argnames=(
            "tile", "max_runs"))(
            jnp.asarray([0, 1, 2]), jnp.asarray([1, 0, 8]), page_idx,
            page_off, tile=append_tile(jnp.float32, psz), max_runs=5)
        if pair:
            self._check("fused_append_rows", pages, rows, runs, atol=0)
        else:
            self._check("fused_append_rows", pages[0], rows[0], runs,
                        atol=0)
