"""vmemmodel (paddle_tpu.analysis.vmemmodel): the static per-kernel
memory model behind the PF rule family.

The ISSUE PR13 acceptance gate lives here: every one of the 19 kernels
registered in observability/costmodel.py must have a canonical entry
whose BlockSpec-derived HBM bytes agree with the registered CostEstimate
within COST_DRIFT_RTOL, every canonical launch must fit the 16 MiB
per-core VMEM budget, and the decode-chain fusion scan must surface the
oproj->ffn seam the ISSUE-14 mega-kernels deliberately keep (the old
rms->swiglu advisory is resolved — that pair now lives inside
fused_oproj_norm/fused_ffn)."""

import os

import pytest

from paddle_tpu.analysis import kernelmodel as km
from paddle_tpu.analysis import vmemmodel as vm
from paddle_tpu.analysis.callgraph import PackageIndex
from paddle_tpu.analysis.runner import discover

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def index():
    return PackageIndex.from_files(
        discover(os.path.join(REPO, "paddle_tpu")))


@pytest.fixture(scope="module")
def sites(index):
    return vm.canonical_sites(index)


class TestCanonicalCoverage:
    def test_every_registered_cost_kernel_has_an_entry(self):
        cm = vm.load_costmodel()
        assert cm is not None
        registered = set(cm.costs())
        modeled = {e["kernel"] for e in vm.CANONICAL.values()}
        assert modeled == registered
        assert len(registered) == 27

    def test_every_entry_resolves_to_one_repo_site(self, sites):
        missing = sorted(set(vm.CANONICAL) - set(sites))
        assert missing == []


class TestCostAgreement:
    """PF406's substance: the cost registry and the committed BlockSpecs
    describe the same kernels."""

    def test_all_canonical_sites_within_tolerance(self, index):
        recs = vm.derive_cost_bytes(index)
        assert len(recs) == 31
        bad = [(r["kernel"], r["status"], r.get("rel_err"))
               for r in recs if r["status"] != "ok"]
        assert bad == []

    def test_most_kernels_are_byte_exact(self, index):
        # only flashmask carries structural slack (its registered cost
        # reuses flash's segment terms); everything else must be exact
        recs = {r["kernel"]: r for r in vm.derive_cost_bytes(index)}
        inexact = sorted(k for k, r in recs.items()
                         if r["rel_err"] and r["rel_err"] > 1e-9)
        assert inexact in ([], ["flashmask_sdpa"])
        assert recs["flashmask_sdpa"]["rel_err"] < vm.COST_DRIFT_RTOL

    def test_drift_detected_when_cost_registry_lies(self, index):
        class _FakeCost:
            def cost(self, name, **kw):
                real = vm.load_costmodel().cost(name, **kw)
                class _C:
                    bytes_read = int(real.bytes_read * 2)
                    bytes_written = int(real.bytes_written * 2)
                    breakdown = {k: v * 2 for k, v in
                                 (real.breakdown or {}).items()}
                return _C()
        recs = vm.derive_cost_bytes(index, cost_module=_FakeCost())
        assert any(r["status"] == "drift" for r in recs)


class TestFootprints:
    def test_all_canonical_launches_fit_vmem(self, sites):
        for qn, site in sites.items():
            fp = vm.site_footprint(site, vm.CANONICAL[qn])
            assert fp["bytes"] <= vm.VMEM_BYTES_PER_CORE, (
                qn, fp["bytes"])

    def test_footprints_are_nonzero(self, sites):
        for qn, site in sites.items():
            fp = vm.site_footprint(site, vm.CANONICAL[qn])
            assert fp["bytes"] > 0, qn

    def test_grid_swept_blocks_double_buffer(self, sites):
        # _rms_forward: x in/out blocks sweep the grid (x2 double
        # buffering), the weight block does not
        site = sites["_rms_forward"]
        entry = vm.CANONICAL["_rms_forward"]
        b = vm.site_bindings(entry)
        bt, h = b["bt"], b["H"]
        expected = (bt * h * 2) * 2 * 2 + h * 2   # x, out dbl-buffered
        fp = vm.site_footprint(site, entry)
        assert fp["bytes"] == expected
        assert fp["unresolved"] == 0

    @pytest.mark.parametrize(
        "family", ["llama", "laguna", "looped", "chunk_summary", "latent"])
    def test_the_ragged_kernels_head_blocks_fit_vmem(self, sites, family):
        # the serving cells' cells of VMEM: what the model resolves (the
        # q and out tiles of a block of heads and of tiles, double-
        # buffered; the f32 accumulator, m and l, the read-ahead
        # cursor), the two rings it counts unresolved beside the two
        # pools in HBM (their dtype is the pools') added by hand, and
        # the head block and the tile block in the bindings are the ones
        # the kernel takes at those shapes (latent attention's value is
        # its row's first 512 columns)
        from paddle_tpu.ops import pallas_ragged as pr
        entry = vm.CANONICAL["ragged_paged_attention"]
        b = vm.site_bindings(entry, family)
        hb, tb, rows, D, psz = b["hb"], b["tb"], b["rows"], b["D"], b["psz"]
        latent = family == "latent"
        fp = vm.site_footprint(sites["ragged_paged_attention"], entry, b)
        assert fp["unresolved"] == 4
        tile = hb * tb * rows * D
        # (m and l lane-replicated, [rows, 128] each, since PR 55: what
        # `_block_vmem` always charged the [rows, 1] columns as)
        assert fp["bytes"] == (4 * tile * 2 + tile * 4
                               + 2 * hb * tb * rows * 128 * 4 + 2 * 4)
        assert pr.ragged_head_block(b["KV"], rows, D, psz, 2,
                                    latent=latent) == hb
        tiles = 144 if latent else 3    # of 2 tokens; of 128 / 96 rows
        assert pr.ragged_tile_block(hb, tiles, rows, D, psz, 2,
                                    512 if latent else None) == tb
        assert (tb > 1) == latent
        assert pr._page_buffers(hb * psz * D * 2) == b["depth"]
        rings = 2 * b["depth"] * hb * psz * D * 2
        # with the rings, the kernel's own sum to the byte (which leaves
        # out the cursor's two words of SMEM)
        assert fp["bytes"] - 2 * 4 + rings == pr._block_vmem(
            hb, rows, D, psz, 2, tb) <= pr._VMEM_BUDGET \
            < vm.VMEM_BYTES_PER_CORE
        if latent:      # its own launch: a [rows, 512] output, ONE ring
            assert pr._block_vmem(hb, rows, D, psz, 2, tb, 512) \
                < pr._block_vmem(hb, rows, D, psz, 2, tb)

    def test_unresolved_blocks_are_counted_not_guessed(self, sites):
        # paged_decode_attention_v2 declares two data-dtype scratch
        # buffers the static model cannot size; they must surface in
        # `unresolved`, not silently inflate/deflate the byte total
        site = sites["paged_decode_attention_v2"]
        fp = vm.site_footprint(site, vm.CANONICAL[
            "paged_decode_attention_v2"])
        assert fp["unresolved"] == 2


class TestGridOk:
    def test_canonical_grids_divide(self, sites):
        for qn, site in sites.items():
            b = vm.site_bindings(vm.CANONICAL[qn])
            assert vm.grid_ok(site, b), qn

    def test_indivisible_grid_rejected(self, sites):
        site = sites["_rms_forward"]
        b = vm.site_bindings(vm.CANONICAL["_rms_forward"])
        b["bt"] = 192                      # 8 % 192 != 0
        assert not vm.grid_ok(site, b)


class TestHelperRebuild:
    """Flash/flashmask route their specs through a local `_specs` helper
    the call-site Env cannot see; the model rebuilds them from the
    helper body (the idiom test_costmodel.py pins for the cost suite)."""

    def test_flash_specs_rebuilt(self, sites):
        site = sites["_flash_fwd_impl"]
        in_specs, out_specs = vm._site_specs(
            site, vm.CANONICAL["_flash_fwd_impl"])
        assert in_specs is not None and len(in_specs) == 5
        assert all(s.block_shape for s in in_specs)

    def test_flashmask_concat_specs_rebuilt(self, sites):
        # the flashmask helper returns [kind] + [se]*4 + [q, k, v]:
        # list-concat and list-repeat must both flatten
        site = sites["_flashmask_fwd_impl"]
        in_specs, _ = vm._site_specs(
            site, vm.CANONICAL["_flashmask_fwd_impl"])
        assert in_specs is not None and len(in_specs) == 8

    def test_transfer_derivable_after_rebuild(self, sites):
        site = sites["_flash_fwd_impl"]
        t = vm.derive_transfer(site, vm.CANONICAL["_flash_fwd_impl"])
        assert t is not None
        assert t["read"] > 0 and t["write"] > 0
        assert t["unresolved"] == 0


class TestFusionCandidates:
    def test_decode_chain_pairs_found(self, index):
        cands = vm.fusion_candidates(index)
        details = {c["detail"]: c for c in cands}
        # the old rms->swiglu advisory is RESOLVED by ISSUE 14 and the
        # rms->rope seam by ISSUE 20 (both pairs live inside the
        # mega-kernels now); what remains is the deliberate two-kernel
        # seam behind attention — aligned token tiling, justified in
        # the DECODE_CHAIN comment (VMEM budget) — and the norm->front
        # retile (8-row producer vs one-token consumer), the
        # registered <=4-launch follow-on seam
        assert "fuse:fused_rms_norm->swiglu" not in details
        assert "fuse:fused_rms_norm->fused_rope_append" not in details
        assert "fuse:fused_oproj_norm->fused_ffn" in details
        assert details["fuse:fused_oproj_norm->fused_ffn"]["class"] \
            == "aligned"
        assert details["fuse:fused_rms_norm->fused_qkv_rope_append"][
            "class"] == "retile"

    def test_candidates_carry_sites(self, index):
        for c in vm.fusion_candidates(index):
            assert c["site"].qualname in vm._CHAIN_SITE.values()
            assert c["producer"] in vm.DECODE_CHAIN
            assert c["consumer"] in vm.DECODE_CHAIN


class TestSharedDriftConstant:
    def test_perf_gate_imports_the_same_tolerance(self):
        # one constant, no drift between paddlelint and perf_gate
        import importlib.util
        import sys
        import types
        path = os.path.join(REPO, "tools", "perf_gate.py")
        spec = importlib.util.spec_from_file_location("_pg_test", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["_pg_test"] = mod
        try:
            spec.loader.exec_module(mod)
            assert mod.COST_DRIFT_RTOL == vm.COST_DRIFT_RTOL
        finally:
            sys.modules.pop("_pg_test", None)
