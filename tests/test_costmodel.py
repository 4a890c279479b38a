"""Analytical cost registry (observability/costmodel.py).

Three contracts:

1. Coverage — every kernel in ops/oracles.py has a registered cost
   function and evaluates to a sane CostEstimate at canonical shapes.
2. BlockSpec consistency — for the paged / ragged / flash families the
   registry's byte formulas EQUAL the transfer sizes the PR-8 kernel
   model derives from the committed grids/BlockSpecs
   (analysis/kernelmodel.py fetch-runs evaluation), so the model and the
   code cannot drift apart silently.
3. Committed pins — the serving rooflines in docs/SERVING_BENCH.json
   and the flagship MFU (docs/FLAGSHIP_data.json + BENCH_REPEATS) are
   reproduced by `decode_step_budget` / `train_mfu`: train and serve
   derive from one cost vocabulary.
"""

import ast
import json
import os

import numpy as np
import pytest

import paddle_tpu.analysis.kernelmodel as km
from paddle_tpu.observability import costmodel as cm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = os.path.join(REPO, "docs")
OPS = os.path.join(REPO, "paddle_tpu", "ops")

BF16 = 2
I32 = 4

#: canonical evaluation shapes per kernel (kwargs for cm.cost)
SHAPES = {
    "fused_rms_norm": dict(T=8, H=256),
    "fused_layer_norm": dict(T=8, H=256),
    "fused_bias_residual_layer_norm": dict(T=8, H=256),
    "fused_moe_dispatch_combine": dict(T=8, K=2, E=4, C=16),
    "fused_rope": dict(B=2, S=16, H=4, D=64, Hk=1),
    "fused_rope_append": dict(T=8, Hq=4, KV=1, D=64, page_size=16),
    "fused_append_rows": dict(T=8, KV=1, D=64, page_size=16, runs=3),
    "fused_chunk_pool": dict(P=6, KV=2, D=64, chunk=16),
    "swiglu": dict(T=8, H=256),
    "flash_sdpa": dict(B=2, H=3, Sq=256, Sk=512, D=64,
                       block_q=128, block_k=128),
    "flashmask_sdpa": dict(B=2, H=3, Sq=256, Sk=512, D=64,
                           block_q=128, block_k=128),
    "paged_decode_attention_v2": dict(B=2, H=4, KV=1, D=128, context=128,
                                      page_size=16),
    "mla_decode_attention": dict(B=2, nh=16, r=512, dr=64, context=256),
    "ragged_paged_attention": dict(T=8, H=4, KV=1, D=128, S=4,
                                   pages_per_seq=8, page_size=16),
    "gmm": dict(M=64, K=128, N=256, G=4),
    "int4_dequantize": dict(K=128, N=256),
    "weight_only_linear": dict(M=8, K=256, N=512),
    "fused_oproj_norm": dict(T=8, Ko=512, H=512),
    "fused_ffn": dict(T=8, H=512, I=1792),
    "fused_qkv_rope_append": dict(T=8, H=512, Hq=32, KV=8, D=128,
                                  page_size=32),
    "ssm_state_update": dict(live=3, P=16, N=16, H=8),
    "ssm_state_put": dict(P=16, N=16, H=8),
    "ssm1_state_update": dict(live=3, C=256),
    "ssm1_chunk_scan": dict(rows=64, C=256),
    "kda_state_update": dict(live=3, H=4, K=16, V=16),
    "mhc_pre": dict(T=16, n=4, C=64),
    "mhc_post": dict(T=16, n=4, C=64),
}


class TestRegistryCoverage:
    def test_all_oracle_kernels_have_costs(self):
        # registration side effects                          # noqa: F401
        from paddle_tpu.ops import (fused, pallas_flash, pallas_flashmask,
                                    pallas_gmm, pallas_kda,
                                    pallas_megadecode, pallas_megafront,
                                    pallas_mhc, pallas_mla, pallas_paged,
                                    pallas_ragged, pallas_ssm, quant)
        from paddle_tpu.ops.oracles import oracles
        names = set(oracles())
        missing = names - set(cm.costs())
        assert not missing, f"kernels without a cost model: {missing}"
        # the canonical shape table covers the same set
        assert set(SHAPES) == names | set(SHAPES)

    @pytest.mark.parametrize("name", sorted(SHAPES))
    def test_estimates_sane(self, name):
        est = cm.cost(name, **SHAPES[name])
        assert est.bytes_read > 0 and est.bytes_written > 0
        assert est.flops >= 0
        assert est.hbm_bytes == est.bytes_read + est.bytes_written
        assert est.arithmetic_intensity >= 0
        # bandwidth-bound time scales down with more bandwidth
        assert est.theoretical_us(819e9) >= est.theoretical_us(2765e9)

    def test_the_kda_scan_and_state_costs(self):
        """Plain functions beside the registry: the scan is XLA."""
        est = cm.kda_chunk_scan_cost(rows=128, sub=64, H=4, K=16, V=16)
        state = 4 * 16 * 16 * 4
        assert est.breakdown["state"] == 2 * state
        assert est.hbm_bytes == 2 * state + 128 * 4 * (3 * 16 + 16 + 1) * 4 \
            + 128 * 4 * 16 * 4
        assert est.flops == 2 * 4 * (6 * 64 * 64 * 16 + 2 * 64 ** 3 // 3
                                     + 6 * 64 * 16 * 16 + 4 * 64 * 64 * 16)
        assert cm.kda_state_bytes_per_seq_layer(
            heads=32, head_dim=128, conv_kernel=4) == 2_097_152 + 73_728

    @pytest.mark.parametrize("heads, head_dim, state, layout, stored", [
        (128, 64, 128, "heads_minor", 4_194_304),       # Nemotron-3-Super
        (128, 64, 128, "state_minor", 4_194_304),
        (32, 128, 256, "heads_minor", 16_777_216),      # Falcon-H1-34B: x 4
        (32, 128, 256, "state_minor", 4_194_304),
        (8, 8, 16, "heads_minor", 8 * 16 * 128 * 4)])
    def test_the_state_as_a_layout_stores_it(self, heads, head_dim, state,
                                             layout, stored):
        """A pool's minor dimension is stored in whole 128-lane rows."""
        assert cm.ssm_state_stored_bytes(
            heads=heads, head_dim=head_dim, state_size=state,
            layout=layout) == stored
        assert cm.ssm_state_bytes_per_seq_layer(
            heads=32, head_dim=128, state_size=256, conv_dim=5120,
            conv_kernel=4) == 4_194_304 + 30_720

    def test_the_state_update_costs_by_layout(self):
        """Falcon-H1-34B's update of 58 live slots: the state once in and
        once out either way; a group's B / C rows [2, 256] float32
        state-minor against [256, 32] in the serving type expanded."""
        shape = dict(live=58, P=128, N=256, H=32)
        hm = cm.cost("ssm_state_update", **shape)
        sm = cm.cost("ssm_state_update", layout="state_minor", G=2, **shape)
        assert hm.breakdown["state"] == sm.breakdown["state"] \
            == 2 * 58 * 4_194_304
        assert hm.flops == sm.flops == 5 * 58 * 128 * 256 * 32
        assert hm.hbm_bytes - sm.hbm_bytes == 58 * (
            2 * 256 * 32 * 2 - 2 * 2 * 256 * 4)

    def test_mamba1_at_the_published_shapes(self):
        """Phi-4-mini-flash: 5,120 channels x 16 columns, 327,680 B a
        (slot, layer) as stored — the channels along the lanes, nothing
        padded — and ONE full pool read by eight blocks."""
        assert cm.ssm_state_bytes_per_seq_layer(
            heads=1, head_dim=5120, state_size=16, conv_dim=5120,
            conv_kernel=4) == 327_680 + 30_720
        up = cm.cost("ssm1_state_update", live=32, C=5120)
        assert up.breakdown["state"] == 2 * 32 * 327_680
        assert up.flops == 7 * 32 * 81_920
        scan = cm.cost("ssm1_chunk_scan", rows=256, C=5120)
        assert scan.breakdown["state"] == 2 * 327_680
        assert scan.flops == 7 * 256 * 81_920
        # 32 sequences at ~6.4 k tokens: 25 pages each of 1,310,720 B
        assert cm.shared_pool_read_bytes(
            pages=800, page_bytes=1_310_720, readers=8) == 8_388_608_000

    def test_unknown_kernel_raises_with_known_list(self):
        with pytest.raises(KeyError, match="known"):
            cm.cost("no_such_kernel", T=1)

    def test_breakdown_sums_bounded_by_totals(self):
        for name, kw in SHAPES.items():
            est = cm.cost(name, **kw)
            if est.breakdown:
                assert sum(est.breakdown.values()) <= est.hbm_bytes, name


# ---------------------------------------------------------------------------
# BlockSpec consistency: registry bytes == kernel-model fetch accounting
# ---------------------------------------------------------------------------

def _sites():
    files = []
    for f in ("pallas_paged.py", "pallas_ragged.py", "pallas_flash.py"):
        files.append((f"paddle_tpu.ops.{f[:-3]}", os.path.join(OPS, f),
                      os.path.join("paddle_tpu", "ops", f)))
    idx = km.PackageIndex.from_files(files)
    return idx, km.collect_kernel_calls(idx)


@pytest.fixture(scope="module")
def sites():
    return _sites()


def _one(sites, qualname):
    hits = [s for s in sites if s.qualname == qualname]
    assert len(hits) == 1, (qualname, [s.qualname for s in sites])
    return hits[0]


class TestBlockSpecConsistency:
    def test_paged_bytes_match_block_specs(self, sites):
        # q and the output ride BlockSpecs; K/V stay in HBM behind the
        # kernel's page DMAs, so their bytes are the table's pages, each
        # once a (sequence, KV head), K and V
        _, ss = sites
        site = _one(ss, "paged_decode_attention_v2")
        B, KV, D, psz, pages = 2, 1, 128, 16, 8
        b = dict(B=B, KV=KV, rep=4, page_size=psz, D=D,
                 pages_per_group=2, total_pages=8)
        got = km.transfer_bytes(site, b, [BF16] * 3, [BF16])
        assert got is not None
        q = got["in"][0]
        est = cm.cost("paged_decode_attention_v2", B=B, H=4, KV=KV, D=D,
                      context=pages * psz, page_size=psz,
                      pages_per_seq=pages)
        assert q + got["out"][0] == est.breakdown["activations"]
        assert got["out"][0] == est.bytes_written
        assert est.breakdown["kv"] == 2 * B * KV * pages * psz * D * BF16
        assert est.bytes_read == q + est.breakdown["kv"]

    def test_paged_v2_any_specs_opt_out(self, sites):
        # K/V stay in HBM behind manual DMA (memory_space=ANY): the
        # evaluator must SKIP those specs
        _, ss = sites
        site = _one(ss, "paged_decode_attention_v2")
        b = dict(B=2, KV=1, rep=4, page_size=16, D=128,
                 pages_per_group=2, total_pages=8)
        got = km.transfer_bytes(site, b, [BF16] * 3, [BF16])
        assert got is not None
        assert None in got["in"]

    def test_ragged_bytes_match_block_specs(self, sites):
        # grid (KV / hb, tiles): the q / out tiles ride BlockSpecs, the pools
        # stay in HBM behind the kernel's page DMAs (specs opt out, as
        # paged v2); their bytes are the pages the kernel's own count
        # visits for the launch the cost states
        from paddle_tpu.ops import pallas_ragged as pr
        _, ss = sites
        site = _one(ss, "ragged_paged_attention")
        T, S, rep, psz, nj, D = 8, 4, 4, 16, 8, 128
        tq = pr.ragged_tile_tokens(T, rep, "bfloat16")
        assert all(pr.ragged_tile_tokens(t, r, dt)
                   == cm._ragged_tile_tokens(t, r, w)
                   for t in (8, 9, 40, 288, 2048) for r in (1, 4, 8, 16)
                   for dt, w in (("float32", 4), ("bfloat16", 2),
                                 ("int8", 1)))
        # ... and the restated tile block equal to the kernel's wherever
        # the cell's VMEM does not bind: the serving cells' launches
        # (A.X-K1's 64 heads over one row of 640 columns; one KV head of
        # 128 columns under 16, 4 and 1 query heads; a block of 8 heads)
        for t, kv, r, d, v in ((288, 1, 64, 640, 512), (288, 1, 16, 128, None),
                               (40, 1, 4, 128, None), (8, 1, 4, 128, None),
                               (288, 1, 1, 128, None), (288, 8, 4, 128, None)):
            tile = pr.ragged_tile_tokens(t, r, "bfloat16")
            hb = pr.ragged_head_block(kv, tile * r, d, 256, 2,
                                      latent=v is not None)
            assert cm._ragged_tile_block(kv, t, r, tile) \
                == pr.ragged_tile_block(hb, -(-t // tile), tile * r, d, 256,
                                        2, v)
        b = dict(KV=1, hb=1, tb=1, n_cells=-(-T // tq), rows=tq * rep,
                 psz=psz, D=D)
        got = km.transfer_bytes(site, b, [BF16] * 3, [BF16])
        assert got is not None and got["in"][1:] == [None, None]
        # the head block moves whole tiles of the same rows: a launch's
        # q and out bytes do not depend on it
        for hb in (1, 2, 4, 8):
            blocked = km.transfer_bytes(site, dict(b, KV=8, hb=hb),
                                        [BF16] * 3, [BF16])
            assert blocked["in"][0] == 8 * got["in"][0]
            assert blocked["out"] == [8 * got["out"][0]]
        est = cm.cost("ragged_paged_attention", T=T, H=4, KV=1, D=D,
                      S=S, pages_per_seq=nj, page_size=psz)
        q = got["in"][0]
        assert q + got["out"][0] == est.breakdown["activations"]
        assert got["out"][0] == est.bytes_written
        rows = np.arange(S, dtype=np.int32) * (T // S)
        visits = pr.ragged_pages_visited(
            rows, np.full(S, T // S), np.full(S, nj * psz), T=T, rep=rep,
            dtype="bfloat16", page_size=psz, pages_per_seq=nj)
        assert visits == S * nj
        assert est.breakdown["kv"] == 2 * visits * psz * D * BF16
        assert q + est.breakdown["kv"] == est.bytes_read
        # a sliding window bounds the walk: the count the kernel's work
        # list makes never passes the cost's, and meets it where the
        # window's span straddles a page more than its length needs
        win = cm.cost("ragged_paged_attention", T=T, H=4, KV=1, D=D,
                      S=S, pages_per_seq=nj, page_size=psz, window=20)
        assert win.bytes_written == est.bytes_written
        worst = 0
        for kvl in range(nj * psz - psz, nj * psz + 1):
            v = pr.ragged_pages_visited(
                rows, np.full(S, T // S), np.full(S, kvl), T=T, rep=rep,
                dtype="bfloat16", page_size=psz, pages_per_seq=nj,
                window=20)
            worst = max(worst, v)
            assert 2 * v * psz * D * BF16 <= win.breakdown["kv"]
        assert 2 * worst * psz * D * BF16 == win.breakdown["kv"] \
            < est.breakdown["kv"]
        assert win.flops < est.flops

    def test_flash_fwd_bytes_match_block_specs(self, sites):
        idx, ss = sites
        site = _one(ss, "_flash_fwd_impl")
        mi = idx.modules["paddle_tpu.ops.pallas_flash"]
        fi = mi.functions["_specs"]
        # the in_specs ride through the tuple-unpacked `_specs` helper;
        # rebuild them over its env
        env = km.Env(mi, fi)
        ret = next(n for n in ast.walk(fi.node)
                   if isinstance(n, ast.Return))
        spec_calls = ret.value.elts[0].elts
        specs = [km.build_block_spec(c, mi, fi, env) for c in spec_calls]
        assert len(specs) == 5                # seg_q, seg_kv, q, k, v

        B, H, Sq, Sk, D, bq, bk = 2, 3, 256, 512, 64, 128, 128
        nq, nk = Sq // bq, Sk // bk
        # the grid's third axis walks the visit table: without a causal
        # mask every pair, a query block's nk pairs in a row — so the
        # table's query block changes nq times a (batch, head) and its
        # key block at every pair
        grid = [B, H, nq * nk]
        binds = dict(bq=bq, bk=bk, D=D, qi_runs=nq, kj_runs=nq * nk)
        elems = [km.spec_transfer_elems(s, grid, 3, binds) for s in specs]
        assert None not in elems
        seg_q, seg_kv, q, k, v = elems
        read = (seg_q + seg_kv) * I32 + (q + k + v) * BF16

        # out specs: o uses the same tuple-unpacked qmap and lse the
        # helper's row spec (rebuild both under its env)
        o_spec, lse_spec = (km.build_block_spec(s.node, mi, fi, env)
                            for s in site.out_specs)
        o = km.spec_transfer_elems(o_spec, grid, 3, binds)
        lse = km.spec_transfer_elems(lse_spec, grid, 3, binds)
        assert o is not None and lse is not None
        written = o * BF16 + lse * 4

        est = cm.cost("flash_sdpa", B=B, H=H, Sq=Sq, Sk=Sk, D=D,
                      block_q=bq, block_k=bk)
        assert read == est.bytes_read
        assert written == est.bytes_written
        # component identities: q once, K/V once per q-block
        assert q * BF16 == B * H * Sq * D * BF16
        assert k * BF16 == B * H * nq * Sk * D * BF16
        assert o * BF16 == B * H * Sq * D * BF16
        # with the table's runs unbound the model falls back to one fetch
        # a grid step: an upper bound, never a guess below
        loose = km.spec_transfer_elems(specs[2], grid, 3,
                                       dict(bq=bq, bk=bk, D=D))
        assert loose == B * H * nq * nk * bq * D

    def test_grids_evaluate_for_all_three_sites(self, sites):
        _, ss = sites
        paged = _one(ss, "paged_decode_attention_v2")
        assert km.grid_values(paged, dict(B=2, KV=1)) == [2, 1]
        rag = _one(ss, "ragged_paged_attention")
        assert km.grid_values(
            rag, dict(KV=1, hb=1, n_cells=3)) == [1, 3]
        assert km.grid_values(
            rag, dict(KV=16, hb=8, n_cells=3)) == [2, 3]
        fwd = _one(ss, "_flash_fwd_impl")
        assert km.grid_values(
            fwd, dict(B=2, H=3, n_pairs=8)) == [2, 3, 8]


# ---------------------------------------------------------------------------
# committed pins: SERVING_BENCH rooflines + flagship MFU from one registry
# ---------------------------------------------------------------------------

def _bench():
    with open(os.path.join(DOCS, "SERVING_BENCH.json")) as f:
        return json.load(f)


#: row -> (family, kv kwargs) for the committed bench configs
ROW_KV = {
    "decode": ("llama", dict(kv_heads=1, head_dim=128)),
    "decode_b1": ("llama", dict(kv_heads=1, head_dim=128)),
    "decode_b16": ("llama", dict(kv_heads=1, head_dim=128)),
    "decode_int8": ("llama", dict(kv_heads=1, head_dim=128)),
    "decode_int4": ("llama", dict(kv_heads=1, head_dim=128)),
    "decode_bf16_ref": ("llama", dict(kv_heads=1, head_dim=128)),
    "moe_decode": ("moe", dict(kv_heads=4, head_dim=128)),
    "moe_decode_int8": ("moe", dict(kv_heads=4, head_dim=128)),
    "mla_decode": ("mla", dict(kv_latent_dim=512 + 64)),
    "mla_decode_int8": ("mla", dict(kv_latent_dim=512 + 64)),
}


class TestCommittedPins:
    @pytest.mark.parametrize("row", sorted(ROW_KV))
    def test_serving_rooflines_reproduced(self, row):
        r = _bench()[row]
        family, kv = ROW_KV[row]
        budget = cm.decode_step_budget(
            family, batch=r["batch"],
            context=r["prefill_len"] + r["new_tokens"] / 2,
            layers=8, weight_bytes=r["weight_bytes"], **kv)
        got = cm.roofline_tokens_per_s(budget, hbm_bw=819e9)
        assert got == pytest.approx(r["roofline_tokens_per_s"], rel=1e-4)
        # the committed fraction is measured/roofline under this budget
        frac = r["decode_tokens_per_s_per_chip"] / got
        assert frac == pytest.approx(r["roofline_fraction"], abs=2e-3)

    def test_headline_band_1p13_to_1p28(self):
        # the ROADMAP's "1.13-1.28x the naive HBM roofline" claim, now
        # derived from costmodel instead of the hand constant
        bench = _bench()
        fracs = []
        for row in ("decode", "decode_b1", "decode_b16", "decode_int8"):
            r = bench[row]
            family, kv = ROW_KV[row]
            budget = cm.decode_step_budget(
                family, batch=r["batch"],
                context=r["prefill_len"] + r["new_tokens"] / 2,
                layers=8, weight_bytes=r["weight_bytes"], **kv)
            fracs.append(r["decode_tokens_per_s_per_chip"]
                         / cm.roofline_tokens_per_s(budget, hbm_bw=819e9))
        assert 1.10 <= min(fracs) and max(fracs) <= 1.31, fracs

    def test_page_granular_budget_never_below_row_granular(self):
        naive = cm.decode_step_budget(
            "llama", batch=8, context=1000, layers=8,
            weight_bytes=7 * 10**8, kv_heads=1, head_dim=128)
        paged = cm.decode_step_budget(
            "llama", batch=8, context=1000, layers=8,
            weight_bytes=7 * 10**8, kv_heads=1, head_dim=128,
            page_size=16)
        assert paged["kv_bytes"] >= naive["kv_bytes"]
        assert paged["kv_bytes"] == 8 * 8 * 1008 * 2 * 128 * 2

    def test_flagship_mfu_reproduced(self):
        with open(os.path.join(DOCS, "FLAGSHIP_data.json")) as f:
            fl = json.load(f)
        with open(os.path.join(DOCS, "BENCH_REPEATS_r5.json")) as f:
            reps = json.load(f)
        tok_s = reps["mean"]
        # the committed trajectory: ~61.4k tokens/s/chip
        assert 58e3 <= tok_s <= 65e3
        n = fl["shard"]["params"]
        # 6N identity between FLAGSHIP's ledger and the registry
        assert 6 * n == fl["shard"]["flops_per_token_6N"]
        mfu = cm.train_mfu(tokens_per_s=tok_s, n_params=n)
        # FLAGSHIP reports 65.5% measured shard MFU
        assert 0.62 <= mfu <= 0.69, mfu

    def test_flops_per_sample_matches_budget(self):
        f = cm.flops_per_sample(n_params=10**8, tokens_per_sample=2048)
        assert f == 6 * 10**8 * 2048
        # attention term engages when the shape is known
        f2 = cm.flops_per_sample(n_params=10**8, tokens_per_sample=2048,
                                 layers=8, hidden=2048)
        assert f2 == (6 * 10**8 + 12 * 8 * 2048 * 2048) * 2048
