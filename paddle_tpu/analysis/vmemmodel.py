"""Static per-kernel VMEM / HBM memory model (ISSUE 13 tentpole).

Built on the :mod:`kernelmodel` grid x BlockSpec evaluator: for every
registered oracle kernel this module publishes CANONICAL decode-shaped
bindings (the llama/gpt/moe/mla family shapes the engine actually
launches) and derives, purely from the committed AST,

  - the per-core VMEM footprint of one launch: resolvable block bytes
    (doubled when the index_map references a grid dim — Pallas keeps a
    revolving double buffer for re-fetched operands) plus
    ``scratch_shapes`` accumulators.  Unresolvable parts are COUNTED,
    not guessed, so every footprint is an explicit lower bound;
  - the HBM transfer bytes of one launch (``fetch runs x block bytes``,
    the same accounting `observability/costmodel.py` states in closed
    form), which PF406 cross-checks against the registered
    ``CostEstimate`` within :data:`COST_DRIFT_RTOL`;
  - producer/consumer tiling signatures across the decode-layer kernel
    chain, which PF404 turns into the fusion-opportunity worklist for
    ROADMAP item 1 (mega-kernel decode).

The flash/flashmask in_specs ride through the tuple-unpacked ``_specs``
helpers, invisible to the flow-insensitive ``Env``; they are rebuilt
over the helper's scope — flashmask's with its ``order == 'qk'`` branch
recorded (the technique `tests/test_costmodel.py` committed for the
flash pin); flash's maps read the launch's scalar-prefetched visit table
(``qi[t]``), whose runs the canonical binding states (``qi_runs``).

Pure stdlib (`ast` only): the cost registry is loaded from
``observability/costmodel.py`` BY FILE PATH, so nothing here ever
imports jax.  Degrade to unknown, never guess.
"""

from __future__ import annotations

import ast
import importlib.util
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

from . import kernelmodel as km
from .callgraph import PackageIndex
from .kernelmodel import KernelCallSite

__all__ = [
    "VMEM_BYTES_PER_CORE", "COST_DRIFT_RTOL", "DTYPE_WIDTHS",
    "CANONICAL", "FAMILY_SHAPES", "DECODE_CHAIN",
    "load_costmodel", "canonical_sites", "site_bindings", "grid_ok",
    "site_footprint", "derive_transfer", "derive_cost_bytes",
    "fusion_candidates", "rebuild_helper_specs", "resolved_value",
]

#: Pallas VMEM budget per TensorCore (v4/v5 generations: ~16 MiB).
VMEM_BYTES_PER_CORE = 16 * 1024 * 1024

#: PF406 / perf_gate shared tolerance: vmemmodel-derived bytes and the
#: registered CostEstimate must agree within this relative error.  ONE
#: constant — tools/perf_gate.py imports it, so the two gates cannot
#: drift apart.
COST_DRIFT_RTOL = 0.05

DTYPE_WIDTHS: Dict[str, int] = {
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "float8_e4m3fn": 1, "float8_e5m2": 1, "int8": 1, "uint8": 1,
    "bool_": 1,
}

_COSTMODEL_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "observability", "costmodel.py")


def load_costmodel():
    """The cost registry, loaded by file path (pure python + math; going
    through the package would drag in jax). None when unavailable."""
    name = "_paddlelint_costmodel"
    if name in sys.modules:
        return sys.modules[name]
    try:
        spec = importlib.util.spec_from_file_location(
            name, _COSTMODEL_PATH)
        if spec is None or spec.loader is None:
            return None
        mod = importlib.util.module_from_spec(spec)
        # dataclasses resolves cls.__module__ through sys.modules at
        # class-creation time; register before exec
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        return mod
    except Exception:
        sys.modules.pop(name, None)
        return None


# ---------------------------------------------------------------------------
# family shapes + canonical per-site bindings
# ---------------------------------------------------------------------------
#
# The real model configs the engine serves (models/llama.py,
# models/gpt.py, models/moe_llm.py, models/deepseek.py).  PF405 sweeps
# every canonical site's grid divisibility under its applicable
# families, not just the canonical symbols.

FAMILY_SHAPES: Dict[str, Dict[str, int]] = {
    "llama": dict(hidden=4096, intermediate=14336, heads=32, kv_heads=8,
                  head_dim=128),
    "gpt": dict(hidden=4096, intermediate=16384, heads=32, kv_heads=32,
                head_dim=128),
    "moe": dict(hidden=4096, intermediate=14336, heads=32, kv_heads=8,
                head_dim=128, experts=8, top_k=2),
    "mla": dict(hidden=5120, heads=16, lora_rank=512, rope_dim=64),
}

# One entry per registered oracle kernel, keyed by the qualname of the
# function that owns its pallas_call.  Fields:
#   kernel       cost-registry name (ops/oracles.py name)
#   bindings     Name -> int for the site's block/grid symbols, decode-
#                shaped (T = decode batch rows; page_size 32; D 128)
#   in_widths /  dtype bytes per in/out spec, in source order (prefetch
#   out_widths   operands are excluded from in_specs, matching Pallas)
#   cost_kwargs  shapes handed to cost(kernel, ...) for PF406
#   mode         "exact": compare hbm read+write; "activations": the
#                site's resolvable specs cover only the activation side
#                (paged v2 keeps K/V behind memory_space=ANY manual
#                DMA), so compare against breakdown["activations"]
#   any_inputs   in-spec indices EXPECTED to evaluate to None (ANY)
#   rebuild      in_specs live behind the module's `_specs` helper;
#                rebuild them with the order='qk' branch recorded
#   token_tiled  the launch sweeps the token axis (PF404 chain signat.)
#   families     PF405 family sweep: family name -> binding overrides
CANONICAL: Dict[str, Dict[str, Any]] = {
    # -- ops/fused.py ------------------------------------------------------
    "_rms_forward": dict(
        kernel="fused_rms_norm",
        bindings=dict(T=8, bt=8, H=4096),
        in_widths=[2, 2], out_widths=[2],
        cost_kwargs=dict(T=8, H=4096),
        token_tiled=True,
        families={"llama": dict(H=4096), "gpt": dict(H=4096)},
    ),
    "fused_layer_norm": dict(
        kernel="fused_layer_norm",
        bindings=dict(T=8, bt=8, H=4096),
        in_widths=[2, 2, 2], out_widths=[2],
        cost_kwargs=dict(T=8, H=4096),
        token_tiled=True,
        families={"gpt": dict(H=4096)},
    ),
    "_brln_forward": dict(
        kernel="fused_bias_residual_layer_norm",
        bindings=dict(T=8, bt=8, H=4096),
        in_widths=[2, 2, 2, 2, 2], out_widths=[2],
        cost_kwargs=dict(T=8, H=4096),
        token_tiled=True,
        families={"gpt": dict(H=4096)},
    ),
    "_moe_dc_forward": dict(
        kernel="fused_moe_dispatch_combine",
        bindings=dict(T=8, bt=8, K=2, E=8, C=64),
        in_widths=[4, 4, 4], out_widths=[4, 4],
        cost_kwargs=dict(T=8, K=2, E=8, C=64),
        token_tiled=True,
        families={"moe": dict(E=8, K=2)},
    ),
    # fused_rope launches _rope_forward once for q and once for k; the
    # cost entry covers the PAIR, so the canonical binding folds both
    # head counts into one conceptual launch (H = Hq + Hk = 40) — the
    # cos/sin fetch then matches the single trig read the cost states.
    "_rope_forward": dict(
        kernel="fused_rope",
        bindings=dict(B=4, S=256, bs=256, H=40, D=128),
        in_widths=[2, 2, 2], out_widths=[2],
        cost_kwargs=dict(B=4, S=256, H=32, Hk=8, D=128),
        token_tiled=False,
        families={"llama": dict(H=40, D=128)},
    ),
    # the site is the append by cache-tile runs (the rope before it is
    # `_rope_forward`'s, above; the cost with rope=False leaves it out):
    # G = 8 runs of one row each over the resident [T, KV, D] roped-K
    # and V rows, one (KV, 1, tile, D) block a plane a run
    "_append_kv_runs": dict(
        kernel="fused_rope_append",
        bindings=dict(T=8, KV=8, D=128, tile=16, G=8),
        in_widths=[2, 2, 2, 2], out_widths=[2, 2],
        cost_kwargs=dict(T=8, Hq=32, KV=8, D=128, page_size=32, runs=8,
                         tile=16, rope=False),
        token_tiled=False,
        families={"llama": dict(KV=8, D=128),
                  "gpt": dict(KV=32, D=128)},
    ),
    # the same work list over ONE pool: the latent row's one head, whose
    # resident rows ride as float32, one (1, 1, tile, D) block a run
    "fused_append_rows": dict(
        kernel="fused_append_rows",
        bindings=dict(T=8, KV=1, D=640, tile=16, G=8),
        in_widths=[4, 2], out_widths=[2],
        cost_kwargs=dict(T=8, KV=1, D=640, page_size=32, runs=8, tile=16),
        token_tiled=False,
        families={"mla": dict(KV=1, D=640)},
    ),
    # EvaByte's pooling: 6 closed chunks of 16 rows, 32 KV heads
    "fused_chunk_pool": dict(
        kernel="fused_chunk_pool",
        bindings=dict(P=6, KV=32, D=128, chunk=16),
        in_widths=[2, 2, 4, 4], out_widths=[2, 2],
        cost_kwargs=dict(P=6, KV=32, D=128, chunk=16),
        token_tiled=False,
    ),
    "_swiglu_forward": dict(
        kernel="swiglu",
        bindings=dict(T=8, bt=8, H=14336),
        in_widths=[2, 2], out_widths=[2],
        cost_kwargs=dict(T=8, H=14336),
        token_tiled=True,
        families={"llama": dict(H=14336), "gpt": dict(H=16384)},
    ),
    # -- ops/pallas_flash.py / pallas_flashmask.py -------------------------
    "_flash_fwd_impl": dict(
        kernel="flash_sdpa",
        # the grid's third axis walks the launch's visit table (every
        # pair, without a causal mask): the query block changes once a
        # run of nk pairs, the key block at every pair
        bindings=dict(B=1, H=8, Sq=1024, Sk=1024, D=128,
                      bq=512, bk=512, nq=2, nk=2,
                      n_pairs=4, qi_runs=2, kj_runs=4),
        in_widths=[4, 4, 2, 2, 2], out_widths=[2, 4],
        cost_kwargs=dict(B=1, H=8, Sq=1024, Sk=1024, D=128),
        rebuild=True,
        token_tiled=False,
    ),
    # the startend row-index mask rows and the SMEM skip map are not in
    # the closed-form cost (which carries flash's seg term instead);
    # both are stats-sized against the K/V stream, so the site lands
    # inside COST_DRIFT_RTOL rather than exactly on the formula.
    "_flashmask_fwd_impl": dict(
        kernel="flashmask_sdpa",
        bindings=dict(B=1, H=8, Sq=1024, Sk=1024, D=128,
                      bq=512, bk=512, nq=2, nk=2),
        in_widths=[4, 4, 4, 4, 4, 2, 2, 2], out_widths=[2, 4],
        cost_kwargs=dict(B=1, H=8, Sq=1024, Sk=1024, D=128),
        rebuild=True,
        token_tiled=False,
    ),
    # -- ops/pallas_paged.py / pallas_ragged.py / pallas_mla.py ------------
    "paged_decode_attention_v2": dict(
        kernel="paged_decode_attention_v2",
        bindings=dict(B=8, KV=8, rep=4, D=128, G=2, psz=32),
        in_widths=[2, 2, 2], out_widths=[2],
        cost_kwargs=dict(B=8, H=32, KV=8, D=128, context=256,
                         page_size=32, pages_per_seq=8),
        mode="activations",
        any_inputs=(1, 2),
        token_tiled=False,
    ),
    # one cell of one tile (tb) of TQ = 8 tokens (rows = TQ * rep) for a
    # block of all 8 KV heads (hb: `ragged_head_block` takes every head
    # wherever the cell's VMEM allows, 16 at most; `ragged_tile_block`
    # gives a cell several tiles only where it serves ONE head); K/V
    # stay in HBM behind the kernel's own page DMAs, as in paged v2;
    # depth = the ring's slots (`_page_buffers`: 2 at serving page
    # sizes, one block in flight beside the one computed)
    "ragged_paged_attention": dict(
        kernel="ragged_paged_attention",
        bindings=dict(KV=8, hb=8, tb=1, n_cells=1, rows=32, D=128, psz=32,
                      depth=2),
        in_widths=[2, 2, 2], out_widths=[2],
        cost_kwargs=dict(T=8, H=32, KV=8, D=128, S=8, pages_per_seq=8,
                         page_size=32),
        mode="activations",
        any_inputs=(1, 2),
        token_tiled=True,
        # the serving cells' cells of VMEM, widest tile of each: llama
        # (Mistral: 4 query rows a KV head x 32 tokens); laguna's two
        # layer kinds (9 query rows x 16 tokens, window layers; a window
        # changes which pages a tile walks, not what it holds); the
        # looped decoder (Ouro: 16 KV heads of one query head, pages of
        # 64); chunk-summary attention (EvaByte: 32 KV heads of one
        # query head, blocks of 16 — pooled rows ride in the same pages);
        # latent attention (A.X-K1: 64 query heads over ONE row of 640
        # columns, 8 tiles of 2 tokens a cell; priced at this site's
        # specs, so with a [rows, D] output and a V ring its own launch,
        # `_latent_call`, goes without)
        families={"llama": dict(KV=8, hb=8, rows=128, D=128, psz=256),
                  "laguna": dict(KV=8, hb=8, rows=144, D=128, psz=256),
                  "looped": dict(KV=16, hb=16, rows=128, D=128, psz=64),
                  "chunk_summary": dict(KV=32, hb=16, rows=128, D=128,
                                        psz=256),
                  "latent": dict(KV=1, hb=1, tb=8, rows=128, D=640,
                                 psz=256)},
    ),
    "mla_decode_attention": dict(
        kernel="mla_decode_attention",
        bindings=dict(B=8, nh=16, r=512, dr=64, block_t=128, nj=4),
        in_widths=[2, 2, 2, 2], out_widths=[2],
        cost_kwargs=dict(B=8, nh=16, r=512, dr=64, context=512,
                         block_t=128),
        token_tiled=False,
        families={"mla": dict(nh=16, r=512, dr=64)},
    ),
    # -- ops/pallas_gmm.py / quant.py --------------------------------------
    # gmm: one m-block, one n-block (the cost's nn factor is then 1 and
    # the pl.when group-elision lower bound coincides with grid x block)
    "_gmm_fwd_impl": dict(
        kernel="gmm",
        bindings=dict(nm=1, nn=1, G=8, bm=128, bn=128, K=4096, Mp=128),
        in_widths=[2, 2], out_widths=[2],
        cost_kwargs=dict(M=128, K=4096, N=128, G=8,
                         block_m=128, block_n=128),
        token_tiled=False,
        families={"moe": dict(G=8, K=4096)},
    ),
    # int4_dequantize: tensor-parallel shard shapes; K=1024 keeps the
    # whole-column f32 out block (K x bn x 4B, doubled) inside VMEM
    "int4_dequantize": dict(
        kernel="int4_dequantize",
        bindings=dict(K2=512, Np=1024, bn=1024),
        in_widths=[1, 4], out_widths=[4],
        cost_kwargs=dict(K=1024, N=1024),
        token_tiled=False,
        families={"llama": dict(K2=512, Np=1024)},
    ),
    # weight_only_linear (int8 path): N=1792 is the 8-way tensor-
    # parallel shard of llama's 14336 — the whole [K, N] int8 slab is
    # VMEM-resident (index_map refs no grid dim: fetched once)
    "_wol_int8_fwd_impl": dict(
        kernel="weight_only_linear",
        bindings=dict(M=128, bm=128, K=4096, N=1792),
        in_widths=[2, 1, 4], out_widths=[2],
        cost_kwargs=dict(M=128, K=4096, N=1792,
                         algo="weight_only_int8"),
        token_tiled=False,
        families={"llama": dict(K=4096, N=1792)},
    ),
    # -- ops/pallas_megadecode.py (ISSUE 14 mega-kernel back half) ---------
    # 8-way tensor-parallel shard shapes, like _wol_int8_fwd_impl: H=512
    # is llama's 4096/8, I=1792 its 14336/8 — the whole weight slab is
    # VMEM-resident (constant index_maps: fetched once per launch).
    "_oproj_norm_forward": dict(
        kernel="fused_oproj_norm",
        bindings=dict(T=8, bt=8, Ko=512, H=512),
        in_widths=[2, 2, 2, 4, 2, 2, 2], out_widths=[2, 2],
        cost_kwargs=dict(T=8, Ko=512, H=512),
        token_tiled=True,
        families={"llama": dict(Ko=512, H=512)},
    ),
    "_oproj_norm_int4": dict(
        kernel="fused_oproj_norm",
        bindings=dict(T=8, bt=8, Ko2=256, H=512),
        in_widths=[2, 2, 2, 1, 4, 2, 2, 2], out_widths=[2, 2],
        cost_kwargs=dict(T=8, Ko=512, H=512, algo="weight_only_int4"),
        token_tiled=True,
        families={"llama": dict(Ko2=256, H=512)},
    ),
    "_ffn_forward": dict(
        kernel="fused_ffn",
        bindings=dict(T=8, bt=8, H=512, I=1792, Ku=512),
        in_widths=[2, 2, 2, 4, 2, 4, 2, 4, 2, 2], out_widths=[2],
        cost_kwargs=dict(T=8, H=512, I=1792),
        token_tiled=True,
        families={"llama": dict(H=512, I=1792)},
    ),
    "_ffn_int4": dict(
        kernel="fused_ffn",
        bindings=dict(T=8, bt=8, H=512, H2=256, I=1792, I2=896),
        in_widths=[2, 2, 2, 1, 4, 1, 4, 1, 4, 2, 2], out_widths=[2],
        cost_kwargs=dict(T=8, H=512, I=1792, algo="weight_only_int4"),
        token_tiled=True,
        families={"llama": dict(H2=256, I2=896)},
    ),
    # -- ops/pallas_megafront.py (ISSUE 20 mega-kernel front half) ---------
    # 8-way shard hidden (H=512) against the FULL qkv out width
    # N=(Hq+2KV)*D — out channels don't shard with the contraction; the
    # concatenated slab is VMEM-resident (constant index_map, one fetch)
    # while the token row, trig rows and page blocks sweep with t.
    "_qkv_rope_append_fwd": dict(
        kernel="fused_qkv_rope_append",
        bindings=dict(T=8, H=512, N=6144, heads=32, KV=8, D=128,
                      psz=32, d2=64),
        in_widths=[2, 2, 4, 2, 2, 2, 2, 2], out_widths=[2, 2, 2],
        cost_kwargs=dict(T=8, H=512, Hq=32, KV=8, D=128, page_size=32),
        token_tiled=True,
        families={"llama": dict(H=512, N=6144, heads=32, KV=8, D=128),
                  "gpt": dict(KV=32, N=12288)},
    ),
    "_qkv_rope_append_int4": dict(
        kernel="fused_qkv_rope_append",
        bindings=dict(T=8, H2=256, N=6144, heads=32, KV=8, D=128,
                      psz=32),
        in_widths=[2, 2, 1, 4, 2, 2, 2, 2], out_widths=[2, 2, 2],
        cost_kwargs=dict(T=8, H=512, Hq=32, KV=8, D=128, page_size=32,
                         algo="weight_only_int4"),
        token_tiled=True,
        families={"llama": dict(H2=256, N=6144)},
    ),
    # MLA front: q [H, nh*(dn+dr)] and kv_a [H, r+dr] concatenate into
    # one slab; the pool row is [latent | rope-key] (Dc = r + dr)
    "_mla_qkv_rope_append_fwd": dict(
        kernel="fused_qkv_rope_append",
        bindings=dict(T=8, H=640, N=3648, r=512, dd2=32, heads=16,
                      dh=192, psz=32, Dc=576),
        in_widths=[2, 2, 4, 2, 2, 2, 2], out_widths=[2, 2],
        cost_kwargs=dict(T=8, H=640, Hq=16, page_size=32,
                         nope_dim=128, rope_dim=64, lora_rank=512),
        token_tiled=True,
        families={"mla": dict(H=640, N=3648, r=512, heads=16)},
    ),
    # -- ops/pallas_ssm.py (a slot-indexed state pool, heads minor) --------
    # 8 live slots of [P = 64, N, H] float32 in J = 4 blocks of PB rows:
    # each slot's state once in and once out, its row's operands
    "ssm_state_update": dict(
        kernel="ssm_state_update",
        bindings=dict(B=8, J=4, PB=16, N=128, H=128),
        in_widths=[4, 4, 2, 2, 4], out_widths=[4, 4],
        cost_kwargs=dict(live=8, P=64, N=128, H=128),
        token_tiled=False,
    ),
    "ssm_state_put": dict(
        kernel="ssm_state_put",
        bindings=dict(J=4, PB=16, N=128, H=128),
        in_widths=[4, 4], out_widths=[4],
        cost_kwargs=dict(P=64, N=128, H=128),
        token_tiled=False,
    ),
    # Mamba-1 (a decay a (channel, column)): 8 live slots of [1, N = 16, C]
    # float32, one slot's whole state a grid step; and a chunk's 256 rows
    # in row blocks of RB over channel blocks of CB lanes
    "ssm1_state_update": dict(
        kernel="ssm1_state_update",
        bindings=dict(B=8, N=16, C=5120),
        in_widths=[4, 4, 4, 4, 4, 4], out_widths=[4, 4],
        cost_kwargs=dict(live=8, C=5120, N=16),
        token_tiled=False,
    ),
    "ssm1_chunk_scan": dict(
        kernel="ssm1_chunk_scan",
        bindings=dict(Lp=256, RB=64, CB=512, N=16, C=5120),
        in_widths=[4, 4, 4, 4, 4, 4], out_widths=[4, 4],
        cost_kwargs=dict(rows=256, C=5120, N=16),
        token_tiled=False,
    ),
    # -- ops/pallas_kda.py (the same pool, heads major: a [K, V] tile) -----
    # 8 live slots of [H = 32, K, V] float32 in J = 4 blocks of HB heads:
    # each slot's state once in and once out, its row's q, k, g, v, beta
    "kda_state_update": dict(
        kernel="kda_state_update",
        bindings=dict(B=8, J=4, HB=8, K=128, V=128),
        in_widths=[4, 4, 4, 4, 4, 4], out_widths=[4, 4],
        cost_kwargs=dict(live=8, H=32, K=128, V=128),
        token_tiled=False,
    ),
    # -- ops/pallas_mhc.py (a residual of n = 4 streams, [T, n C]) ---------
    # T = 384 flat rows of [4 x 3584] bfloat16. `mhc_pre` holds R = 128
    # rows a step: the block 3.5 MiB (7 MiB double-buffered), the turned
    # weights [32, 14336] 0.9 MiB, the outputs [128, 3584] bf16 + [128,
    # 128] f32 and the 64 KiB register square that is turned: 11.6 MiB of
    # the 16 MiB; 256 rows would not fit, and fewer than 128 would turn a
    # part-filled square. `mhc_post` holds R = 64 rows in AND out (1.75
    # MiB each, double-buffered 7 MiB) beside y and the coefficients
    "mhc_pre": dict(
        kernel="mhc_pre",
        bindings=dict(T=384, R=128, nC=14336, C=3584, rows=32, S=128,
                      MHC_COEF_LANES=128),
        in_widths=[2, 2, 4], out_widths=[2, 4],
        cost_kwargs=dict(T=384, n=4, C=3584),
        token_tiled=True,
    ),
    "mhc_post": dict(
        kernel="mhc_post",
        bindings=dict(T=384, R=64, nC=14336, C=3584, MHC_COEF_LANES=128),
        in_widths=[2, 2, 4], out_widths=[2],
        cost_kwargs=dict(T=384, n=4, C=3584),
        token_tiled=True,
    ),
}

#: The decode-layer kernel chain in launch order (PF404 walks adjacent
#: pairs).  ISSUE 14 collapsed the back half into the two megadecode
#: launches — o-proj + residual + norm, then the whole FFN — and ISSUE
#: 20 consumed the front-half seam: the qkv projection matmuls, rope,
#: and the paged K/V scatter now live in one fused_qkv_rope_append
#: launch, so the old fused_rms_norm -> fused_rope_append advisory
#: (whose only obstacle was the 8-rows-vs-1 retile) is RESOLVED — the
#: fused kernel emits q at the attention consumer's one-token
#: granularity, and fused_rope_append stays registered for the
#: standalone op / fallback path.  The advisories that remain standing
#: are justified seams, not oversights:
#:   - fused_rms_norm -> fused_qkv_rope_append 'retile': the norm
#:     still runs a bt=8 row block while the fused front sweeps one
#:     token per step; folding the norm in is the registered seam for
#:     the ROADMAP <=4-launch follow-on (a [T, H] x [H, (Hq+2KV)D]
#:     slab plus the norm row block co-resides at the family shapes —
#:     the obstacle is purely the 8-vs-1 retile);
#:   - fused_oproj_norm -> fused_ffn 'aligned': the deliberate two-
#:     kernel cut — the o-proj slab plus all three FFN slabs exceed the
#:     16 MiB budget even 8-way sharded, so only the [T, H] residual +
#:     normed pair crosses HBM between them (down from four
#:     intermediates in the unfused chain).
DECODE_CHAIN: List[str] = [
    "fused_rms_norm", "fused_qkv_rope_append", "ragged_paged_attention",
    "fused_oproj_norm", "fused_ffn",
]

_CHAIN_SITE: Dict[str, str] = {
    "fused_rms_norm": "_rms_forward",
    "fused_rope_append": "_append_kv_runs",
    "fused_qkv_rope_append": "_qkv_rope_append_fwd",
    "ragged_paged_attention": "ragged_paged_attention",
    "fused_oproj_norm": "_oproj_norm_forward",
    "fused_ffn": "_ffn_forward",
}


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def site_bindings(entry: Dict[str, Any],
                  family: Optional[str] = None) -> Dict[str, int]:
    b = dict(entry["bindings"])
    if family is not None:
        b.update(entry.get("families", {}).get(family, {}))
    return b


def resolved_value(expr: ast.AST, env: km.Env,
                   bindings: Dict[str, int]) -> Optional[int]:
    """Evaluate `expr` with the site's own assignments taking precedence
    over the canonical bindings: a literal ``bn = 64`` in the file beats
    the published shape (that is the defect PF403/PF405 exist to catch);
    an unresolvable chain (``bn = next(...)``) falls back to bindings."""
    v = km.eval_int_expr(env.resolve(expr), bindings)
    if v is None:
        v = km.eval_int_expr(expr, bindings)
    return v


def canonical_sites(index: PackageIndex) -> Dict[str, KernelCallSite]:
    """qualname -> call site for every CANONICAL kernel present in the
    analyzed set (each owning function holds exactly one pallas_call)."""
    out: Dict[str, KernelCallSite] = {}
    for site in km.collect_kernel_calls(index):
        qn = site.qualname
        if qn in CANONICAL and qn not in out:
            out[qn] = site
    return out


def grid_ok(site: KernelCallSite, bindings: Dict[str, int]) -> bool:
    """The grid evaluates and every ``a // b`` component divides exactly
    (a mis-gridded launch makes byte accounting meaningless — PF405 owns
    that finding; PF401/PF406 skip)."""
    if km.grid_values(site, bindings) is None:
        return False
    for e in site.grid_elts or []:
        if isinstance(e, ast.BinOp) and isinstance(e.op, ast.FloorDiv):
            a = km.eval_int_expr(e.left, bindings)
            d = km.eval_int_expr(e.right, bindings)
            if a is None or not d or a % d:
                return False
    return True


def _flatten_spec_list(expr: Optional[ast.AST],
                       env: km.Env) -> Optional[List[ast.AST]]:
    """Evaluate a ``[a] + [b] * 4 + [...]`` spec-list expression to its
    element ASTs (the flashmask `_specs` return shape)."""
    expr = env.resolve(expr)
    if isinstance(expr, (ast.List, ast.Tuple)):
        return list(expr.elts)
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
        left = _flatten_spec_list(expr.left, env)
        right = _flatten_spec_list(expr.right, env)
        if left is None or right is None:
            return None
        return left + right
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Mult):
        base = _flatten_spec_list(expr.left, env)
        n = km.eval_int_expr(expr.right, {})
        if base is None or n is None or n < 0:
            return None
        return base * n
    return None


def rebuild_helper_specs(site: KernelCallSite, helper: str = "_specs"
                         ) -> Tuple[Optional[List[km.BlockSpecModel]],
                                    Optional[List[km.BlockSpecModel]]]:
    """Rebuild (in_specs, out_specs) for sites whose specs ride through
    the module's tuple-unpacked `_specs` helper.  Records the
    ``order == 'qk'`` branch body over the helper's env (Env is
    flow-insensitive; without this the else-branch maps would win) and
    flattens the returned list expression."""
    mi = site.mi
    fi = mi.functions.get(helper)
    if fi is None:
        return None, None
    env = km.Env(mi, fi)
    branch = next((n for n in ast.walk(fi.node) if isinstance(n, ast.If)),
                  None)
    if branch is not None:
        for stmt in branch.body:
            env._record(stmt)
    ret = next((n for n in ast.walk(fi.node)
                if isinstance(n, ast.Return)), None)
    if ret is None or not isinstance(ret.value, ast.Tuple) \
            or not ret.value.elts:
        return None, None
    elts = _flatten_spec_list(ret.value.elts[0], env)
    if elts is None:
        return None, None
    in_specs = []
    for e in elts:
        spec = km.build_block_spec(e, mi, fi, env)
        if spec is None:
            return None, None
        in_specs.append(spec)
    out_specs = None
    if site.out_specs is not None:
        out_specs = [km.build_block_spec(s.node, mi, fi, env) or s
                     for s in site.out_specs]
    return in_specs, out_specs


def _site_specs(site: KernelCallSite, entry: Dict[str, Any]
                ) -> Tuple[Optional[List[km.BlockSpecModel]],
                           Optional[List[km.BlockSpecModel]]]:
    if entry.get("rebuild"):
        return rebuild_helper_specs(site)
    return site.in_specs, site.out_specs


# ---------------------------------------------------------------------------
# VMEM footprint
# ---------------------------------------------------------------------------

def _scratch_bytes(site: KernelCallSite,
                   bindings: Dict[str, int]) -> Tuple[int, int]:
    """(bytes, unresolved entries) for the VMEM/SMEM scratch shapes.
    Semaphores and ANY-space scratch carry no VMEM block."""
    total = 0
    unresolved = 0
    for expr in site.scratch or []:
        if not (isinstance(expr, ast.Call)
                and km._last_name(expr.func) in ("VMEM", "SMEM")
                and expr.args):
            continue
        width = DTYPE_WIDTHS.get(km.scratch_dtype_name(expr) or "")
        shape = km._seq_elts(expr.args[0])
        if width is None or shape is None:
            unresolved += 1
            continue
        elems = 1
        for e in shape:
            v = km.eval_int_expr(e, bindings)
            if v is None:
                elems = None
                break
            elems *= v
        if elems is None:
            unresolved += 1
        else:
            total += elems * width
    return total, unresolved


def site_footprint(site: KernelCallSite, entry: Dict[str, Any],
                   bindings: Optional[Dict[str, int]] = None
                   ) -> Dict[str, int]:
    """Per-core VMEM bytes of one launch under the canonical bindings:
    each resolvable non-ANY block (x2 when its index_map references a
    grid dim — the revolving fetch buffer), SMEM blocks excluded, plus
    scratch accumulators.  ``unresolved`` counts the parts that did not
    evaluate — the footprint is a documented lower bound."""
    b = dict(bindings) if bindings is not None else site_bindings(entry)
    in_specs, out_specs = _site_specs(site, entry)
    total = 0
    unresolved = 0
    grid_len = site.grid_len or 0
    for specs, widths in ((in_specs, entry.get("in_widths", [])),
                          (out_specs, entry.get("out_widths", []))):
        for i, spec in enumerate(specs or []):
            if spec.memory_space in ("ANY", "SMEM"):
                continue
            width = widths[i] if i < len(widths) else None
            if width is None or spec.block_shape is None:
                unresolved += 1
                continue
            elems = 1
            for e in spec.block_shape:
                v = km.eval_int_expr(e, b)
                if v is None:
                    elems = None
                    break
                elems *= v
            if elems is None:
                unresolved += 1
                continue
            mult = 1
            if spec.index_map is not None and \
                    km.index_map_grid_refs(spec.index_map, grid_len):
                mult = 2
            total += elems * width * mult
    sb, su = _scratch_bytes(site, b)
    return {"bytes": total + sb, "unresolved": unresolved + su}


# ---------------------------------------------------------------------------
# HBM transfer derivation + cost cross-check (PF406)
# ---------------------------------------------------------------------------

def derive_transfer(site: KernelCallSite, entry: Dict[str, Any],
                    bindings: Optional[Dict[str, int]] = None
                    ) -> Optional[Dict[str, int]]:
    """{'read': bytes, 'write': bytes, 'unresolved': n} for one launch
    under the canonical bindings, or None when the grid itself does not
    evaluate.  In-spec indices listed in ``any_inputs`` are expected to
    opt out (manual-DMA operands) and are not counted unresolved."""
    b = dict(bindings) if bindings is not None else site_bindings(entry)
    grid = km.grid_values(site, b)
    if grid is None or site.grid_len is None:
        return None
    in_specs, out_specs = _site_specs(site, entry)
    skip_in = set(entry.get("any_inputs", ()))
    res = {"read": 0, "write": 0, "unresolved": 0}
    for specs, widths, key, skip in (
            (in_specs, entry.get("in_widths", []), "read", skip_in),
            (out_specs, entry.get("out_widths", []), "write", set())):
        for i, spec in enumerate(specs or []):
            width = widths[i] if i < len(widths) else None
            elems = km.spec_transfer_elems(spec, grid, site.grid_len, b)
            if elems is None or width is None:
                if i not in skip:
                    res["unresolved"] += 1
                continue
            res[key] += elems * width
    return res


def derive_cost_bytes(index: PackageIndex,
                      cost_module=None) -> List[Dict[str, Any]]:
    """One record per CANONICAL kernel present in `index`: the
    AST-derived HBM bytes vs the registered CostEstimate.  status is
    'ok' / 'drift', or 'skipped:<why>' when the comparison is not
    meaningful (absent site, failed grid divisibility — PF405 owns that
    — or an unresolvable spec)."""
    cm = cost_module if cost_module is not None else load_costmodel()
    sites = canonical_sites(index)
    records: List[Dict[str, Any]] = []
    for qn, entry in CANONICAL.items():
        site = sites.get(qn)
        if site is None:
            continue
        rec: Dict[str, Any] = {
            "kernel": entry["kernel"], "qualname": qn,
            "path": site.mi.rel, "line": site.line,
        }
        b = site_bindings(entry)
        if not grid_ok(site, b):
            rec["status"] = "skipped:grid"
            records.append(rec)
            continue
        t = derive_transfer(site, entry, b)
        if t is None or t["unresolved"]:
            rec["status"] = "skipped:unresolved"
            records.append(rec)
            continue
        derived = t["read"] + t["write"]
        rec["derived"] = derived
        if cm is None:
            rec["status"] = "skipped:costmodel"
            records.append(rec)
            continue
        try:
            est = cm.cost(entry["kernel"], **entry["cost_kwargs"])
        except Exception:
            rec["status"] = "skipped:cost-error"
            records.append(rec)
            continue
        if entry.get("mode") == "activations":
            expected = (est.breakdown or {}).get("activations")
        else:
            expected = est.bytes_read + est.bytes_written
        if not expected:
            rec["status"] = "skipped:cost-empty"
            records.append(rec)
            continue
        rel = abs(derived - expected) / expected
        rec.update(expected=expected, rel_err=rel,
                   status="ok" if rel <= COST_DRIFT_RTOL else "drift")
        records.append(rec)
    return records


# ---------------------------------------------------------------------------
# fusion opportunities (PF404)
# ---------------------------------------------------------------------------

def _leading_sweep(spec: Optional[km.BlockSpecModel],
                   grid_len: Optional[int]) -> Optional[ast.AST]:
    """The block's leading extent when the spec is a leading-axis sweep:
    index_map returns ``(g, 0, ..., 0)`` with g referencing a grid dim.
    None otherwise."""
    if spec is None or spec.block_shape is None or spec.index_map is None:
        return None
    rets = spec.index_map.returns
    if not rets:
        return None
    comps = rets[0]
    if len(comps) != len(spec.block_shape):
        return None
    for c in comps[1:]:
        if km._int_const(c) != 0:
            return None
    if not km.index_map_grid_refs(spec.index_map, grid_len or 0):
        return None
    return spec.block_shape[0]


def fusion_candidates(index: PackageIndex) -> List[Dict[str, Any]]:
    """Adjacent DECODE_CHAIN pairs whose producer out-tiling and
    consumer in-tiling are both token-axis sweeps — each one is an HBM
    round-trip a fused kernel would elide.  class 'aligned' (identical
    leading block extents: fusable as-is) or 'retile' (both token-swept
    but at different granularity)."""
    sites = canonical_sites(index)
    out: List[Dict[str, Any]] = []
    for prod, cons in zip(DECODE_CHAIN, DECODE_CHAIN[1:]):
        pq, cq = _CHAIN_SITE[prod], _CHAIN_SITE[cons]
        pe, ce = CANONICAL[pq], CANONICAL[cq]
        ps, cs = sites.get(pq), sites.get(cq)
        if ps is None or cs is None:
            continue
        if not (pe.get("token_tiled") and ce.get("token_tiled")):
            continue
        p_spec = (ps.out_specs or [None])[0]
        c_spec = (cs.in_specs or [None])[0]
        p_lead = _leading_sweep(p_spec, ps.grid_len)
        c_lead = _leading_sweep(c_spec, cs.grid_len)
        if p_lead is None or c_lead is None:
            continue
        pv = km.eval_int_expr(p_lead, site_bindings(pe))
        cv = km.eval_int_expr(c_lead, site_bindings(ce))
        klass = "aligned" if (pv is not None and pv == cv) else "retile"
        out.append({
            "producer": prod, "consumer": cons, "class": klass,
            "site": ps, "detail": f"fuse:{prod}->{cons}",
        })
    return out
