"""Every family's step program, pinned to the text it lowers to.

ONE place for what guards "another family's program did not move": the
sha256 of `eng._programs[name].lower(...).as_text()` (no debug info) for
fourteen toy families times the two row counts the step body is compiled
at (`unified`: the decode rows and a prefill chunk; `unified_nochunk`:
the decode rows alone), at toy widths, on the CPU under the suite's
matmul precision. The toy models and engines are the family files' own
and are imported from there; nothing here steps an engine.

A pin moves when the TEXT moves, which is more often than the program:
a PR that changes a kernel every step launches re-records every pin
(PR 42, 45, 55: the ragged kernel; PR 48: the stored weight layout;
PR 53: the per-layer kernels through `engine._once`; PR 57: Nemotron's
alone, the Mamba-2 chunk scan; PR 59: Ling's `unified` alone, the KDA
chunk scan — its `unified_nochunk` holds no scan and held). A PR whose claim is that the programs
did NOT change (PR 58: six step bodies became three) re-records none,
or shows on the chip why a text moved and that the compiled program did
not (ISSUE 58 says how). The nine `unified` hashes of llama, moe, mla,
gpt, laguna, eva, looped, nemotron and ling are the ones PR 55 / PR 57
/ PR 59 recorded; fifteen were recorded at PR 58's parent (404ac2e),
before `engine.py` was touched; sdar's two came with PR 60, which moved
none of the other twenty-four, and were re-recorded by PR 61 (a block's
commit rides in the next block's first launch: a region of rows, its
sequences, an append of its own), which moved none of them either;
lfm2's two came with PR 64 (a mixer whose memory is a tail only, a
snapshot plane a block), which moved none of the twenty-six.
"""

import functools
import hashlib

import jax
import jax.numpy as jnp
import pytest

import test_bailing_hybrid_serving as ling
import test_evabyte_serving as eva
import test_falcon_h1_serving as falcon_h1
import test_lfm2_serving as lfm2
import test_nemotron_h_serving as nemotron
import test_ouro_serving as looped
import test_phi4flash_serving as phi4flash
import test_sdar_serving as sdar
import test_xing_serving as xing
from test_engine_programs import _lowered_toy

#: family -> its toy engine at the pin's sizes
ENGINES = {
    **{f: (lambda f=f: _lowered_toy(f)[1])
       for f in ("llama", "moe", "mla", "gpt", "laguna")},
    "eva": eva._pin_engine,
    "looped": looped._pin_engine,
    "nemotron": lambda: nemotron._engine(nemotron.seeded()[0]),
    "ling": lambda: ling._engine(ling.seeded()[0]),
    "xing": lambda: xing._engine(xing.seeded(experts_held=(4, 4))[0]),
    "falcon_h1": lambda: falcon_h1._engine(falcon_h1.seeded()[0]),
    "phi4flash": lambda: phi4flash._engine(phi4flash.seeded()[0]),
    "sdar": lambda: sdar._engine(sdar.seeded()[0]),
    "lfm2": lambda: lfm2._engine(lfm2.seeded()[0]),
}

PINS = {
    ("llama", "unified"):
        "d04b742de247dc594c6e05a8b0b29146a4a87aee2367c7ae2ea1be6387b4b117",
    ("moe", "unified"):
        "d7641834ce331be935b3e234fdded13754d4371a03efc7bb846282fb9eb40676",
    ("mla", "unified"):
        "4789c63ca885428be5bb8927624224428bc6112b8a2aa92597e926d64c82fe9b",
    ("gpt", "unified"):
        "cdbd25f7193cf1f43bb83d09919ba8b6d6a441d36229e25f1530f9f251dd2227",
    ("laguna", "unified"):
        "7c93ed69d80782546a97a396a03901c63abcbab0d3ccafcaef38b76340424431",
    ("eva", "unified"):
        "375c700ab6799f1dda5c6653d05e9cb38a6547370ccfa713b8144e1e41899947",
    ("looped", "unified"):
        "c78ea15ea6da800562dbe0f38d5247585e8bc0d212966c83174475fcc8ef8f42",
    ("nemotron", "unified"):
        "8189d8f0d7798726eac8d18b718860c3302d51c03f858aa993d1c236f4b12384",
    # re-recorded by PR 59 (the KDA chunk scan's form)
    ("ling", "unified"):
        "8face0118886ee17356ab663cecb146319e007bce153f63983f17f1e3bf2decb",
    # recorded at PR 58's parent (404ac2e), the engine untouched
    ("xing", "unified"):
        "65e1b60f81b73612ceceebcd7e535dd5c13df24a07917040643f90456cf252b8",
    ("falcon_h1", "unified"):
        "4fb593c95e43281d056eebc23300a9a522b7c632dc73ebea3351cb39c9784e0f",
    ("phi4flash", "unified"):
        "3ae4fc7188e622265e5273964f94a67a37050d22ac3068600f5da493d3310e48",
    ("llama", "unified_nochunk"):
        "d2f6454edab36b3b27766ef733c2fc04ecc7515c852225b39bc6544d1f8a69ff",
    ("moe", "unified_nochunk"):
        "ea2b03020a75e96ece680d296351e32480e533da85757c93d761cd21e3ceeaab",
    ("mla", "unified_nochunk"):
        "c6ab7866cf55963610d82449bcc7786b14121e278b094dc11574172992501e5b",
    ("gpt", "unified_nochunk"):
        "f9f2a44b0d33939bfa93c2e130a1c44dd513d8b6b6c3a1fd185f041e0fdd18f2",
    ("laguna", "unified_nochunk"):
        "21e1a3977f190282d5fa90e5cbaeb2041d36d1208968f305e9d9c0fbe69ed2df",
    ("eva", "unified_nochunk"):
        "f82521a1f0cb9211be1857d08c3a93467809878966f0b748fc0557337311c680",
    ("looped", "unified_nochunk"):
        "e91d8482b746b2bb229f55f2b1c3168dbb48c219c13bdc559f5fed5edbe9396a",
    ("nemotron", "unified_nochunk"):
        "d8d0cdd1eec522a09e2e2bed46fad0da443adcea2b86fe9d3f898e96a9cd3313",
    ("ling", "unified_nochunk"):
        "b9a588c6a3826e1e1994dd1e06a968f7e9e4432db9c1454b43b4f06b17d9e0fe",
    ("xing", "unified_nochunk"):
        "551f3ee4a73a5a6bf0f3ffe1fd0cf6d21b401adcfa694b726aa4e6fcdff187d7",
    ("falcon_h1", "unified_nochunk"):
        "6f13b59af144032a3a3add14309070c849b32d2ecf3085de29dd28147073a78a",
    ("phi4flash", "unified_nochunk"):
        "88832a20c2a221c8e5021f778738a2abdf1d0bed621969964f4010838742bdbe",
    # generation by diffusion over blocks (PR 60: the moe family's chain
    # + the q / k norms, the block rule, `unmask`; re-recorded by PR 61:
    # the riding commits' region, sequences and append)
    ("sdar", "unified"):
        "688479f75b13a3cf12d116dcddcb63a8c6033d5252b38b36487c0bc9a023f81b",
    ("sdar", "unified_nochunk"):
        "3c28ec12190ab1eee96be4f9800d7268937dd43c7d2827ebcfc0044743646a18",
    # a hybrid whose state blocks hold a tail and no state, the prefix
    # cache ON (PR 64: the hybrid chain + the `C` mixer, a snapshot plane
    # a block, a state table of B + 4); it moved none of the twenty-six
    ("lfm2", "unified"):
        "d605330e1d7d990fd28043f9174e4b8171444b2a72d3f8b950aa5151ec17b297",
    ("lfm2", "unified_nochunk"):
        "7c6ce324a41f74a404b8368fbda13bceefff58cdd2d6c336b512163547f4e80e",
}


@functools.lru_cache(maxsize=None)
def _engine(family):
    return ENGINES[family]()


def lower_step(eng, program):
    """The step program `program` of `eng` lowered from shapes, with the
    nine positional inputs `benchmarks/` passes it: (w, tok, pools,
    positions, num_tokens, kv_lengths, tables, tok_page, tok_off). What
    is a pair for whom: a table and a page column a layer KIND where the
    model has window layers; the summary rows, pooling pages and offsets
    of chunk-summary attention; the state table of a hybrid; the rows a
    slot's pass unmasks where the model generates by blocks — whose
    riding commits are sequences of their own between the slots and the
    chunk."""
    B = eng.max_slots
    S = B + eng._riders + 1
    C = eng._chunk_parts()[program[len("unified"):]]
    T = eng._launch_rows(C)

    def i32(*d):
        return jax.ShapeDtypeStruct(d, jnp.int32)

    lens, table, page, off = i32(S), i32(S, eng.pages_per_seq), \
        i32(T), i32(T)
    if eng.num_window_pages:
        table, page = (table, table), (page, page)
    if eng._eva:
        pooled = i32(2, B + C // eng._p["cfg"].chunk_size)
        lens, page, off = (lens, lens), (page, pooled), (off, pooled)
    if eng._ssm_layers:
        # (a finite-history family with the prefix cache on: one entry
        # more, the page whose snapshot the chunk continues)
        lens = (lens, i32(B + 3 + eng._tail_snapshots))
    if eng._block:
        lens = (lens, i32(B))
    return eng._programs[program].lower(
        eng._w, i32(T), eng._pools, i32(T), i32(S), lens, table, page,
        off)


@pytest.mark.parametrize("program", ["unified", "unified_nochunk"])
@pytest.mark.parametrize("family", sorted(ENGINES))
def test_the_step_lowers_to_the_pinned_text(family, program):
    text = lower_step(_engine(family), program).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PINS[family, program]


# ---------------------------------------------------------------------------
# the TRAINER's step (ISSUE 66): the Mistral training cell's program at its
# rehearsal sizes on four simulated devices (ZeRO 2 x mp 2, remat "full",
# fused qkv / ffn).  The step builder takes the decoder family from the
# model since PR 66 (a routed family's layers hand a loss term out of the
# layer scan, a period of layer kinds is the scan's unit); a dense family's
# step must lower to the text it lowered to before.  Recorded at PR 66's
# parent (aea953f) and unchanged by PR 66.
# ---------------------------------------------------------------------------

TRAIN_PIN = "8fcbacbffce859e8d2bf7f2f175734b09e438f14529c5b98f34a6d2a15f720ea"


def test_the_mistral_train_step_lowers_to_the_pinned_text():
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.trainer.pretrain import (PretrainConfig,
                                             build_llama_pretrain_step,
                                             make_hybrid_mesh_for)
    import paddle_tpu as paddle
    paddle.seed(7)
    mc = LlamaConfig(vocab_size=512, hidden_size=128, intermediate_size=256,
                     num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=2, head_dim=32,
                     max_position_embeddings=64, rope_theta=1000000.0,
                     rms_norm_eps=1e-5, sequence_parallel=False,
                     fuse_attention_qkv=True, fuse_attention_ffn=True,
                     fuse_pack_groups=2)
    cfg = PretrainConfig(mc, global_batch=4, seq_len=64, mp=2, sharding=2,
                         remat="full", scan_layers=False, ce_chunks=2)
    mesh = make_hybrid_mesh_for(cfg, devices=jax.devices()[:4])
    state, jstep, meta = build_llama_pretrain_step(cfg, mesh)
    spec = jax.ShapeDtypeStruct((4, 64), jnp.int32,
                                sharding=meta["data_sharding"])
    text = jstep.lower(state, spec, spec).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == TRAIN_PIN
