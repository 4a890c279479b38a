"""The second half of ``test_tpu_aot_compile.py`` (split at ISSUE 68, when
the one file passed 300 s of case time and ended tier-1's run alone:
ROADMAP D9): the compiled serving STEP — its pools updated in place, the
shared kernel copies against the plain calls — and the train path, for
the same DESCRIBED TPU v5e.  The rules of that file hold here: the
topology is described inside its module-scoped ``chip`` fixture, which
this file takes from it together with the smoke's widths.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest

from test_tpu_aot_compile import (D, F32, H, HQ, I, I32, KV,  # noqa: F401
                                  chip)


# ---------------------------------------------------------------------------
# the serving step owns its page pools (ISSUE 29): every pool parameter
# is aliased to the output that replaces it, and no copy of a pool is
# left in the compiled step.  Donation is honoured on the CPU too, so a
# CPU test cannot see the copy come back; this one can.
# ---------------------------------------------------------------------------

def _small_engine(family):
    """A ragged engine at toy widths the chip's tiling accepts (heads x
    128, page 16, the smoke's 8 slots + a 32-row chunk), weights in
    bfloat16 as the serving cells hold them."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    paddle.seed(0)
    if family == "window":
        from paddle_tpu.models.laguna import (LagunaForCausalLM,
                                              laguna_tiny_config)
        model = LagunaForCausalLM(laguna_tiny_config(
            head_dim=128, experts_held=(4, 4)))
    elif family == "latent":
        from paddle_tpu.models.axk1 import (AXK1ForCausalLM,
                                            axk1_tiny_config)
        model = AXK1ForCausalLM(axk1_tiny_config(experts_held=(4, 4)))
    else:
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        model = LlamaForCausalLM(llama_tiny_config(
            hidden_size=256, num_attention_heads=2, num_key_value_heads=1,
            head_dim=128))
    model.eval()
    for _, prm in model.named_parameters():
        prm._data = prm._data.astype(jnp.bfloat16)
    return ServingEngine(model, max_slots=8, page_size=16, max_context=128,
                         prefill_chunk=32, num_pages=65)


def _pool_copies(text, shapes):
    """Lines of a compiled program that copy an array of a pool's shape
    (`copy`, or the `copy-done` of an asynchronous one)."""
    pat = re.compile("= (" + "|".join(
        re.escape("bf16[" + ",".join(map(str, s)) + "]") for s in shapes)
        + r")\S* copy(-done)?\(")
    return [ln.strip() for ln in text.splitlines() if pat.search(ln)]


@pytest.mark.parametrize("chunk_part", ["chunk", "nochunk"])
@pytest.mark.parametrize("family,n_shapes", [("llama", 1), ("window", 2),
                                             ("latent", 1)],
                         ids=["llama_engines_choice", "window_two_pools",
                              "latent_one_pool_a_layer"])
def test_unified_step_updates_its_pools_in_place(chip, family, n_shapes,
                                                 chunk_part):
    """... at both of the step's row counts (ISSUE 53): with the prefill
    chunk's rows behind the decode rows, and the decode rows alone."""
    eng = _small_engine(family)
    assert eng.ragged
    B = eng.max_slots
    sfx = "" if chunk_part == "chunk" else "_nochunk"
    C = eng._chunk_parts()[sfx]
    unified, jit_feed = (eng._programs[name + sfx]
                         for name in ("unified", "feed"))
    rows, seqs = chip.shape((B + C,), I32), chip.shape((B + 1,), I32)
    table = chip.shape((B + 1, eng.pages_per_seq), I32)
    if family == "window":      # a table and a page column a layer kind
        table, page = (table, table), (rows, rows)
    else:
        page = rows

    def deployed(pool):
        # a deployment's pool, in shape only (the body takes the page
        # count from its argument): 64 MiB, or 48 under a window. A toy
        # pool is staged through fast memory whoever owns it, and those
        # copies would hide the one this test is about.
        kv, pages, psz, d = pool.shape
        mib = 48 if pages == eng.num_window_pages else 64
        return chip.shape((kv, mib * 2 ** 20 // (kv * psz * d * 2) + 1,
                           psz, d), pool.dtype)

    pools = jax.tree.map(deployed, eng._pools)
    args = (jax.tree.map(lambda a: chip.shape(a.shape, a.dtype), eng._w),
            rows, pools, rows, seqs, seqs, table, page, rows)
    shapes = {p.shape for p in jax.tree.leaves(pools)}
    assert len(shapes) == n_shapes
    compiled = unified.lower(*args).compile()
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        2 * math.prod(p.shape) for p in jax.tree.leaves(pools))
    copies = _pool_copies(compiled.as_text(), shapes)
    assert not copies, copies[:3]
    # the step hands back the greedy token of each logits row, and the
    # token feed (ISSUE 34: a decode row's input stays on the device)
    # compiles for the chip at the step's own shapes, to the `tok` the
    # step takes
    logits, _, tokens, *_ = compiled.out_info
    assert (tokens.shape, tokens.dtype) == (logits.shape[:1], I32)
    feed = jit_feed.lower(chip.shape(tokens.shape, I32), rows,
                          rows).compile()
    assert (feed.out_info.shape, feed.out_info.dtype) == (rows.shape, I32)
    if not C:
        return
    # the same body without ownership: the copies this test looks for
    # are there, so the pattern still reads what the compiler prints
    # (asked once, at the full row count)
    plain = jax.jit(eng._make_unified_body(C)).lower(*args).compile()
    assert plain.memory_analysis().alias_size_in_bytes == 0
    assert len(_pool_copies(plain.as_text(), shapes)) >= len(eng._pools)


# ---------------------------------------------------------------------------
# the per-layer kernels go through ONE jitted copy a step's layers share
# (`engine._once`, ISSUE 53: a program's first launch traces and lowers
# a kernel once, not once a layer).  The step then CALLS what it held
# inline, so its lowered text is not the plain calls'; the compiler
# inlines the calls, and this is where that is checked: the compiled
# step is the plain calls' step, instruction for instruction, in every
# family that takes the shared copies and at both row counts.
# ---------------------------------------------------------------------------

def _kernel_family_engine(family):
    """An engine of each family whose body calls `_once`, at widths the
    chip's tiling accepts (beside `_small_engine`'s three): GPT, EvaByte,
    a Nemotron stage (state-space, routed, attention), three Ling layers
    (KDA dense, KDA routed, latent routed), Xing's four streams."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    if family in ("llama", "window", "latent"):
        return _small_engine(family)
    paddle.seed(0)
    eng = dict(max_slots=8, page_size=16, max_context=256,
               prefill_chunk=128, num_pages=65)
    if family == "gpt":
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny_config
        model = GPTForCausalLM(gpt_tiny_config(
            hidden_size=256, num_attention_heads=2,
            max_position_embeddings=256))
    elif family == "eva":
        from paddle_tpu.models.evabyte import (EvaByteForCausalLM,
                                               evabyte_tiny_config)
        model = EvaByteForCausalLM(evabyte_tiny_config(
            intermediate_size=512, vocab_size=320, num_hidden_layers=2,
            max_position_embeddings=1024, hidden_size=512,
            num_attention_heads=4, num_key_value_heads=4, chunk_size=16,
            window_size=512, num_pred_heads=2, rope_positions=1024))
        eng = dict(max_slots=32, page_size=256, max_context=1024,
                   prefill_chunk=256)
    elif family == "ssm":
        from paddle_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                                  nemotron_h_tiny_config)
        model = NemotronHForCausalLM(nemotron_h_tiny_config(
            hidden_size=256, hybrid_override_pattern="ME*E",
            num_attention_heads=2, num_key_value_heads=1, head_dim=128,
            mamba_num_heads=8, mamba_head_dim=64, n_groups=2,
            ssm_state_size=128, chunk_size=128, moe_latent_size=128,
            moe_intermediate_size=128,
            moe_shared_expert_intermediate_size=128))
    elif family == "kda":
        from paddle_tpu.models.bailing_hybrid import (
            BailingHybridForCausalLM, bailing_hybrid_tiny_config)
        model = BailingHybridForCausalLM(bailing_hybrid_tiny_config(
            hidden_size=256, intermediate_size=256, layers_held=(0, 4, 5),
            num_attention_heads=2, num_key_value_heads=2, head_dim=128,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, kda_sub_chunk=64, moe_intermediate_size=128,
            moe_shared_expert_intermediate_size=128))
    else:
        assert family == "mhc"
        from paddle_tpu.models.xing import XingForCausalLM, xing_tiny_config
        model = XingForCausalLM(xing_tiny_config(
            hidden_size=128, kv_lora_rank=512, qk_rope_head_dim=64,
            qk_nope_head_dim=128, v_head_dim=128))
        eng.update(max_slots=128, num_pages=1100)
    model.eval()
    for _, prm in model.named_parameters():
        prm._data = prm._data.astype(jnp.bfloat16)
    return ServingEngine(model, **eng)


_HLO_DEF = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*)$")
_HLO_REF = re.compile(r"%[\w.\-]+")


def _what_it_computes(text):
    """A compiled module as the multiset of its ENTRY instructions, each
    named by what it computes: its line with every instruction's and
    called computation's own name replaced by the digest of what that
    name stands for. Left out, because they say where an instruction
    came from and not what it does: metadata and frontend attributes, a
    Mosaic kernel's serialized body (its bytecode carries source
    locations; the kernels are the same Python either way), the number
    of a parameter and the order of a fusion's operands (XLA numbers a
    fused computation's parameters as it meets them)."""
    import collections
    import hashlib
    text = re.sub(r",? ?(metadata|frontend_attributes)="
                  r"\{(?:[^{}]|\{[^}]*\})*\}", "", text)
    text = re.sub(r'"body":"[^"]*"', '"body":""', text)
    comps, entry, name = {}, None, None
    for ln in text.splitlines():
        head = re.match(r"^(ENTRY )?(%[\w.\-]+) .*\{$", ln)
        if head and " = " not in ln:
            name = head.group(2)
            comps[name] = []
            entry = name if head.group(1) else entry
        elif ln.startswith("}"):
            name = None
        elif name and _HLO_DEF.match(ln):
            comps[name].append(_HLO_DEF.match(ln).groups())
    digests = {}

    def sha(s):
        return hashlib.sha1(s.encode()).hexdigest()[:16]

    def computation(c):
        if c not in digests:
            digests[c] = sha("\n".join(sorted(instructions(c).values())))
        return digests[c]

    def instructions(c):
        seen = {}

        def named(m):
            ref = m.group(0)
            return seen.get(ref) or (
                "@" + computation(ref) if ref in comps else ref)

        for lhs, rhs in comps[c]:
            rhs = re.sub(r"/\*index=\d+\*/", "", _HLO_REF.sub(named, rhs))
            rhs = re.sub(r"parameter\(\d+\)", "parameter()", rhs)
            rhs = re.sub(
                r" fusion\(([^)]*)\)", lambda m: " fusion(%s)" % ",".join(
                    sorted(m.group(1).split(", "))), rhs)
            seen[lhs] = "#" + sha(rhs)
        return seen

    return collections.Counter(instructions(entry).values())


@pytest.mark.parametrize("family", ["llama", "window", "latent", "gpt",
                                    "eva", "ssm", "kda", "mhc"])
def test_shared_kernel_copies_compile_to_the_plain_calls_step(
        chip, family, monkeypatch):
    from paddle_tpu.serving import engine as engine_mod
    eng = _kernel_family_engine(family)
    assert eng.ragged

    def plain(fn, scope, *args, **static):
        with jax.named_scope(scope):
            return fn(*args, **static)

    def compiled(chunk):
        args, _ = eng._program_shapes(chunk)
        args = jax.tree.map(lambda a: chip.shape(a.shape, a.dtype), args)
        return jax.jit(eng._make_unified_body(chunk),
                       donate_argnums=2).lower(*args).compile()

    for chunk in eng._chunk_parts().values():
        shared = compiled(chunk)
        text = shared.as_text()
        with monkeypatch.context() as mp:
            mp.setattr(engine_mod, "_once", plain)
            inline = compiled(chunk)
        assert text.count("tpu_custom_call") > len(eng._w["layers"])
        assert _what_it_computes(text) == \
            _what_it_computes(inline.as_text())
        assert sum(_what_it_computes(text).values()) > 100
        for stat in ("alias_size_in_bytes", "temp_size_in_bytes",
                     "argument_size_in_bytes", "output_size_in_bytes"):
            assert getattr(shared.memory_analysis(), stat) == \
                getattr(inline.memory_analysis(), stat), stat


# ---------------------------------------------------------------------------
# train path (run_pretrain's llama3_8b_shard recipe: batch 3, seq 8192)
# ---------------------------------------------------------------------------

def _flash_loss(q, k, v):
    from paddle_tpu.ops.pallas_flash import flash_sdpa
    return flash_sdpa(q, k, v, causal=True).astype(F32).sum()


def _padded_columns(text):
    """(custom call, type) of every operand and result of the compiled
    module's Mosaic calls whose MINOR dimension is 1 under an (8, 128)
    tile: 128 lanes of HBM a number."""
    import re
    types = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\(.*?\)|\S+) ",
                            text, re.M))
    shape = re.compile(r"\w+\[([\d,]*)\]\{([\d,]*)(:[^}]*)?\}")
    found = []
    for name, typ, args in re.findall(
            r"^\s*(?:ROOT )?(%[\w.\-]+) = (\(.*?\)|\S+) custom-call\((.*?)\),"
            r" custom_call_target=\"tpu_custom_call\"", text, re.M):
        operands = [types.get(o, "") for o in re.findall(r"%[\w.\-]+", args)]
        for t in [typ, *operands]:
            for dims, order, tiles in shape.findall(t):
                dims = [int(d) for d in dims.split(",") if d]
                if len(dims) > 1 and "T(8,128)" in tiles \
                        and dims[int(order.split(",")[0])] == 1:
                    found.append((name, t))
    return found


def test_flash_attention_fwd_and_grad_compile(chip):
    qkv = chip.shape((3, 8192, HQ, D))   # sdpa repeats kv heads first
    f = jax.grad(_flash_loss, argnums=(0, 1, 2))
    assert chip.compiles(f, qkv, qkv, qkv), chip.refusals.get(f)
    # the four-chip training cell's one-chip shape, blocks of 512: the
    # row statistics (lse, di) cross HBM as dense [B, H, 1, S] rows
    cell = chip.shape((2, 8192, 16, D))
    text = jax.jit(f).lower(cell, cell, cell).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "f32[2,16,1,8192]" in text
    assert _padded_columns(text) == []
    assert _padded_columns(
        "%a = f32[2,16,8192,1]{3,2,1,0:T(8,128)} parameter(0)\n"
        "%c = bf16[2,16,8192,128]{3,2,1,0:T(8,128)(2,1)} custom-call(%a), "
        'custom_call_target="tpu_custom_call"') != []


def _train_elementwise_loss(x, nw, q, k, cos, sin, g, u):
    from paddle_tpu.ops.fused import fused_rms_norm, fused_rope, swiglu
    qr, kr = fused_rope(q, k, cos, sin)
    return (fused_rms_norm(x, nw, 1e-6).astype(F32).sum()
            + qr.astype(F32).sum() + kr.astype(F32).sum()
            + swiglu(g, u).astype(F32).sum())


def test_train_norm_rope_swiglu_fwd_and_grad_compile(chip):
    B, Sq = 3, 8192
    trig = chip.shape((Sq, D // 2), F32)
    act = chip.shape((B, Sq, I))
    f = jax.grad(_train_elementwise_loss, argnums=(0, 1, 2, 3, 6, 7))
    assert chip.compiles(
        f, chip.shape((B, Sq, H)), chip.shape((H,)),
        chip.shape((B, Sq, HQ, D)), chip.shape((B, Sq, KV, D)),
        trig, trig, act, act), chip.refusals.get(f)


def test_trainer_step_scatters_its_row_products_over_mp(chip):
    """The trainer's step at toy widths, `sharding 2 x mp 2` on the
    described 2x2 (the four-chip cell's own build path, from shapes
    only): the sequence layout (PR 65) in the TPU compiler's text.  Every
    sum of a `[B, S, H]` activation over the mp pairs is a reduce-scatter
    (the compiler's `%all-reduce-scatter` fusion), never a bare
    all-reduce; the norms reduce S/mp rows a chip; the gathers of those
    rows answer to the matmuls that use them."""
    from benchmarks.systems import llama_pretrain
    B, S, H = 4 // 2, 256, 256
    config = {"vocab_size": 512, "hidden_size": H, "intermediate_size": 512,
              "num_hidden_layers": 2, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 64, "rope_theta": 1e4,
              "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
              "trainer": {"parallel": {"sharding": 2, "mp": 2},
                          "seq_len": S, "global_batch": 4,
                          "fuse_pack_groups": 2, "remat": "full",
                          "scan_layers": False, "ce_chunks": 2}}
    text = llama_pretrain.compile_for(config, chip.devices).as_text()
    act = f"bf16[{B},{S},{H}]"
    comp, sums, gathers = None, [], []
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) \(", line)
        if head:
            comp = head.group(1)
        op = re.search(r"= (\S+?)\{[^ ]* (all-reduce|all-gather)"
                       r"(?:-start)?\(", line)
        if op and op.group(1) == act:
            name = re.search(r'op_name="([^"]*)"', line)
            (sums if op.group(2) == "all-reduce" else gathers).append(
                (comp, name.group(1) if name else ""))
    # embed + (attn_out, ffn) x (fwd, bwd) + attn_out recomputed, a layer
    assert len(sums) >= 1 + 2 * 5, sums
    assert all(c.startswith("%all-reduce-scatter") for c, _ in sums), sums
    assert gathers and all(
        any(f"/{s}/" in n for s in ("qkv_proj", "attn_out", "ffn"))
        or "(embed)" in n for _, n in gathers), gathers
    for scope in ("/attn_norm/", "/ffn_norm/", "(head_loss)"):
        rows = set(re.findall(
            r"= f32\[(\d+),(\d+)\]\S* reduce\([^\n]*op_name=\"[^\"]*"
            + re.escape(scope), text))
        assert rows and rows <= {(str(B), str(S // 2))}, (scope, rows)


# -- the trainer's routed FFN (ISSUE 67): the permutations' own rules -----

def _routed_ffn_grads(x, wr, wg, wu, wd):
    from paddle_tpu.incubate.moe import dropless_expert_ffn

    def loss(x, wr, wg, wu, wd):
        gates = jax.nn.softmax(x.astype(F32) @ wr, -1)
        with jax.named_scope("routed_ffn"):
            y, _ = dropless_expert_ffn(x, gates, wg, wu, wd, top_k=8,
                                       renormalize=True, held=(16, 16))
        return jnp.sum(y.astype(F32))
    return jax.grad(loss, (0, 1, 2, 3, 4))(x, wr, wg, wu, wd)


def test_the_trained_routed_ffn_compiles_without_a_row_scatter(chip):
    """A Mellum2 layer's routed FFN at the trained cell's sizes (16,384
    tokens x top-8 = 131,072 pair rows of 2,304, 16 of 64 experts held),
    forward and backward, for the described chip: the chunked walk of
    the owned rows lowers (a `while` for the dispatch's forward, one for
    the combine's backward), and no scatter of rows is left under the
    dispatch or the combine — only `bincount`'s seventeen integers."""
    from paddle_tpu.observability import attribution
    T, H, W, E, held = 16384, 2304, 896, 64, 16
    assert chip.compiles(
        _routed_ffn_grads, chip.shape((T, H)), chip.shape((H, E), F32),
        chip.shape((held, H, W)), chip.shape((held, H, W)),
        chip.shape((held, W, H))), chip.refusals.get(_routed_ffn_grads)
    text = chip.texts[_routed_ffn_grads]
    scatters, whiles = [], []
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if name is None or attribution._path_own(name.group(1)) not in (
                "moe_dispatch", "moe_combine"):
            continue
        if " scatter(" in line:
            scatters.append(line.split(" = ")[1].split(" ")[0])
        if " while(" in line:
            whiles.append(attribution._path_own(name.group(1)))
    assert scatters and all(s.startswith("s32[17]") for s in scatters), \
        scatters
    assert sorted(whiles) == ["moe_combine", "moe_dispatch"], whiles
