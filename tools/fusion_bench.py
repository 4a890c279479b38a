"""FLAGS_use_fusion_compiler on/off delta (VERDICT r1 item 5).

Runs a naively-written transformer block stack (inline rmsnorm, softmax
SDPA composite, silu*up FFN — the code a user ports from the reference
without touching fused ops) with and without the jit.fusion pattern
pass, on the local device. Writes docs/FUSION_BENCH.json.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.jit.fusion import fuse


def block(x, w1, wq, wk, wv, wo, w2, wg, wu, wd, B, S, H, D):
    def rms(h, w):
        h32 = h.astype(jnp.float32)
        var = jnp.mean(jnp.square(h32), -1, keepdims=True)
        return (h32 * jax.lax.rsqrt(var + 1e-6)).astype(h.dtype) * w

    h = rms(x, w1)
    q = (h @ wq).reshape(B, S, H, D).transpose(0, 2, 1, 3)
    k = (h @ wk).reshape(B, S, H, D).transpose(0, 2, 1, 3)
    v = (h @ wv).reshape(B, S, H, D).transpose(0, 2, 1, 3)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (D ** -0.5)
    probs = jax.nn.softmax(logits, -1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    x = x + (o.transpose(0, 2, 1, 3).reshape(B, S, H * D) @ wo)
    h2 = rms(x, w2)
    return x + (jax.nn.silu(h2 @ wg) * (h2 @ wu)) @ wd


def flagship_decode_rows() -> dict:
    """VERDICT r3 item 3: measure the C++ StableHLO pass where it matters —
    the 8B-shard serving path (prefill step + decode step), not synthetic
    stacks. Records the achieved delta even if ~1.0x (XLA already fuses
    much of this; the honest number bounds the pass's real contribution)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama3_8b_shard_config)
    from paddle_tpu.generation import (_decode_params, _cached_step_body,
                                       _llama_weights, _init_caches)
    from paddle_tpu.jit import fusion_cc

    if not fusion_cc.available():
        return {"skipped": "fusion_pass.so unavailable"}

    S0, new = 1024, 128
    total = S0 + new
    B = 8
    cfg = llama3_8b_shard_config(mp=8, pp=4,
                                 max_position_embeddings=total)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    for prm in model.parameters():
        prm._data = prm._data.astype(jnp.bfloat16)
    p = _decode_params(model)
    w = _llama_weights(p)
    body = _cached_step_body(p, total)
    rng = np.random.RandomState(0)
    ids_pf = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S0)), jnp.int32)
    ids_dec = ids_pf[:, :1]
    caches = _init_caches(p, B, total)

    def bench_pair(tag, start, ids, reps):
        def fn(w, ids, caches):
            return body(w, ids, caches, start)
        plain = jax.jit(fn)

        def run_plain():
            logits, _ = plain(w, ids, caches)
            return logits

        fused = fusion_cc.fuse_compile(fn, w, ids, caches)

        def run_fused():
            logits, _ = fused(w, ids, caches)
            return logits

        def t(run):
            float(jnp.sum(run()))
            t0 = time.perf_counter()
            for _ in range(reps):
                o = run()
            float(jnp.sum(o))
            return (time.perf_counter() - t0) / reps * 1e3

        tp = t(run_plain)
        tf = t(run_fused)
        d = float(jnp.max(jnp.abs(run_plain().astype(jnp.float32)
                                  - run_fused().astype(jnp.float32))))
        return {f"{tag}_plain_ms": round(tp, 3),
                f"{tag}_fused_ms": round(tf, 3),
                f"{tag}_speedup": round(tp / tf, 3),
                f"{tag}_matches": fused.n_fused,
                f"{tag}_max_abs_diff": d}

    out = dict(config="llama3_8b_shard mp=8 pp=4, B=8, prefill 1024 / "
                      "decode 1 step")
    out.update(bench_pair("prefill", 0, ids_pf, reps=5))
    out.update(bench_pair("decode", S0, ids_dec, reps=20))
    # derive the conclusion from what THIS run measured — never bake a
    # narrative that can contradict the numbers beside it
    psp, dsp = out["prefill_speedup"], out["decode_speedup"]
    if psp < 1.05 and dsp < 1.05:
        out["finding"] = (
            f"pass is not a win on the flagship serving path this run "
            f"(prefill {psp}x, decode {dsp}x): XLA already fuses these "
            "regions; the pass pays off on naive user code (stack/gate "
            "rows). FLAGS_use_fusion_compiler stays opt-in.")
    else:
        out["finding"] = (
            f"pass helped this run (prefill {psp}x, decode {dsp}x); "
            "re-evaluate the opt-in default if this repeats.")
    return out


def main() -> None:
    B, S, H, D, F, L = 4, 2048, 8, 128, 4096, 4
    HD = H * D
    rng = np.random.RandomState(0)
    dt = jnp.bfloat16
    x = jnp.asarray(rng.standard_normal((B, S, HD)), dt)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.02, dt)
    layers = [dict(w1=jnp.ones((HD,), dt), wq=mk(HD, HD), wk=mk(HD, HD),
                   wv=mk(HD, HD), wo=mk(HD, HD), w2=jnp.ones((HD,), dt),
                   wg=mk(HD, F), wu=mk(HD, F), wd=mk(F, HD))
              for _ in range(L)]

    def stack(x, layers):
        for lp in layers:
            x = block(x, lp["w1"], lp["wq"], lp["wk"], lp["wv"],
                      lp["wo"], lp["w2"], lp["wg"], lp["wu"], lp["wd"],
                      B, S, H, D)
        return x

    plain = jax.jit(stack)
    fused = jax.jit(fuse(stack))

    def bench(f, n=10):
        jax.block_until_ready(f(x, layers))
        t0 = time.perf_counter()
        for _ in range(n):
            o = f(x, layers)
        jax.block_until_ready(o)
        return (time.perf_counter() - t0) / n * 1e3

    t_plain = bench(plain)
    t_fused = bench(fused)
    d = np.abs(np.asarray(plain(x, layers), np.float32)
               - np.asarray(fused(x, layers), np.float32)).max()

    # --- round-3 patterns: bias+residual+LN and the MoE gate pair ---
    def brln(xh, r, b, w, lb):
        h = xh + b[None, :] + r
        mu = jnp.mean(h, -1, keepdims=True)
        var = jnp.mean(jnp.square(h - mu), -1, keepdims=True)
        return ((h - mu) * jax.lax.rsqrt(var + 1e-5) * w[None, :]
                + lb[None, :])

    Tb, Hb = 8192, 4096
    xb = jnp.asarray(rng.standard_normal((Tb, Hb)), dt)
    rb = jnp.asarray(rng.standard_normal((Tb, Hb)), dt)
    vb = jnp.asarray(rng.standard_normal((Hb,)), dt)

    def bench1(f, args, n=20):
        float(f(*args).sum())
        t0 = time.perf_counter()
        for _ in range(n):
            o = f(*args)
        float(o.sum())
        return (time.perf_counter() - t0) / n * 1e3

    brln_args = (xb, rb, vb, vb, vb)
    t_brln_plain = bench1(jax.jit(brln), brln_args)
    t_brln_fused = bench1(jax.jit(fuse(brln)), brln_args)

    from paddle_tpu.incubate.moe import top_k_gating
    Tg, Eg, Cg = 8192, 128, 128

    def gate(g):
        d_, c_, _ = top_k_gating(g, 2, Cg)
        return d_.sum() + c_.sum()

    gg = jax.nn.softmax(jnp.asarray(
        rng.standard_normal((Tg, Eg)), jnp.float32), -1)
    t_gate_plain = bench1(jax.jit(gate), (gg,))
    t_gate_fused = bench1(jax.jit(fuse(gate)), (gg,))

    # --- generic-region fusion (round-4): an unnamed elementwise chain ---
    from paddle_tpu.jit import fusion_cc

    def gchain(a, b, c):
        t = jnp.tanh(a * b + c)
        u = jnp.exp(t * 0.5) - jnp.sqrt(jnp.abs(b) + 1.0)
        return jnp.log(jnp.abs(u) + 2.0) / (jax.nn.sigmoid(c) + 3.0)

    Tg2 = 4096
    ga = jnp.asarray(rng.standard_normal((Tg2, 4096)), jnp.float32)
    gb = jnp.asarray(rng.standard_normal((Tg2, 4096)), jnp.float32)
    gc = jnp.asarray(rng.standard_normal((Tg2, 4096)), jnp.float32)
    generic_row = {"shape": [Tg2, 4096], "skipped": "no fusion_pass.so"}
    if fusion_cc.available():
        gf = fusion_cc.fuse_compile(gchain, ga, gb, gc)
        t_g_plain = bench1(jax.jit(gchain), (ga, gb, gc))
        t_g_fused = bench1(gf, (ga, gb, gc))
        generic_row = {
            "shape": [Tg2, 4096], "n_fused": gf.n_fused,
            "plain_ms": round(t_g_plain, 3),
            "fused_ms": round(t_g_fused, 3),
            "speedup": round(t_g_plain / t_g_fused, 3),
            "finding": (
                ("XLA fuses arbitrary elementwise chains natively — the "
                 "generic region pass exists for CINN parity (arbitrary-"
                 "region capability) and this row bounds its real TPU "
                 "contribution honestly.")
                if t_g_fused >= t_g_plain * 0.95 else
                "generic region fusion won this run; re-evaluate.")}

    out = {"device": str(jax.devices()[0].device_kind),
           "generic_chain": generic_row,
           "shape": dict(B=B, S=S, H=H, D=D, F=F, layers=L),
           "plain_ms": round(t_plain, 2), "fused_ms": round(t_fused, 2),
           "speedup": round(t_plain / t_fused, 3),
           "max_abs_diff": float(d),
           "bias_residual_ln": {
               "shape": [Tb, Hb],
               "plain_ms": round(t_brln_plain, 3),
               "fused_ms": round(t_brln_fused, 3),
               "speedup": round(t_brln_plain / t_brln_fused, 3)},
           "moe_gate_pair": {
               "shape": dict(T=Tg, E=Eg, C=Cg, k=2),
               "plain_ms": round(t_gate_plain, 3),
               "fused_ms": round(t_gate_fused, 3),
               "speedup": round(t_gate_plain / t_gate_fused, 3)},
           "flagship_decode": flagship_decode_rows()}
    path = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "FUSION_BENCH.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
