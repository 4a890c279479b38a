"""The readings that TYPICAL_MULTIPLE, WORST_SHARE_OF_SD and
STATE_MULTIPLE of ``systems/phi4flash_serving.py`` lie between, on the
chip (PERF.md, Findings of PR 56):

    chiprun --timeout 3000 -- python3 benchmarks/tools/phi4flash_limit.py --seeds 11 12 13

For each seed: the cell's own checked sample through the engine, then
the cell's check (``typical_over_noise`` / ``worst_over_sd`` /
``state_over_noise``: the engine's readings, which have to stay under
the limits) and, with the same logits and the same state of the engine,
what has to come out over one of them: the float32 reference with ONE
fault planted (``fault_<name>``, each of
``reference_phi4flash.ABLATIONS``: what an engine with that fault would
show), and the reference with its operands rounded to float8, the
nearest precision below the configuration's bfloat16
(``float8_reference``; the bfloat16 reference is the yardstick itself
and reads 1).  Each reading's ``by_sample`` is in the order of the mix's
``check_prompt_lens``.  The first seed also prints the ratio of the two
terms of a differential head that the draw gives, ``|a1| / |lambda a2|``
and ``|a1 - lambda a2| / |a1|`` in layers 1 (window) and 17 (full) over
1,024 positions, and lambda there.
"""

import argparse
import gc
import json
import os
import sys
import time

T_START = time.time()
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
NAME, MIX = "phi-4-mini-flash-serve-whole", "longreason-saturated"


def head_terms(system, ids, l):
    """(lambda, |a1| / |lambda a2|, |a1 - lambda a2| / |a1|) of layer
    ``l``'s differential heads over the positions of ``ids``, by the
    float32 reference fed the embedding's rows (no layers below)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.lib import reference_phi4flash as ref
    w = system._ref_weights
    sp = ref.spec(system.cfg)
    L = w["layers"][l]
    f32 = jnp.float32
    H, KV, D = sp.heads, sp.kv_heads, sp.head_dim
    with ref.highest():
        x = w["embed"][jnp.asarray(ids, jnp.int32)].astype(f32)
        a = ref._ln(x, L["ln1"], L["ln1_b"], sp.eps)
        S = a.shape[0]
        q = (a @ L["wq"].astype(f32) + L["bq"].astype(f32)).reshape(S, H, D)
        k = (a @ L["wk"].astype(f32) + L["bk"].astype(f32)).reshape(S, KV, D)
        v = (a @ L["wv"].astype(f32) + L["bv"].astype(f32)).reshape(S, KV, D)
        i = np.arange(H // 2)
        vp = jnp.concatenate([v[:, 2 * (i // 2)], v[:, 2 * (i // 2) + 1]], -1)
        t = jnp.arange(S)
        seen = t[:, None] >= t[None, :]
        if ref.layer_kinds(len(w["layers"]))[l] == "W":
            seen &= t[:, None] - t[None, :] < sp.window

        def soft(qs, ks):
            sc = jnp.einsum("qhd,khd->hqk", qs, ks) * D ** -0.5
            p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
            return jnp.einsum("hqk,khw->qhw", p, vp)

        a1 = soft(q[:, 2 * i], k[:, 2 * (i // 2)])
        a2 = soft(q[:, 2 * i + 1], k[:, 2 * (i // 2) + 1])
        lam = float(jnp.exp(jnp.sum(L["lq1"].astype(f32) * L["lk1"].astype(f32)))
                    - jnp.exp(jnp.sum(L["lq2"].astype(f32) * L["lk2"].astype(f32)))
                    + ref.lambda_init(l))
    n = np.linalg.norm
    return {"layer": l, "lambda": lam, "lambda_init": ref.lambda_init(l),
            "a1_over_lambda_a2": float(n(a1) / n(lam * a2)),
            "diff_over_a1": float(n(a1 - lam * a2) / n(a1))}


def limits(config, mix, seed, rehearse, faults, first):
    import numpy as np
    from benchmarks.lib import serving, traffic
    from benchmarks.lib.harness import say
    from benchmarks.systems.phi4flash_serving import System
    system = System(config, rehearse, seed)
    system.check_args["planted_faults"] = faults
    rng = np.random.default_rng(seed + 1)
    chunk = system.engine.prefill_chunk     # (the runner's own fallback)
    lens = mix.get("check_prompt_lens") or \
        [chunk + chunk // 4, chunk // 2, max(chunk // 4, 2)]
    sample = [traffic.Req(0.0, rng.integers(0, system.vocab, n,
                                            dtype=np.int32),
                          int(mix.get("check_output_len", 24)))
              for n in lens]
    t0 = time.time()
    outs = serving.run_requests(system.engine, sample)
    t1 = time.time()
    check = system.check([{"prompt": r.prompt, "output": o}
                          for r, o in zip(sample, outs)])
    check["sample_s"], check["check_s"] = t1 - t0, time.time() - t1
    if first:
        n = len(system._ref_weights["layers"])
        check["head_terms"] = [
            head_terms(system, sample[1].prompt[:1024], l)
            for l in (1, n // 2 + 1)]
    say(f"seed {seed}: {json.dumps(check)}")
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "phi4flash_limit.jsonl"), "a") as f:
        f.write(json.dumps({"seed": seed, **check}) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11])
    ap.add_argument("--faults", nargs="*", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmarks.lib import reference_phi4flash as ref
    from benchmarks.lib.harness import Harness, as_run, load_json
    config = load_json(os.path.join(BENCH, "configs", NAME + ".json"))
    mix = as_run(load_json(os.path.join(BENCH, "traffic", MIX + ".json")),
                 args.rehearse)
    faults = tuple(ref.ABLATIONS if args.faults is None else args.faults)
    Harness(T_START, 1, args.rehearse, False)     # the device check
    for i, seed in enumerate(args.seeds):
        limits(config, mix, seed, args.rehearse, faults, i == 0)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
