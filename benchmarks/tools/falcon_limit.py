"""The readings that TYPICAL_MULTIPLE, WORST_SHARE_OF_SD and
STATE_MULTIPLE of ``systems/falcon_serving.py`` lie between, on the chip
(PERF.md, Findings of PR 54):

    chiprun --timeout 3000 -- python3 benchmarks/tools/falcon_limit.py --seeds 11 12 13

For each seed: the cell's own checked sample through the engine, then
the cell's check (``typical_over_noise`` / ``worst_over_sd`` /
``state_over_noise``: the engine's readings, which have to stay under
the limits) and, with the same logits and the same state of the engine,
what has to come out over one of them: the float32 reference with ONE
fault planted (``fault_<name>`` — the recurrent state rounded to
bfloat16 after every token; ``m`` in another column order; interleaved
rotary pairs; no rotation; ``--drops`` of the fourteen multipliers read
as 1: what an engine with that fault would show), and the reference
with its matrices rounded to float8, the nearest precision below the
configuration's bfloat16 (``float8_reference``; the bfloat16 reference
is the yardstick itself and reads 1).  Each reading's ``by_sample`` is
in the order of the mix's ``check_prompt_lens``.  The first seed also
prints the ratio of the two branches' norms in layer 0
(``|out_s| / |out_a|``) that the draw gives.
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
NAME, MIX = "falcon-h1-34b-serve-pp8-d9", "docchat-steady"
DROPS = ("ssm_z", "ssm_B", "key", "mlp_gate", "attention_out", "ssm_out",
         "embedding", "lm_head")    # (~25 s a fault a seed on the chip)


def branch_ratio(system, ids):
    """|out_s| / |out_a| over the positions of ``ids`` in layer 0, by
    the float32 reference."""
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.lib import reference_falcon as ref
    w = system._ref_weights
    sp = ref.spec(system.cfg, q_block=512)
    with ref.highest():
        x = w["embed"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32) \
            * ref._mults(sp)["embedding"]
        a = ref._rms(x, w["layers"][0]["norm1"], sp.eps)
        s = ref._mamba(a, w["layers"][0], sp, jnp.float32)
        o = ref._attention(a, w["layers"][0], sp, jnp.float32)
    return float(np.linalg.norm(s) / np.linalg.norm(o)), \
        float(np.linalg.norm(s) / np.linalg.norm(x))


def limits(config, mix, seed, rehearse, faults, first):
    import numpy as np
    from benchmarks.lib import serving, traffic
    from benchmarks.lib.harness import say
    from benchmarks.systems.falcon_serving import System
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
    outs = serving.run_requests(system.engine, sample)
    check = system.check([{"prompt": r.prompt, "output": o}
                          for r, o in zip(sample, outs)])
    if first:
        check["branch_ratio_layer0"], check["state_branch_over_residual"] = \
            branch_ratio(system, sample[1].prompt[:512])
    say(f"seed {seed}: {json.dumps(check)}")
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    if os.path.isdir(out):
        with open(os.path.join(out, "falcon_limit.jsonl"), "a") as f:
            f.write(json.dumps({"seed": seed, **check}) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11])
    ap.add_argument("--drops", nargs="*", default=list(DROPS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmarks.lib import reference_falcon as ref
    from benchmarks.lib.harness import Harness, as_run, load_json
    config = load_json(os.path.join(BENCH, "configs", NAME + ".json"))
    mix = as_run(load_json(os.path.join(BENCH, "traffic", MIX + ".json")),
                 args.rehearse)
    faults = ref.FAULTS + tuple("drop_" + d for d in args.drops)
    Harness(T_START, 1, args.rehearse, False)     # the device check
    for i, seed in enumerate(args.seeds):
        limits(config, mix, seed, args.rehearse, faults, i == 0)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
