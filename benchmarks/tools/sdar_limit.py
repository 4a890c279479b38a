"""The readings that TYPICAL_MULTIPLE and WORST_SHARE_OF_SD of
``systems/sdar_serving.py`` lie between, on the chip (PERF.md, Findings
of PR 60):

    chiprun --timeout 3000 -- python3 benchmarks/tools/sdar_limit.py --seeds 11 12 13 14

For each seed: the cell's own checked sample through the engine (three
prompts of remainders 3, 2 and 1, six blocks each), then the cell's
check (``typical_over_noise`` / ``worst_over_sd``: the engine's readings
over every denoise pass's still-masked rows, which have to stay under
the limits; ``rule_exact`` and ``committed_equal``, which have to hold)
and, with the same logits of the engine, what has to come out over one
of the limits: the float32 reference with ONE fault planted
(``without_causal``: the plain causal mask in place of the block rule;
``without_qk_norm``; ``without_renorm``; ``without_commit``: every
earlier block's last-unmasked rows fed as the mask token, the K/V an
engine without its commit pass would have left) and the reference with
its operands rounded to float8, the nearest precision below the
configuration's bfloat16 (``float8_reference``; the bfloat16 reference is
the yardstick itself and reads 1).  Each reading's ``by_sample`` is in
the order of the mix's ``check_prompt_lens``.
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
NAME, MIX = "sdar-30b-a3b-serve-pp8-d6", "blockgen-steady"


def limits(config, mix, seed, rehearse):
    import numpy as np
    from benchmarks.lib import serving, traffic
    from benchmarks.lib.harness import say
    from benchmarks.systems.sdar_serving import System
    system = System(config, rehearse, seed)
    system.check_args["planted_faults"] = True
    rng = np.random.default_rng(seed + 1)
    sample = [traffic.Req(0.0, rng.integers(0, system.vocab, n,
                                            dtype=np.int32),
                          int(mix.get("check_output_len", 24)))
              for n in mix["check_prompt_lens"]]
    t0 = time.time()
    outs = serving.run_requests(system.engine, sample)
    t1 = time.time()
    check = system.check([{"prompt": r.prompt, "output": o}
                          for r, o in zip(sample, outs)])
    check["sample_s"], check["check_s"] = t1 - t0, time.time() - t1
    say(f"seed {seed}: {json.dumps(check)}")
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "sdar_limit.jsonl"), "a") as f:
        f.write(json.dumps({"seed": seed, **check}) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmarks.lib.harness import Harness, as_run, load_json
    config = load_json(os.path.join(BENCH, "configs", NAME + ".json"))
    mix = as_run(load_json(os.path.join(BENCH, "traffic", MIX + ".json")),
                 args.rehearse)
    Harness(T_START, 1, args.rehearse, False)     # the device check
    for seed in args.seeds:
        limits(config, mix, seed, args.rehearse)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
