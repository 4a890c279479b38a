"""The readings that TYPICAL_MULTIPLE and WORST_SHARE_OF_SD of
``systems/ling_serving.py`` lie between, on the chip (PERF.md,
Findings of PR 47):

    chiprun --timeout 3000 -- python3 benchmarks/tools/ling_limit.py --seeds 11 12 13

For each seed: the cell's own checked sample through the engine, then
the cell's check (``typical_over_noise`` / ``worst_over_sd``: the
engine's readings, which have to stay under the limits) and, with the
same logits of the engine, what has to come out over one of them: the
float32 reference with ONE fault planted (``fault_<name>`` for each of
``reference_ling.ABLATIONS`` — the recurrent state rounded to
bfloat16 after every token; the router's expert bias dropped from the
choice; the top-k over all groups; the gate before the heads' norm; no
gate on the latent mixer's heads; one decay a head for all its channels:
what an engine with that fault would show), and the reference with its
matrices rounded to float8, the nearest precision below the
configuration's bfloat16 (``float8_reference``; the bfloat16 reference
is the yardstick itself and reads 1).  Each reading's ``by_sample`` is
in the order of the mix's ``check_prompt_lens``.
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
NAME, MIX = "ling-3.0-flash-serve-ep8-d7", "reason-steady"


def limits(config, mix, seed, rehearse):
    import numpy as np
    from benchmarks.lib import serving, traffic
    from benchmarks.lib.harness import say
    from benchmarks.systems.ling_serving import System
    system = System(config, rehearse, seed)
    system.check_args["planted_faults"] = True
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
    say(f"seed {seed}: {json.dumps(check)}")
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    if os.path.isdir(out):
        with open(os.path.join(out, "ling_limit.jsonl"), "a") as f:
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
