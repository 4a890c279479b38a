"""The readings that TYPICAL_MULTIPLE and WORST_SHARE_OF_SD of
``systems/xing_serving.py`` lie between, on the chip (PERF.md,
Findings of PR 50):

    chiprun -- python3 benchmarks/tools/xing_limit.py --seeds 11 12 13

For each seed: the cell's own checked sample through the engine, then
the cell's check (``typical_over_noise`` / ``worst_over_sd``: the
engine's readings, which have to stay under the limits) and, with the
same logits of the engine, what has to come out over one of them: the
float32 reference with ONE fault planted — a single Sinkhorn iteration
instead of 20, Hpost without its factor 2, the coefficients rounded to
bfloat16, the correction bias dropped (``with_<fault>``: what an engine
with that fault would show) — and the reference with its operands
rounded to float8, the nearest precision below the configuration's
bfloat16 (``float8_reference``).
"""

import argparse
import json
import os
import sys
import time

T_START = time.time()
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[11])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import numpy as np
    from benchmarks.lib import serving, traffic
    from benchmarks.lib.harness import Harness, as_run, load_json, say
    from benchmarks.systems.xing_serving import System
    config = load_json(os.path.join(
        BENCH, "configs", "xing4.0-29b-a4b-serve-ep4-d20.json"))
    mix = as_run(load_json(os.path.join(
        BENCH, "traffic", "assistant-steady.json")), args.rehearse)
    Harness(T_START, 1, args.rehearse, False)     # the device check
    for seed in args.seeds:
        system = System(config, args.rehearse, seed)
        system.check_args["planted_faults"] = True
        rng = np.random.default_rng(seed + 1)
        sample = [traffic.Req(0.0, rng.integers(0, system.vocab, n,
                                                dtype=np.int32),
                              int(mix.get("check_output_len", 24)))
                  for n in mix["check_prompt_lens"]]
        outs = serving.run_requests(system.engine, sample)
        check = system.check([{"prompt": r.prompt, "output": o}
                              for r, o in zip(sample, outs)])
        say(f"seed {seed}: {json.dumps(check)}")
        del system
    return 0


if __name__ == "__main__":
    sys.exit(main())
