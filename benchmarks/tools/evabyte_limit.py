"""The readings that TYPICAL_MULTIPLE and WORST_SHARE_OF_SD of
``systems/evabyte_serving.py`` lie between, on the chip (PERF.md,
Findings of PR 35):

    chiprun --timeout 3000 -- python3 benchmarks/tools/evabyte_limit.py --seeds 11 12 13

For each seed: the cell's own checked sample through the engine, then
the cell's check (``typical_over_noise`` / ``worst_over_sd``: the
engine's readings, which have to stay under the limits) and, with the
same logits of the engine, what has to come out over one of them: the
float32 reference with ONE fault planted (``fault_<name>`` for each of
``reference_evabyte.ABLATIONS``: no pooled rows, mean pooling, ``mu``
off, a sliding window, pooled rows visible before their window closes,
a bfloat16 residual stream, gain ``g`` for ``1 + g`` — what an engine
with that fault would show), and the reference with its matrices
rounded to float8, the nearest precision below the configuration's
bfloat16 (``float8_reference``).  Each reading's ``by_sample`` is in
the order of the mix's ``check_prompt_lens``.
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
    from benchmarks.systems.evabyte_serving import System
    config = load_json(os.path.join(
        BENCH, "configs", "evabyte-6.5b-serve-pp4-d8.json"))
    mix = as_run(load_json(os.path.join(
        BENCH, "traffic", "filectx-saturated.json")), args.rehearse)
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
        out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
        if os.path.isdir(out):
            with open(os.path.join(out, "evabyte_limit.jsonl"), "a") as f:
                f.write(json.dumps({"seed": seed, **check}) + "\n")
        del system
    return 0


if __name__ == "__main__":
    sys.exit(main())
