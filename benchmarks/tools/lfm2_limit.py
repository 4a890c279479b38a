"""The readings that TYPICAL_MULTIPLE, WORST_SHARE_OF_SD and
BORDER_MULTIPLE of ``systems/lfm2_serving.py`` lie between, on the chip
(PERF.md, Findings of PR 64):

    chiprun --timeout 3000 -- python3 benchmarks/tools/lfm2_limit.py --seeds 11 12 13 14

For each seed: the cell's own checked sample through the engine (prompts
of 8,000, 1,300 and 61 tokens, 24 tokens each), then the cell's check —
which serves the adoption probe itself (``X[:4096] + 2`` whole, a MISS;
then the same prompt again and ``X[:4096] + 700`` side by side:
``adopted_tokens`` has to read [4096, 4096]) — with
``typical_over_noise`` / ``worst_over_sd`` the engine's readings over all
five samples and ``border_over_noise`` the hit's first generated row from
the miss's, which have to stay under the limits (``miss_typical_over_noise``
is the MISS held to the reference: where the short adopter reads high,
whether adoption is at fault); and, with the same logits of the engine,
what has to come out over one of the limits: the float32 reference with
ONE fault planted (``fault_conv_silu``: a silu on the convolution;
``fault_gate_b``: the B gate left out; ``fault_bias_weighs``: the expert
bias weighing; ``fault_renorm``: no renormalisation; ``fault_qk_norm``: no
q / k norm; and over the probe's two samples ``fault_tail_zero``: zeros in
place of the snapshot at the adoption, ``fault_tail_stale``: the snapshot
of the page BEFORE, each with its ``border``: the faulty reference's
border row from the engine's miss) and the reference with its operands
rounded to float8, the nearest precision below the configuration's
bfloat16 (``float8_reference``; the bfloat16 reference is the yardstick
itself and reads 1).  ``border_by_reference`` is what the border row reads
when held to the reference and not to the miss.  Each reading's
``by_sample`` is in the order of the mix's ``check_prompt_lens``, then the
probe's two.
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
NAME, MIX = "lfm2-24b-a2b-serve-pp4-d10", "agent-0.8knee"


def limits(config, mix, seed, rehearse):
    import numpy as np
    from benchmarks.lib import serving, traffic
    from benchmarks.lib.harness import say
    from benchmarks.systems.lfm2_serving import System
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
    with open(os.path.join(out, "lfm2_limit.jsonl"), "a") as f:
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
