"""Step time of the serving engine against its deployment parameters
(page size), ONE process, the weights made once.  Not part of a check:
the builder runs it once on the chip to fix the configuration's
``engine`` table; PERF.md records what it read.

    python3 benchmarks/tools/sweep_engine.py --config <name> --page-sizes 32,64,128
"""

import argparse
import gc
import json
import os
import statistics
import sys
import time

T_START = time.time()
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def timed_steps(eng, reqs):
    for prompt, new in reqs:
        eng.add_request(prompt, max_new_tokens=new)
    rows = []
    while eng.has_work():
        t0 = time.perf_counter()
        out = eng.step()
        rows.append((time.perf_counter() - t0, out["prefill_tokens"],
                     out["decoded"]))
    eng.collect()
    return rows


def med(xs):
    return round(1e3 * statistics.median(xs), 2) if xs else None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--page-sizes", required=True)
    ap.add_argument("--pool-tokens", type=int, default=48000)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import numpy as np
    from paddle_tpu.serving import ServingEngine
    from benchmarks.lib.harness import Harness, load_json, say
    from benchmarks.systems.llama_serving import System
    config = load_json(os.path.join(REPO, "benchmarks", "configs",
                                    args.config + ".json"))
    h = Harness(T_START, 1, args.rehearse, False)
    system = System(config, args.rehearse, 1)
    base = dict(system.engine_args)
    rng = np.random.default_rng(0)
    slots, ctx = base["max_slots"], base["max_context"]
    short, long_ = (200, 3000) if not args.rehearse else (20, 150)
    for ps in [int(x) for x in args.page_sizes.split(",")]:
        system.engine = None
        gc.collect()
        kw = dict(base, page_size=ps, num_pages=args.pool_tokens // ps
                  if not args.rehearse else base["num_pages"])
        t0 = time.perf_counter()
        eng = ServingEngine(system.model, **kw)
        system.engine = eng
        done = h.compiles.window()
        warm = timed_steps(eng, [(rng.integers(0, system.vocab, 40,
                                               dtype=np.int32), 4)])
        t_compile = time.perf_counter() - t0
        dec = timed_steps(eng, [(rng.integers(0, system.vocab, short,
                                              dtype=np.int32), 40)
                                for _ in range(slots)])
        lng = timed_steps(eng, [(rng.integers(0, system.vocab, long_,
                                              dtype=np.int32), 8)
                                for _ in range(8)])
        say("ENGINE " + json.dumps({
            "page_size": ps, "num_pages": kw["num_pages"],
            "ragged": eng.ragged, "first_steps_s": round(t_compile, 1),
            "compiles": done()["compiles"],
            "decode_step_ms_32_slots": med(
                [t for t, p, d in dec if p == 0 and d >= slots - 1]),
            "short_prefill_step_ms": med([t for t, p, d in dec if p > 0]),
            "long_prefill_step_ms": med([t for t, p, d in lng if p > 0]),
            "long_decode_step_ms_8_slots": med(
                [t for t, p, d in lng if p == 0]),
            "warm_steps": len(warm)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
