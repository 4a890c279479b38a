"""The readings that TYPICAL_MULTIPLE and WORST_SHARE_OF_SD of
``systems/ouro_serving.py`` lie between, on the chip, and the
page-size readings the configuration's file records (PERF.md, Findings of PR 39):

    chiprun --timeout 3000 -- python3 benchmarks/tools/ouro_limit.py --seeds 11 12 13
    chiprun --timeout 3000 -- python3 benchmarks/tools/ouro_limit.py --seeds --page-sizes 32 64 128

For each seed: the cell's own checked sample through the engine, then
the cell's check (``typical_over_noise`` / ``worst_over_sd``: the
engine's readings, which have to stay under the limits) and, with the
same logits of the engine, what has to come out over one of them: the
float32 reference with ONE fault planted (``fault_<name>`` for each of
``reference_ouro.ABLATIONS`` — one pass; three passes; pass u reading
pass u - 1's rows; no norm between the passes; the two output norms
dropped; the embedding added again every pass — and
``fault_shared_slot``, a cache of one slot a layer, the paper's
last-pass reuse: what an engine with that fault would show), and the
reference with its matrices rounded to float8, the nearest precision
below the configuration's bfloat16 (``float8_reference``; the bfloat16
reference is the yardstick itself and reads 1).  Each reading's
``by_sample`` is in the order of the mix's ``check_prompt_lens``.

``--page-sizes``: an engine at each page size, the pool's tokens held
fixed — seconds to the first step's result (lowering and compiling,
cold or from the cache as the machine has it) and the cell's traffic
for ``--seconds``.
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
NAME, MIX = "ouro-2.6b-serve-whole", "reason-saturated"


def _sample(system, mix, seed):
    import numpy as np
    from benchmarks.lib import traffic
    rng = np.random.default_rng(seed + 1)
    return [traffic.Req(0.0, rng.integers(0, system.vocab, n, dtype=np.int32),
                        int(mix.get("check_output_len", 24)))
            for n in mix["check_prompt_lens"]]


def _record(name: str, rec: dict) -> None:
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    if os.path.isdir(out):
        with open(os.path.join(out, name), "a") as f:
            f.write(json.dumps(rec) + "\n")


def limits(config, mix, seed, rehearse):
    from benchmarks.lib import serving
    from benchmarks.lib.harness import say
    from benchmarks.systems.ouro_serving import System
    system = System(config, rehearse, seed)
    system.check_args["planted_faults"] = True
    sample = _sample(system, mix, seed)
    outs = serving.run_requests(system.engine, sample)
    check = system.check([{"prompt": r.prompt, "output": o}
                          for r, o in zip(sample, outs)])
    say(f"seed {seed}: {json.dumps(check)}")
    _record("ouro_limit.jsonl", {"seed": seed, **check})


def timed(h, config, mix, seed, seconds, what):
    """The cell's traffic for ``seconds`` through an engine built as
    ``config`` says: what the first step cost and the window's steps."""
    import numpy as np
    from benchmarks.lib import serving, stats, traffic
    from benchmarks.lib.harness import say
    from benchmarks.runners._serve import kv_tokens_of
    from benchmarks.systems.ouro_serving import System
    t0 = time.perf_counter()
    system = System(config, h.rehearse, seed)
    eng = system.engine
    eng.on_logits = None
    t1 = time.perf_counter()
    eng.add_request(np.arange(5, dtype=np.int32), max_new_tokens=2)
    eng.run_to_completion()
    first = time.perf_counter() - t1
    serving.run_requests(eng, _sample(system, mix, seed))
    reqs = traffic.closed_loop(mix, seconds, seed, system.vocab,
                               system.max_total)
    obs = serving.window(eng, serving.ClosedSource(reqs, int(mix["clients"])),
                         h, seconds, 0.0, kv_tokens_of)
    res = serving.reduce_window(obs, False)
    gaps = np.diff([s["t"] for s in obs["steps"]]) * 1e3
    rec = dict(what, build_s=round(t1 - t0, 1), first_step_s=round(first, 1),
               steps=len(obs["steps"]), step_ms_p50=float(np.median(gaps)),
               step_ms_p95=stats.percentile(list(gaps), 95),
               tpot_p95_ms=stats.percentile(res["tpot_ms"], 95),
               serve_tok_s=res["serve_tok_s"],
               mean_live=res["mean_live_requests"],
               completed=res["completed"], engine=system.engine_args)
    say(f"timed: {json.dumps(rec)}")
    _record("ouro_timed.jsonl", rec)
    del system, eng
    gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, nargs="*", default=[11])
    ap.add_argument("--page-sizes", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmarks.lib.harness import Harness, as_run, load_json
    config = load_json(os.path.join(BENCH, "configs", NAME + ".json"))
    mix = as_run(load_json(os.path.join(BENCH, "traffic", MIX + ".json")),
                 args.rehearse)
    h = Harness(T_START, 1, args.rehearse, False)     # the device check
    for seed in args.seeds:
        limits(config, mix, seed, args.rehearse)
        gc.collect()
    table = "rehearsal" if args.rehearse else None
    eng = (config[table] if table else config)["engine"]
    tokens = eng["num_pages"] * eng["page_size"]
    for ps in args.page_sizes:
        sized = dict(eng, page_size=ps, num_pages=tokens // ps)
        conf = dict(config, engine=sized)
        if table:
            conf["rehearsal"] = dict(config[table], engine=sized)
        timed(h, conf, mix, 7, args.seconds, {"page_size": ps})
    return 0


if __name__ == "__main__":
    sys.exit(main())
