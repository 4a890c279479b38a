"""Find the highest arrival rate a serving cell sustains: ONE process,
the system built once, a window per rate.  Not part of a check; the
builder runs it once on the chip and writes 0.8 x the knee into the
mix's ``rate_per_s``.

    python3 benchmarks/tools/sweep_rate.py --workload <cell> --rates 2,4,6 --seconds 20

With ``--seeds a,b,c --order seeded --drain 60`` it gives one window per
rate and seed with the arrival order drawn from the seed: how far the
tail moves when other long prompts meet.
"""

import argparse
import importlib
import json
import os
import sys
import time

T_START = time.time()
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default=None)
    ap.add_argument("--order", choices=("seeded", "fixed"), default=None)
    ap.add_argument("--drain", type=float, default=0.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from benchmarks.lib import serving, stats, traffic
    from benchmarks.lib.harness import Harness, as_run, load_json, say
    from benchmarks.runners._serve import kv_tokens_of
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(REPO, conf["file"]))
    mix = as_run(load_json(os.path.join(
        REPO, "benchmarks", "traffic", cell["traffic"] + ".json")),
        args.rehearse)
    if args.order:
        mix["order"] = args.order
    seeds = [int(x) for x in (args.seeds or str(args.seed)).split(",")]
    h = Harness(T_START, 1, args.rehearse, False)
    system = importlib.import_module(
        f"benchmarks.systems.{config['system']}").System(
            config, args.rehearse, args.seed)
    eng = system.engine
    warm = traffic.open_loop(dict(mix, rate_per_s=4.0), 2.0, args.seed,
                             system.vocab, system.max_total)
    serving.run_requests(eng, warm)
    for rate, seed in [(float(r), s) for r in args.rates.split(",")
                       for s in seeds]:
        reqs = traffic.open_loop(dict(mix, rate_per_s=rate), args.seconds,
                                 seed, system.vocab, system.max_total)
        comp = h.compiles.window()
        obs = serving.window(eng, serving.OpenSource(reqs), h,
                             args.seconds, args.drain, kv_tokens_of)
        res = serving.reduce_window(obs, True)
        row = {"rate": rate, "seed": seed, "offered": len(reqs),
               "completed": res["completed"],
               "backlog_at_end": res["cut_at_end"],
               "ttft": stats.summary(res["ttft_ms"]),
               "tpot": stats.summary(res["tpot_ms"]),
               "queue": stats.summary(res["queue_wait_ms"]),
               "tok_s": res["serve_tok_s"],
               "steps": res["steps_in_window"],
               "mean_live": res["mean_live_requests"],
               "compiles": comp()["compiles"]}
        say("SWEEP " + json.dumps(row))
        if res["cut_at_end"] > 2 * eng.max_slots:
            say("SWEEP stops: the backlog no longer drains at this rate")
            break
        # drain what is left so the next rate starts empty
        while eng.has_work():
            eng.step()
        eng.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
