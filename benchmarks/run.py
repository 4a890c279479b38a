"""Run ONE cell of BENCHMARK.json ONCE, in this process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the system from the seed, warms up the cell's own shapes, checks
outputs against the plain reference, measures for ``--seconds`` and
prints the result as the last line of standard output.  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics.  Without a TPU whose ``device_kind`` is in the benchmark's
peak table it exits non-zero and prints no result.

``--rehearse`` is the one exception: the same control flow at the toy
sizes in each file's ``rehearsal`` table, on whatever jax finds.  Its
line says ``"rehearsal": true`` and every timed or traced metric in it
is null: nothing it prints is a device number.

The harness is driven by data.  A cell is an entry of ``workloads``; its
configuration is ``configs/<config>.json``, its traffic
``traffic/<traffic>.json``, whose ``kind`` names ``runners/<kind>.py``;
the configuration's ``system`` names ``systems/<system>.py``; each
per-layer metric is read by ``layer_metrics/<metric>.py``.  A new cell,
configuration, mix or metric is new files and new entries.
"""

import time
T_START = time.time()       # process start, as near as Python can take it

import argparse             # noqa: E402
import importlib            # noqa: E402
import importlib.util       # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import sys                  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
TIMED_SOURCES = ("host_clock", "device_trace", "program_span")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_reader(name: str):
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, REPO)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        sys.exit(f"unknown workload {args.workload!r}; BENCHMARK.json has "
                 f"{sorted(cells)}")
    cell = cells[args.workload]
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])
    if args.rehearse and cell["chips"] > 1:
        # virtual CPU devices stand in for the chips; set before jax loads
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")

    from benchmarks.lib.harness import Harness, load_json, say
    config = load_json(os.path.join(REPO, conf_entry["file"]))
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 cell["traffic"] + ".json"))
    h = Harness(T_START, cell["chips"], args.rehearse, bool(args.trace))
    say(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']} ({mix['kind']}), seed {args.seed}, "
        f"{seconds:g}s, trace {args.trace}"
        + (" — REHEARSAL, no device metric" if args.rehearse else ""))
    runner = importlib.import_module(f"benchmarks.runners.{mix['kind']}")
    res = runner.run(h, config, mix, args.seed, seconds)

    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            reader = load_reader(m["name"])
            value = reader.read(h) if reader is not None else None
            if value is None:
                continue        # nothing to read: left out of the line
            if args.rehearse and m["source"] in TIMED_SOURCES:
                value = None
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if not applies(m, cell["name"]):
                continue
            if m["name"] not in res["end_to_end"]:
                sys.exit(f"the run produced no {m['name']}")
            value = None if args.rehearse else res["end_to_end"][m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics,
            "device": h.device_block()}
    if args.rehearse:
        line["rehearsal"] = True
    elif args.trace and h.reduced is not None:
        from benchmarks.lib.trace import breakdown
        line["breakdown"] = breakdown(h.reduced)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
