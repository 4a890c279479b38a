"""The spread of a cell's runs, as the builder's instructions define it:
for each metric and each set of runs, the distance between the first and
third quartile (``statistics.quantiles(values, n=4)``) as a share of the
median.  Reads result lines (the last line of each run's output).

    python3 benchmarks/tools/spread.py set1/*.log -- set2/*.log
"""

import json
import statistics
import sys


def last_line(path):
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def spread(values):
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    sets, cur = [], []
    for a in sys.argv[1:]:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    table = {}
    for i, paths in enumerate(sets):
        for p in paths:
            line = last_line(p)
            if line is None:
                print(f"no result line in {p}")
                continue
            if not line["correct"] or line["failed"]:
                print(f"{p}: correct={line['correct']} "
                      f"failed={line['failed']}/{line['attempted']}")
            for name, m in line["metrics"].items():
                table.setdefault(name, {}).setdefault(i, []).append(
                    m["value"])
    for name, by_set in table.items():
        for i, vals in sorted(by_set.items()):
            print(f"{name} set {i + 1}: n={len(vals)} median="
                  f"{statistics.median(vals):.6g} spread="
                  f"{100 * spread(vals):.2f}% values="
                  f"{[round(v, 4) for v in vals]}")
        meds = [statistics.median(v) for _, v in sorted(by_set.items())]
        if len(meds) == 2:
            print(f"{name}: second median / first = {meds[1] / meds[0]:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
