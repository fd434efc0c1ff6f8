"""Median and spread of each metric over the runs prove.sh left behind.

    python benchmarks/chip/spread.py <dir> <cell>

A spread is the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median: the
quantity a bound is set from. Beside it, in brackets, the same with the
set's run farthest from the median left out: the driver refuses a bound as
too tight where the mean of the two sets' such spreads is over HALF the
bound. The rule (PERF.md section 2): the smallest bound, to one significant
figure, whose half is at least 1.5 times the widest such mean of any cell,
never under 0.01. A set's first run compiles; its ``setup_s`` is printed
apart and left out of the spread. Under each set the widest reading of
every number the comparison rests on (the result line's ``compared``),
beside its limit.
"""

import glob
import json
import re
import statistics
import sys


def spread(values: list) -> float:
    if len(values) < 2:
        return 0.0
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(directory: str, cell: str) -> None:
    sets: dict = {}
    for path in sorted(glob.glob(f"{directory}/{cell}.set*.run*.json")):
        k, i = map(int, re.search(r"set(\d+)\.run(\d+)", path).groups())
        with open(path) as f:
            sets.setdefault(k, {})[i] = json.load(f)
    for k, runs in sorted(sets.items()):
        lines = [runs[i] for i in sorted(runs)]
        print(f"{cell} set {k}: {len(lines)} runs, correct "
              f"{[r['correct'] for r in lines]}, attempted "
              f"{[r['attempted'] for r in lines]}, failed "
              f"{[r['failed'] for r in lines]}")
        for name in lines[0]["metrics"]:
            values = [r["metrics"][name]["value"] for r in lines
                      if name in r["metrics"]]
            if name == "setup_s" and k == 1:
                print(f"  {name} first run {values[0]:.3f}")
                values = values[1:]
            median = statistics.median(values)
            kept = list(values)
            kept.remove(max(values, key=lambda v: abs(v - median)))
            print(f"  {name} median {median:.6g} spread "
                  f"{spread(values):.4%} ({spread(kept):.4%}) "
                  f"min {min(values):.6g} max {max(values):.6g}")
        for name in lines[0].get("compared", {}):
            readings = [r["compared"][name] for r in lines]
            print(f"  compared {name} widest "
                  f"{max(n['value'] for n in readings):.6g} limit "
                  f"{readings[0]['limit']}")
    try:
        with open(f"{directory}/{cell}.trace.json") as f:
            print(f"{cell} traced: {f.read().strip()}")
    except FileNotFoundError:
        pass
    for path in sorted(glob.glob(f"{directory}/{cell}.more*.json")):
        with open(path) as f:
            line = json.load(f)
        print(f"{path}: correct {line['correct']}, attempted "
              f"{line['attempted']}, failed {line['failed']}, "
              f"{ {n: m['value'] for n, m in line['metrics'].items()} }")


if __name__ == "__main__":
    main(*sys.argv[1:3])
