"""Median and spread of each metric over the runs prove.sh left behind.

    python benchmarks/chip/spread.py <dir> <cell>

A spread is the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median: the
quantity a bound is set from (about five times the widest spread of a
metric over the cells, never under 1%). A set's first run compiles; its
``setup_s`` is printed apart and left out of the spread.
"""

import glob
import json
import re
import statistics
import sys


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
            spread = 0.0
            if len(values) > 1:
                q1, _, q3 = statistics.quantiles(values, n=4)
                spread = (q3 - q1) / median
            print(f"  {name} median {median:.6g} spread {spread:.4%} "
                  f"min {min(values):.6g} max {max(values):.6g}")
    try:
        with open(f"{directory}/{cell}.trace.json") as f:
            print(f"{cell} traced: {f.read().strip()}")
    except FileNotFoundError:
        pass


if __name__ == "__main__":
    main(*sys.argv[1:3])
