"""Where the spread of a cell's walls lives, from the audit lines of its runs.

    python benchmarks/chip/walls.py <log> [<log> ...]

Each log is the standard output of one or more ``--trace 0`` runs (what
prove.sh appends to ``chiprun_out/<cell>.log``); a run is its audit line
with ``starts_s``, ``walls_s`` and ``collections``. Printed for each run:
its median and mean wall, the drift inside the window (the last fifth's
median over the first fifth's), the stalls (walls over 3x the median: how
many, their seconds over the median, how many overlap a full collection),
and the collector's share of the window. Then the split over the runs:

- between processes: the spread of the runs' medians (interquartile
  distance over the median, as spread.py's), beside the same spread of the
  runs' means and of the means with the stalls taken out;
- drift: the median and the widest of the runs' drifts;
- stalls: their count and their seconds as a share of all the windows.
"""

from __future__ import annotations

import json
import os
import runpy
import statistics
import sys

STALL = 3.0  # a wall over this many medians is a stall
spread = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "spread.py"))["spread"]


def runs(paths: list) -> list:
    found = []
    for path in paths:
        with open(path) as f:
            for line in f:
                if line.startswith("{") and '"walls_s"' in line:
                    found.append(json.loads(line))
    # a traced run's few queries are no window
    return [run for run in found if not run.get("trace")]


def one(run: dict) -> dict:
    """The readings of one run's window."""
    walls, starts = run["walls_s"], run["starts_s"]
    median = statistics.median(walls)
    fifth = max(1, len(walls) // 5)
    collections = run.get("collections", [])
    full = [(start, start + length) for generation, start, length
            in collections if generation == 2]
    stalls = [(start, wall) for start, wall in zip(starts, walls)
              if wall > STALL * median]
    calm = [wall for wall in walls if wall <= STALL * median]
    return {
        "n": len(walls),
        "median": median,
        "mean": statistics.fmean(walls),
        "calm_mean": statistics.fmean(calm),
        "drift": statistics.median(walls[-fifth:])
        / statistics.median(walls[:fifth]) - 1,
        "stalls": len(stalls),
        "stall_s": sum(wall - median for _start, wall in stalls),
        "stalls_in_full_collection": sum(
            1 for start, wall in stalls
            if any(a < start + wall and start < b for a, b in full)),
        "full_collections": len(full),
        "full_collection_s": sum(b - a for a, b in full),
        "longest_collection_s": max(
            (length for _g, _s, length in collections), default=0.0),
        "collector_s": run.get("young_collections_s", 0.0) + sum(
            length for _g, _s, length in collections),
        "window_s": run["window_s"],
    }


def main(paths: list) -> None:
    readings = [one(run) for run in runs(paths)]
    for r in readings:
        print("run: n {n} median {median:.6f} mean {mean:.6f} drift "
              "{drift:+.3%} stalls {stalls} ({stall_s:.4f} s over the median, "
              "{stalls_in_full_collection} in a full collection) full "
              "collections {full_collections} ({full_collection_s:.4f} s, "
              "longest of any {longest_collection_s:.4f}) collector "
              "{collector_s:.4f} s of {window_s:.2f}".format(**r))
    if len(readings) < 2:
        return
    medians = [r["median"] for r in readings]
    print(f"between processes: medians spread {spread(medians):.3%} "
          f"(min {min(medians):.6f} max {max(medians):.6f}), means spread "
          f"{spread([r['mean'] for r in readings]):.3%}, means without "
          f"stalls {spread([r['calm_mean'] for r in readings]):.3%}")
    drifts = [r["drift"] for r in readings]
    print(f"drift inside a window: median {statistics.median(drifts):+.3%}, "
          f"widest {max(drifts, key=abs):+.3%}")
    window = sum(r["window_s"] for r in readings)
    stall = sum(r["stall_s"] for r in readings)
    print(f"stalls: {sum(r['stalls'] for r in readings)} of "
          f"{sum(r['n'] for r in readings)} readings, {stall:.4f} s = "
          f"{stall / window:.3%} of the windows, "
          f"{sum(r['stalls_in_full_collection'] for r in readings)} in a "
          f"full collection; the collector "
          f"{sum(r['collector_s'] for r in readings) / window:.3%} of the "
          f"windows")


if __name__ == "__main__":
    main(sys.argv[1:])
