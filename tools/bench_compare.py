#!/usr/bin/env python
"""Diff two bench result JSON files (BENCH_DETAIL.json shape) and flag
regressions — the machine-checkable half of the bench trajectory.

Compares, wherever both files carry them:

- per-query wall seconds (``per_query_s``; ``--queries`` restricts)
- suite total (``total_s``)
- warm-repeat walls (``warm_repeat_s``)
- peak staged bytes (``peak_staged_bytes``, direction-aware: LOWER is
  better — memory regressions are flagged even when walls hold)
- serving metrics folded into ``meta.serving`` by `bench.py --serving`
  (qps: HIGHER is better; cheap/straggler p99 ms: LOWER is better; SLO
  latency attainment: HIGHER is better)
- micro_bench cases under ``micro`` (a {case: record} map or the raw
  benchmarks/micro_bench.py JSONL record list): per-metric direction —
  ms/copied_mb/peak_staged_mb LOWER, gbps/mb_per_s HIGHER; a case
  marked "skipped" (e.g. data_plane_wire_lz4 without the lz4 module)
  never reads as a regression

A comparison REGRESSES when the current value is worse than baseline by
more than ``--threshold`` (relative, default 0.10 = 10%); values under
``--min-seconds`` are skipped for per-query walls (sub-threshold noise
on a 50 ms query is not signal). Exit code: 0 = no regression, 1 =
regression(s), 2 = usage/IO error. ``--json`` prints the full
machine-readable comparison document on stdout.

Usage:
  python tools/bench_compare.py BASELINE.json CURRENT.json
  python tools/bench_compare.py a.json b.json --threshold 0.25 --json
  python tools/bench_compare.py a.json b.json --queries q1,q6
"""

from __future__ import annotations

import argparse
import json
import sys


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench_compare: cannot read {path}: {e}", file=sys.stderr)
        raise SystemExit(2)


def _rel_change(base: float, cur: float) -> float:
    """(cur - base) / base; 0 for a zero/degenerate baseline."""
    if not base:
        return 0.0
    return (cur - base) / base


def _compare_value(name: str, base, cur, threshold: float,
                   higher_is_better: bool = False,
                   min_value: float = 0.0) -> dict:
    entry = {
        "name": name,
        "baseline": base,
        "current": cur,
        "higher_is_better": higher_is_better,
    }
    try:
        b, c = float(base), float(cur)
    except (TypeError, ValueError):
        entry["status"] = "skipped"
        return entry
    if max(abs(b), abs(c)) < min_value:
        entry["status"] = "skipped"  # below the noise floor
        return entry
    change = _rel_change(b, c)
    if b == 0 and c != 0:
        # zero baseline: any growth is infinite relative change. A
        # lower-is-better metric that was 0 (copied_mb on the shm plane)
        # regressing to nonzero must flag, not hide behind the
        # degenerate division. Finite sentinel keeps --json standard.
        change = 1e9 if c > 0 else -1e9
    entry["rel_change"] = round(change, 4)
    worse = (-change if higher_is_better else change) > threshold
    better = (change if higher_is_better else -change) > threshold
    entry["status"] = ("regression" if worse
                       else "improvement" if better else "ok")
    return entry


#: micro_bench case metric -> direction (True = higher is better).
#: Metrics not listed here are informational and never compared.
_MICRO_DIRECTIONS = {
    "ms": False,
    "gbps": True,
    "mb_per_s": True,
    "copied_mb": False,   # bytes a socket carried: the shm-vs-copy axis
    "payload_mb": False,
    "peak_staged_mb": False,
    "ratio": False,
    "speedup_vs_copy": True,
    # runtime-adaptivity axes (skew_shuffle_* / partial_agg_bailout_*):
    # per-task tail + the static/adaptive wall ratio. Adaptation COUNTS
    # (skew_splits, bailed_out, replan totals in BENCH_DETAIL meta) stay
    # unlisted on purpose — they are informational context, and "fired
    # more often" is neither a regression nor an improvement by itself.
    "task_p99_ms": False,
    "speedup_vs_static": True,
    "overhead_vs_off": False,
}


def _micro_cases(doc: dict) -> dict:
    """A document's `micro` section as {case: record}. Accepts either
    that map directly or the raw benchmarks/micro_bench.py JSONL record
    list (each record self-names via its "bench" field)."""
    m = doc.get("micro")
    if isinstance(m, list):
        m = {r.get("bench"): r for r in m
             if isinstance(r, dict) and r.get("bench")}
    return m if isinstance(m, dict) else {}


def compare(baseline: dict, current: dict, threshold: float = 0.10,
            queries=None, min_seconds: float = 0.02) -> dict:
    """-> {"comparisons": [...], "regressions": [...],
    "improvements": [...], "threshold": t}. Pure function of the two
    documents (unit-testable without files)."""
    comparisons: list = []

    def section(base_map, cur_map, prefix, **kw) -> None:
        if not isinstance(base_map, dict) or not isinstance(cur_map, dict):
            return
        keys = sorted(set(base_map) & set(cur_map))
        if queries is not None:
            keys = [k for k in keys if k in queries]
        for k in keys:
            comparisons.append(_compare_value(
                f"{prefix}{k}", base_map[k], cur_map[k], threshold, **kw
            ))

    section(baseline.get("per_query_s"), current.get("per_query_s"),
            "per_query_s:", min_value=min_seconds)
    section(baseline.get("warm_repeat_s"), current.get("warm_repeat_s"),
            "warm_repeat_s:", min_value=min_seconds)
    # direction-aware memory column: peak staged bytes per query/arm
    # (LOWER is better — a growing staged peak is a data-plane
    # regression even when walls hold)
    section(baseline.get("peak_staged_bytes"),
            current.get("peak_staged_bytes"), "peak_staged_bytes:")
    if baseline.get("total_s") is not None and (
        current.get("total_s") is not None
    ):
        comparisons.append(_compare_value(
            "total_s", baseline["total_s"], current["total_s"], threshold
        ))
    bs = (baseline.get("meta") or {}).get("serving") or {}
    cs = (current.get("meta") or {}).get("serving") or {}
    #: serving metric -> direction (True = higher is better)
    serving_metrics = {
        "qps": True,
        "cheap_p99_ms": False,
        "cheap_p50_ms": False,
        "straggler_p99_ms_on": False,
        "slo_latency_attainment": True,
        "peak_staged_bytes": False,
        "burst_p99_ms_cache_off": False,
        "burst_p99_ms_cache_on": False,
        "cache_hit_rate": True,
    }
    for name, hib in serving_metrics.items():
        if bs.get(name) is not None and cs.get(name) is not None:
            comparisons.append(_compare_value(
                f"serving:{name}", bs[name], cs[name], threshold,
                higher_is_better=hib,
            ))
    # leak-harness totals (runtime/leakcheck.py via DFTPU_LEAK_CHECK):
    # resources still live at query-end sweeps, folded into BENCH_DETAIL
    # meta by bench.py. A missing key means "harness off or zero leaks" —
    # both read as 0, so any nonzero current total flags as a regression
    # even against a baseline that predates the harness.
    bl = (baseline.get("meta") or {}).get("leaked_resources_total") or 0
    cl = (current.get("meta") or {}).get("leaked_resources_total") or 0
    if bl or cl:
        comparisons.append(_compare_value(
            "leaked_resources_total", bl, cl, threshold,
            higher_is_better=False,
        ))
    # micro_bench cases (data_plane_copy/view/shm, wire roundtrips, ...):
    # intersection of both documents' case sets, per-metric direction
    # from _MICRO_DIRECTIONS. A case either side marked "skipped" (e.g.
    # data_plane_wire_lz4 on an image without lz4) compares as skipped —
    # "not run" must never read as "regressed".
    bm, cm = _micro_cases(baseline), _micro_cases(current)
    for case in sorted(set(bm) & set(cm)):
        b, c = bm[case], cm[case]
        if not isinstance(b, dict) or not isinstance(c, dict):
            continue
        if b.get("skipped") or c.get("skipped"):
            comparisons.append({
                "name": f"micro:{case}",
                "baseline": b.get("skipped", "ran"),
                "current": c.get("skipped", "ran"),
                "higher_is_better": False,
                "status": "skipped",
            })
            continue
        for metric, hib in _MICRO_DIRECTIONS.items():
            if b.get(metric) is not None and c.get(metric) is not None:
                comparisons.append(_compare_value(
                    f"micro:{case}:{metric}", b[metric], c[metric],
                    threshold, higher_is_better=hib,
                ))
    return {
        "threshold": threshold,
        "comparisons": comparisons,
        "regressions": [c for c in comparisons
                        if c["status"] == "regression"],
        "improvements": [c for c in comparisons
                         if c["status"] == "improvement"],
        "compared": len([c for c in comparisons
                         if c["status"] != "skipped"]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0]
    )
    ap.add_argument("baseline", help="baseline BENCH_*.json")
    ap.add_argument("current", help="current BENCH_*.json")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="relative regression threshold (default 0.10)")
    ap.add_argument("--min-seconds", type=float, default=0.02,
                    help="ignore per-query walls under this (noise "
                         "floor, default 0.02s)")
    ap.add_argument("--queries", default=None,
                    help="comma list restricting per-query comparisons")
    ap.add_argument("--json", action="store_true",
                    help="emit the comparison document as JSON")
    args = ap.parse_args(argv)
    if args.threshold < 0:
        print("bench_compare: --threshold must be >= 0", file=sys.stderr)
        return 2

    queries = None
    if args.queries:
        queries = {q.strip() for q in args.queries.split(",") if q.strip()}
    result = compare(
        _load(args.baseline), _load(args.current),
        threshold=args.threshold, queries=queries,
        min_seconds=args.min_seconds,
    )
    if args.json:
        print(json.dumps(result, indent=2))
    else:
        for c in result["comparisons"]:
            if c["status"] == "skipped":
                continue
            arrow = {"regression": "WORSE", "improvement": "better",
                     "ok": "ok"}[c["status"]]
            print(f"{c['name']:<40} {c['baseline']:>12} -> "
                  f"{c['current']:>12}  "
                  f"{c.get('rel_change', 0) * 100:+7.1f}%  {arrow}")
        n = len(result["regressions"])
        print(f"{result['compared']} compared, {n} regression(s), "
              f"{len(result['improvements'])} improvement(s) at "
              f"threshold {args.threshold * 100:.0f}%")
    return 1 if result["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
