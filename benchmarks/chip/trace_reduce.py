"""From a profiler trace to busy time, op durations and idle gaps, with
``jax.profiler.ProfileData`` and nothing else.

``load`` turns an ``.xplane.pb`` into plain data, ``[{"name": plane,
"lines": [{"name": line, "events": [[name, start_ns, duration_ns], ...]}]}]``
(the shape of the recorded fixture under tests/data/), and ``reduce`` works
on that alone. What it reads:

- device planes ``/device:<KIND>:<n>``, their line ``XLA Ops`` (every op the
  core runs, a ``while`` spanning its body's ops) or, where that is empty,
  ``XLA Modules``;
- the host's ``bench.*`` events, which run.py writes with
  ``jax.profiler.TraceAnnotation`` on the same clock: ``bench.query`` around
  each query, ``bench.parse`` / ``bench.execute`` / ``bench.fetch`` inside it.

The traced window runs from the first ``bench.query``'s start to the last
one's end. Busy is the union of the op intervals inside it. Idle time is
what is left of the window on the busiest device, booked to the ``bench.*``
span it falls in: to ``bench.query`` where it lies inside a query and outside
those three, to "between queries" where it lies outside every query.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(?!CUSTOM)[A-Za-z]+:\d+$")
COLLECTIVE = re.compile(r"^%?(all-to-all|all-gather|all-reduce|"
                        r"reduce-scatter|collective-permute)")
QUERY = "bench.query"


def find(trace_dir: str) -> str:
    """The one ``.xplane.pb`` a ``start_trace(trace_dir)`` session wrote."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb under {trace_dir}")
    return paths[0]


def load(path: str) -> list:
    from jax.profiler import ProfileData

    return [{"name": plane.name,
             "lines": [{"name": line.name,
                        "events": [[e.name, e.start_ns, e.duration_ns]
                                   for e in line.events]}
                       for line in plane.lines]}
            for plane in ProfileData.from_file(path).planes]


def union(intervals) -> list:
    """Sorted, disjoint [start, end] covering the same points."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def _device_ops(plane: dict) -> list:
    lines = {line["name"]: line["events"] for line in plane["lines"]}
    return lines.get("XLA Ops") or lines.get("XLA Modules") or []


@functools.lru_cache(maxsize=None)  # a trace repeats a few hundred names
def _short(name: str) -> str:
    """An op under the name the trace prints, cut to its left-hand side and
    opcode: ``%fusion.3 = f32[8]{0} fusion(...), kind=kLoop`` ->
    ``%fusion.3 fusion kLoop``."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:120]
    opcode = re.search(r"([a-z][a-z0-9-]*)\(", rhs)
    kind = re.search(r"kind=(\w+)", rhs)
    return " ".join(x for x in (lhs, opcode and opcode.group(1),
                                kind and kind.group(1)) if x)[:120]


def reduce(planes: list) -> dict:
    host = [e for plane in planes if plane["name"].startswith("/host:")
            for line in plane["lines"] for e in line["events"]
            if e[0].startswith("bench.")]
    queries = [e for e in host if e[0] == QUERY]
    if not queries:
        raise ValueError("trace holds no bench.query span")
    t0 = min(e[1] for e in queries)
    t1 = max(e[1] + e[2] for e in queries)
    spans = sorted((e for e in host if e[0] != QUERY), key=lambda e: e[1])

    def clip(events) -> list:
        """(name, start, end) of the events that touch the window, cut to
        it."""
        return [(name, max(s, t0), min(s + d, t1)) for name, s, d in events
                if s < t1 and s + d > t0]

    devices = {}
    for plane in planes:
        ops = clip(_device_ops(plane)) if DEVICE_PLANE.match(
            plane["name"]) else []
        if ops:
            devices[plane["name"]] = ops
    if not devices:  # run.py refuses such a run; a CPU rehearsal gets here
        return {"queries": len(queries), "window_s": (t1 - t0) / 1e9,
                "devices": [], "busy_s": 0.0, "busiest_busy_s": 0.0,
                "ops": [], "gaps": [], "collective_ops": 0,
                "collective_s": 0.0}
    busy = {name: union((s, e) for _, s, e in ops)
            for name, ops in devices.items()}
    busy_s = {name: sum(e - s for s, e in intervals) / 1e9
              for name, intervals in busy.items()}
    busiest = max(busy_s, key=busy_s.get)

    by_op: dict = {}
    for name, s, e in devices[busiest]:
        key = _short(name)
        by_op[key] = by_op.get(key, 0.0) + (e - s) / 1e9

    intervals = busy[busiest]
    ends = [e for _, e in intervals]

    def idle(start: float, end: float) -> float:
        """Nanoseconds of [start, end] in which no op ran on the busiest
        device."""
        covered = 0.0
        for s, e in intervals[bisect.bisect_right(ends, start):]:
            if s >= end:
                break
            covered += min(e, end) - max(s, start)
        return end - start - covered

    gaps: dict = {}
    for name, s, d in spans:
        gaps[name] = gaps.get(name, 0.0) + idle(s, s + d) / 1e9
    in_queries = sum(idle(s, s + d) for _, s, d in queries) / 1e9
    gaps[QUERY] = in_queries - sum(gaps.values())
    gaps["between queries"] = idle(t0, t1) / 1e9 - in_queries
    gaps = {name: seconds for name, seconds in gaps.items()
            if seconds > 1e-9}  # a nanosecond: below it, rounding

    collectives = [e - s for name, s, e in devices[sorted(devices)[0]]
                   if COLLECTIVE.match(name)]

    def ranked(d: dict) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])]

    return {
        "queries": len(queries),
        "window_s": (t1 - t0) / 1e9,
        "devices": sorted(devices),
        "device_busy_s": busy_s,
        "busy_s": sum(busy_s.values()) / len(busy_s),
        "busiest": busiest,
        "busiest_busy_s": busy_s[busiest],
        "ops": ranked(by_op),
        "gaps": ranked(gaps),
        "collective_ops": len(collectives),
        "collective_s": sum(collectives) / 1e9,
    }
