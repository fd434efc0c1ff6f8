"""One run of one benchmark cell on the machine this is started on.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process, no children. ``main`` refuses to start without a TPU, or with
fewer chips than the cell's configuration names, before any data is made.
Everything that belongs to one cell, configuration, tier, suite, traffic
loop or metric is a file of its own beside this one, found by name:

    workloads/<cell>.json      config, traffic mix, why, who
    configs/<config>.json      suite, scale, tier, its arguments, chips
    traffic/<mix>.json         the loop's name and its parameters
    traffic/<loop>.py          run(call, mix, seed, seconds, limit) -> records
    tiers/<tier>.py            Tier(ctx, args, suite): run, run_traced, close
    suites/<suite>/suite.py    load, sql, expected, measure, LIMITS, frame,
                               least_bytes
    metrics/<name>.py          read(record) -> number, or None
    peaks.json                 device_kind -> published peaks
    trace_reduce.py            .xplane.pb -> busy time, ops, idle gaps

Which metrics a cell reports is read from BENCHMARK.json: its ``end_to_end``
metrics with ``--trace 0``, its ``per_layer`` metrics with ``--trace 1``.
The last line of stdout is the result; the lines before it are for audit.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time

# set-up is counted from here: what lies before it is the interpreter's own
# start and the few standard-library imports above; jax and the program are
# imported inside the functions below
_PROCESS_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE = os.path.join(HERE, ".cache")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def read_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts: str):
    path = os.path.join(HERE, *parts)
    name = "chipbench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def emit(**line) -> None:
    print(json.dumps(line), flush=True)


def cell_metrics(cell: str, trace: bool) -> list:
    """The cell's entries of BENCHMARK.json: every metric of the run's kind
    that has no ``workloads`` key or lists the cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = json.load(f)["per_layer" if trace else "end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


def run_cell(cell: str, seed: int, seconds: float, trace: bool,
             scale=None) -> dict:
    """Set up the cell, measure one window, compare, reduce. -> the result
    line as a dict. ``scale`` overrides the configuration's (the CPU
    rehearsals under tests/ pass a tiny one); nothing here looks at the
    device's platform."""
    import jax

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from datafusion_distributed_tpu import hostenv
    from datafusion_distributed_tpu.sql.context import SessionContext

    clock = time.perf_counter
    workload = read_json("workloads", f"{cell}.json")
    config = read_json("configs", f"{workload['config']}.json")
    mix = read_json("traffic", f"{workload['traffic']}.json")
    suite = load_module("suites", config["suite"], "suite.py")
    tier_module = load_module("tiers", f"{config['tier']}.py")
    loop = load_module("traffic", f"{mix['loop']}.py")
    scale = config["scale"] if scale is None else scale

    # every program goes to the persistent cache, the small ones too, so
    # that only a checkout's first run of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compile_cache_dir = hostenv.compile_cache_dir()
    devices = jax.devices()[:config["chips"]]
    emit(cell=cell, seed=seed, seconds=seconds, trace=int(trace), scale=scale,
         platform=devices[0].platform, kind=devices[0].device_kind,
         count=len(jax.devices()), chips=config["chips"],
         compile_cache_dir=compile_cache_dir)

    # -- set-up -----------------------------------------------------------
    setup = {}
    t = clock()
    tables = suite.load(scale, seed, os.path.join(CACHE, "data"))
    setup["generate_s"] = clock() - t
    t = clock()
    ctx = SessionContext()
    for name, arrow in tables.items():
        ctx.register_arrow(name, arrow)
    setup["register_s"] = clock() - t
    texts = {q: suite.sql(q) for q in mix["queries"]}
    least_bytes = {q: suite.least_bytes(q, tables) for q in texts}

    def call(query: str) -> dict:
        if not trace:
            frame, retries = tier.run(texts[query])
            return {"frame": frame, "retries": retries}
        spans: dict = {}

        @contextlib.contextmanager
        def span(name: str):
            start = clock()
            with jax.profiler.TraceAnnotation(name):
                yield
            spans[name] = spans.get(name, 0.0) + clock() - start

        with jax.profiler.TraceAnnotation("bench.query"):
            frame, retries = tier.run_traced(texts[query], span)
        return {"frame": frame, "retries": retries, "spans": spans}

    # the collector while the window is open: the youngest generation's
    # collections counted and summed, every older one [generation, start,
    # seconds]
    young = [0, 0.0]
    collections: list = []
    collecting = [0.0]

    def on_collection(phase: str, info: dict) -> None:
        if phase == "start":
            collecting[0] = clock()
        elif info["generation"] == 0:
            young[0] += 1
            young[1] += clock() - collecting[0]
        else:
            collections.append([info["generation"], collecting[0],
                                clock() - collecting[0]])

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **_: compiles.append(event)
        if event == COMPILE_EVENT else None)
    trace_dir = os.path.join(CACHE, "trace", f"{cell}-seed{seed}")
    tier = tier_module.Tier(ctx, config["tier_args"], suite)
    try:
        t = clock()
        for query in texts:
            tier.run(texts[query])  # compiles, or loads from the cache
            call(query)             # warm, by the path the window takes
        setup["warmup_s"] = clock() - t
        compiled_in_setup = len(compiles)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # our spans only: less host load
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        gc.callbacks.append(on_collection)
        opened = clock()
        setup["setup_s"] = opened - _PROCESS_START
        records = loop.run(call, mix, seed, seconds,
                           limit=mix.get("traced_queries", 3) if trace
                           else None)
        closed = clock()
        compiles_in_window = len(compiles) - compiled_in_setup
        if trace:
            jax.profiler.stop_trace()
    finally:
        if on_collection in gc.callbacks:
            gc.callbacks.remove(on_collection)
        tier.close()

    # -- after the window: compare, count, reduce ---------------------------
    stats = [d.memory_stats() or {} for d in devices]
    t = clock()
    expected = {q: suite.expected(q, tables) for q in texts}
    reference_s = clock() - t
    # every result against the reference's; ``compared`` keeps the worst
    # reading of the window under each of the suite's names, beside its limit
    compared = {name: {"value": 0, "limit": limit}
                for name, limit in suite.LIMITS.items()}
    compared["results_missing"] = {"value": 0, "limit": 0}
    for r in records:
        result = r.pop("result")
        r["ok"] = False
        if result is None:  # it raised: the loop printed the traceback
            compared["results_missing"]["value"] += 1
            continue
        r["retries"], r["spans"] = result["retries"], result.get("spans")
        numbers = suite.measure(result["frame"], expected[r["query"]])
        over = {name: value for name, value in numbers.items()
                if value > suite.LIMITS[name]}
        for name, value in numbers.items():
            compared[name]["value"] = max(compared[name]["value"], value)
        r["ok"] = not over
        if over:
            emit(mismatch=r["query"], over=over)
    kind = devices[0].device_kind
    reduced = None
    if trace:
        reducer = load_module("trace_reduce.py")
        reduced = reducer.reduce(reducer.load(reducer.find(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    record = {
        "setup": setup,
        "window_s": (records[-1]["end"] if records else closed) - opened,
        "queries": records,
        "compiles_in_window": compiles_in_window,
        "peak_bytes": [s.get("peak_bytes_in_use") for s in stats],
        "least_bytes": least_bytes,
        "peaks": read_json("peaks.json").get(kind),
        "trace": reduced,
    }
    metrics = {}
    for entry in cell_metrics(cell, trace):
        value = load_module("metrics", f"{entry['name']}.py").read(record)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    done = [r for r in records if r["ok"]]
    emit(setup=setup, reference_s=reference_s, trace=int(trace),
         window_s=record["window_s"], requested_s=seconds,
         samples=len(records), agreed=len(done),
         compiled_in_setup=compiled_in_setup,
         compiles_in_window=compiles_in_window,
         rows={name: t.num_rows for name, t in tables.items()},
         # every query's start since the window opened and its wall, and
         # every collection of the window, as measured: walls.py reads them
         starts_s=[r["start"] - opened for r in records],
         walls_s=[r["end"] - r["start"] for r in records],
         young_collections=young[0], young_collections_s=young[1],
         collections=[[g, start - opened, length]
                      for g, start, length in collections])
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(
                  (p for p in record["peak_bytes"] if p), default=0)}
    result = {"correct": bool(records) and len(done) == len(records),
              "attempted": len(records),
              "failed": len(records) - len(done),
              "metrics": metrics, "device": device}
    if reduced is not None:
        # the program's own report of the traced requests, whole: each span
        # kind's median self time, whatever a metric file reads of it
        rows = load_module("layer_rows.py").rows(record)
        emit(traced_queries=reduced["queries"], window_s=reduced["window_s"],
             device_busy_s=reduced.get("device_busy_s", {}),
             program_self_ms={
                 kind: statistics.median(
                     row["self_s"][kind] * 1e3 for row in rows
                     if kind in row["self_s"])
                 for kind in sorted({k for row in rows for k in row["self_s"]})})
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["ops"][:10],
                               "idle_gaps": reduced["gaps"][:10]}
    result["compared"] = compared  # the line's last key
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    workload = read_json("workloads", f"{args.workload}.json")
    chips = read_json("configs", f"{workload['config']}.json")["chips"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"run.py: {args.workload} needs {chips} TPU chip(s), found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    if devices[0].device_kind not in read_json("peaks.json"):
        print(f"run.py: no peaks for device kind "
              f"{devices[0].device_kind!r} in peaks.json", file=sys.stderr)
        return 1
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    if args.trace and not result["device"]["busy_s"]:
        print("run.py: no operation ran on the device in the traced window",
              file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    for name, number in result["compared"].items():
        print(f"compared {name} {number['value']} limit {number['limit']}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
