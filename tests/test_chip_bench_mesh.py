"""The four-chip cell's path, rehearsed on four of the eight virtual CPU
devices at SF0.01: `benchmarks/chip/tiers/mesh.py` (the call `mesh4-q1`
times) against the benchmark's plain reference (`suites/tpch/oracle.py`,
pandas) on seeded data, against the direct tier, and the mesh tier's spans
and counters as `tracing.layer_report()` and the cell's metric files read
them. Answers and counts only: none of the numbers is a measurement."""

import contextlib
import importlib.util
import json
import os
import statistics
import time

import pytest

from datafusion_distributed_tpu import spans
from datafusion_distributed_tpu.runtime import tracing
from datafusion_distributed_tpu.sql.context import SessionContext

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483649  # more than 32 signed bits hold, as the driver's are
REQUESTS = 3
# metric file -> what its BENCHMARK.json entry says beside `mesh4-q1`
MESH_METRICS = {
    "stack_inputs_ms": ("ms", "program_span", "mesh input placement"),
    "stack_input_mb": ("MB", "program_counter", "mesh input placement"),
    "mesh_masked_filters": ("count", "program_counter", "operators"),
}


def _load(name: str, *parts: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmarks", "chip", *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load("chipbench_run", "run.py")


def read(name: str, record: dict):
    return run.load_module("metrics", f"{name}.py").read(record)


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    """The cell's set-up at SF0.01, as `run.run_cell` makes it: the suite's
    seeded tables registered, the reference's answer, both tiers."""
    workload = run.read_json("workloads", "mesh4-q1.json")
    config = run.read_json("configs", f"{workload['config']}.json")
    assert (config["tier"], config["chips"]) == ("mesh", 4)
    suite = run.load_module("suites", config["suite"], "suite.py")
    tables = suite.load(0.01, SEED, str(tmp_path_factory.mktemp("data")))
    ctx = SessionContext()
    for name, arrow in tables.items():
        ctx.register_arrow(name, arrow)
    mesh = run.load_module("tiers", "mesh.py").Tier(
        ctx, config["tier_args"], suite)
    direct = run.load_module("tiers", "direct.py").Tier(ctx, {}, suite)
    assert mesh.mesh.devices.size == 4
    return {"suite": suite, "sql": suite.sql("q1"), "mesh": mesh,
            "direct": direct, "expected": suite.expected("q1", tables)}


@contextlib.contextmanager
def profiler_session(trace_dir):
    """A recording `jax.profiler` session: the program's only switch."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def run_traced(tier, sql: str):
    """`run.run_cell`'s traced call, the benchmark's own spans left out."""
    return tier.run_traced(sql, lambda name: contextlib.nullcontext())


@pytest.fixture(scope="module")
def window(cell, tmp_path_factory):
    """One warm query, then REQUESTS under a profiler session. -> the part
    of run.py's record the readers look at, the report's rows, the result
    frames, every span of the window."""
    cell["mesh"].run(cell["sql"])
    tracing.DEFAULT_TRACE_STORE.clear()
    queries, frames = [], []
    with profiler_session(tmp_path_factory.mktemp("trace")):
        for _ in range(REQUESTS):
            start = time.perf_counter()
            frame, retries = run_traced(cell["mesh"], cell["sql"])
            queries.append({"start": start, "end": time.perf_counter(),
                            "retries": retries})
            frames.append(frame)
    spans = [span
             for trace in tracing.DEFAULT_TRACE_STORE.finished_traces()
             for span in trace.span_list()]
    return {"record": {"queries": queries}, "rows": tracing.layer_report(),
            "frames": frames, "spans": spans}


@pytest.mark.parametrize("traced", [False, True], ids=["plain", "traced"])
def test_mesh_tier_q1_agrees_with_the_plain_reference(cell, window, traced):
    if traced:
        frames = window["frames"]
    else:
        frame, retries = cell["mesh"].run(cell["sql"])
        assert retries == 0
        frames = [frame]
    for frame in frames:
        assert len(frame) == 4
        cell["suite"].compare(frame, cell["expected"])


def test_mesh_q1_equals_the_direct_tiers(cell):
    """The configuration's tolerance (`guarantees.floats`) between the two
    tiers as well: the shards' partial sums add up in another order."""
    mesh_frame, _ = cell["mesh"].run(cell["sql"])
    direct_frame, _ = cell["direct"].run(cell["sql"])
    assert list(mesh_frame.columns) == list(direct_frame.columns)
    cell["suite"].compare(mesh_frame, direct_frame)
    cell["suite"].compare(direct_frame, cell["expected"])


def test_traced_mesh_q1_rows_carry_the_tiers_spans_and_counters(window):
    record, rows, spans = window["record"], window["rows"], window["spans"]
    assert len(rows) == REQUESTS
    assert [q["retries"] for q in record["queries"]] == [0] * REQUESTS
    for row in rows:
        assert row["total_s"]["mesh.stack_inputs"] > 0
        assert row["total_s"]["mesh.execute"] > 0
        # the warm query placed the plan's inputs (PR 36): a declared 0
        assert row["counters"]["bytes"]["mesh.stack_inputs"] == 0
        assert row["counters"]["masked_filters"] == 1
        assert row["counters"]["new_traces"] == 0
        assert row["counters"]["transfers"] > 0  # the fetch joined its row
    # the counter sits on the `mesh.execute` span, on a program-cache hit
    kinds = [span.kind for span in spans]
    assert kinds.count("mesh.execute") == REQUESTS
    assert kinds.count("mesh.stack_inputs") == REQUESTS
    for span in spans:
        if span.kind == "mesh.execute":
            assert span.attrs["cache"] == "hit"
            assert span.attrs["masked_filters"] == 1
        else:
            assert "masked_filters" not in span.attrs
        if span.kind == "mesh.stack_inputs":
            assert (span.attrs["tasks"], span.attrs["bytes"],
                    span.attrs["reused"]) == (4, 0, 1)


@pytest.mark.parametrize("name", MESH_METRICS)
def test_the_mesh_tiers_metric_files_read_the_report(name, window):
    record, rows = window["record"], window["rows"]
    want = {
        "stack_inputs_ms": lambda r: r["total_s"]["mesh.stack_inputs"] * 1e3,
        "stack_input_mb":
            lambda r: r["counters"]["bytes"]["mesh.stack_inputs"] / 1e6,
        "mesh_masked_filters": lambda r: r["counters"]["masked_filters"],
    }[name]
    value = read(name, record)
    assert value == pytest.approx(statistics.median(map(want, rows)))
    # nothing is placed in a warm window: 0.0, a number and not None
    assert value == 0.0 if name == "stack_input_mb" else value > 0
    # requests from before the window, or no request at all: nothing
    assert read(name, {"queries": [{"start": time.perf_counter()}]}) is None
    assert read(name, {"queries": []}) is None


def test_a_plans_first_request_places_bytes_and_later_ones_declare_zero(cell):
    """What `mesh.stack_inputs` says since PR 36, by both of its readers: a
    plan's first request puts its inputs on the chips (`bytes` > 0, nothing
    reused), every later one reuses them (0 bytes, a small positive time).
    A fresh `SessionContext` plans anew, so its first request is a first."""
    ctx = SessionContext()
    for name in cell["mesh"].ctx.catalog.tables:
        ctx.register_table(name, cell["mesh"].ctx.catalog.tables[name])
    tier = run.load_module("tiers", "mesh.py").Tier(
        ctx, {"num_tasks": 4}, cell["suite"])
    ctx.config.distributed_options["tracing"] = "on"
    readings = []
    for _ in range(2):
        start = time.perf_counter()
        frame, _ = run_traced(tier, cell["sql"])
        cell["suite"].compare(frame, cell["expected"])
        record = {"queries": [{"start": start, "end": time.perf_counter()}]}
        readings.append((read("stack_input_mb", record),
                         read("stack_inputs_ms", record)))
    (first_mb, first_ms), (later_mb, later_ms) = readings
    assert first_mb > 0 and later_mb == 0.0
    assert first_ms > later_ms > 0


def test_layer_rows_report_a_declared_zero_of_bytes():
    """`_layer_rows` keeps a span's `bytes` of 0 (it dropped them before
    PR 36, and `stack_input_mb` would have read None), and still leaves out
    a span that declares none."""
    store = spans.TraceStore()
    with spans.trace_call("query", {"tracing": "on"}, store=store) as call:
        with call.tracer.span("mesh.stack_inputs",
                              "mesh.stack_inputs") as span:
            span.set(bytes=0, tasks=4, reused=1)
        with call.tracer.span("mesh.execute", "mesh.execute"):
            pass
    (row,) = tracing.layer_report(store)
    assert row["counters"]["bytes"] == {"mesh.stack_inputs": 0}


@pytest.mark.parametrize("name", ["direct_groupings", "dense_aggregates"])
def test_an_operator_counter_reads_the_mesh_and_the_direct_tier(name, cell,
                                                                window):
    """`metrics/direct_groupings.py` (PR 30) and `metrics/dense_aggregates.py`
    (PR 32) read `execute` and `mesh.execute` alike: q1's partial and final
    aggregates on the mesh, its one aggregate on the direct tier, all
    grouped by dictionary codes, all reduced densely over a domain of 6,
    and counted on a program-cache hit. (Reads the window's store: before
    the tests below, which clear it.)"""
    assert [r["counters"][name] for r in window["rows"]] == [2] * REQUESTS
    assert read(name, window["record"]) == 2
    start = time.perf_counter()
    cell["direct"].ctx.config.distributed_options["tracing"] = "on"
    try:
        run_traced(cell["direct"], cell["sql"])
    finally:
        cell["direct"].ctx.config.distributed_options.pop("tracing", None)
    assert read(name, {"queries": [{"start": start}]}) == 1
    assert read(name, {"queries": []}) is None


def test_the_fetch_of_a_replicated_result_is_one_round_trip(cell, window):
    """`metrics/fetch_round_trips.py` (PR 35) on the mesh and the direct
    tier: the replicated result's eighteen buffers (`fetch_transfers`'
    count: the row count, ten columns and seven masks; twenty until PR 37,
    while q1's two group keys carried a mask of their own) come from one
    device's copies in one wait, through
    `table_to_arrow` here and `Table.to_pandas` there. (Reads the window's
    store: before the tests below, which clear it.)"""
    for row in window["rows"]:
        assert row["counters"]["round_trips"] == 1
        assert row["counters"]["transfers"] == 18
    assert read("fetch_round_trips", window["record"]) == 1
    assert read("fetch_transfers", window["record"]) == 18
    start = time.perf_counter()
    cell["direct"].ctx.config.distributed_options["tracing"] = "on"
    try:
        run_traced(cell["direct"], cell["sql"])
    finally:
        cell["direct"].ctx.config.distributed_options.pop("tracing", None)
    record = {"queries": [{"start": start}]}
    assert read("fetch_round_trips", record) == 1
    assert read("fetch_transfers", record) == 18
    assert read("fetch_round_trips", {"queries": []}) is None


@pytest.mark.parametrize("name", MESH_METRICS)
def test_the_mesh_readers_find_nothing_on_another_tier(name, cell, window,
                                                       monkeypatch):
    """A request with no `mesh.*` span (the direct tier's), and a program
    with no report at all, read as None: the line leaves the metric out."""
    tracing.DEFAULT_TRACE_STORE.clear()
    start = time.perf_counter()
    cell["direct"].ctx.config.distributed_options["tracing"] = "on"
    try:
        run_traced(cell["direct"], cell["sql"])
    finally:
        cell["direct"].ctx.config.distributed_options.pop("tracing", None)
    record = {"queries": [{"start": start}]}
    assert len(tracing.layer_report()) == 1
    assert read(name, record) is None
    assert read("masked_filters", record) == 1  # the direct tier's reader
    monkeypatch.delattr(tracing, "layer_report")
    assert read(name, window["record"]) is None


def test_benchmark_json_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [w for w in bench["workloads"] if w["name"] == "mesh4-q1"]
    workload = run.read_json("workloads", "mesh4-q1.json")
    config = run.read_json("configs", "tpch-sf1-mesh4.json")
    assert entry == {"name": "mesh4-q1", "config": "tpch-sf1-mesh4",
                     "traffic": "q1-closed1", "chips": 4,
                     "why": workload["why"]}
    (listed,) = [c for c in bench["configs"] if c["name"] == entry["config"]]
    assert (listed["source"], listed["reduced"], listed["file"]) == (
        config["source"], [], "benchmarks/chip/configs/tpch-sf1-mesh4.json")
    # two deployments from one public benchmark: sources that differ
    assert len({c["source"] for c in bench["configs"]}) == len(
        bench["configs"])
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, source, layer) in MESH_METRICS.items():
        module = run.load_module("metrics", f"{name}.py")
        assert (module.UNIT, module.SOURCE, module.LAYER, module.MOVES) == (
            unit, source, layer, "query_p50_s")
        assert metrics[name] == {
            "name": name, "unit": unit, "better": metrics[name]["better"],
            "source": source, "layer": layer, "moves": "query_p50_s",
            "workloads": ["mesh4-q1"]}
    # the reducer's pattern misses the TPU's `%all_to_all.N` (PERF.md,
    # PR 29): the reader's file stands, its entry waits for a repair
    assert "collective_ms" not in metrics
    # the accepted entries that list `direct-q1` still do; a `benchmark`
    # PR may append cells to them (PERF.md section 7)
    for name in ("prepare_ms", "fetch_transfers", "masked_filters"):
        assert "direct-q1" in metrics[name]["workloads"]
    # PR 35's reader of the fetch's waits lists every cell there was when
    # it came, in their order; a `benchmark` PR may append later ones
    module = run.load_module("metrics", "fetch_round_trips.py")
    assert metrics["fetch_round_trips"] == {
        "name": "fetch_round_trips", "unit": module.UNIT, "better": "lower",
        "source": module.SOURCE, "layer": metrics["fetch_ms"]["layer"],
        "moves": "query_p50_s",
        "workloads": [w["name"] for w in bench["workloads"]][:5]}
    # every metric without a list is reported in the new cell too
    assert {m["name"] for m in run.cell_metrics("mesh4-q1", True)} >= {
        "execute_ms", "fetch_ms", "overflow_retries", "hbm_roofline_share",
        "fetch_round_trips", *MESH_METRICS}
