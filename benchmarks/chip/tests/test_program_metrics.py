"""The metric files that read the program's own report
(`runtime/tracing.py layer_report`), rehearsed on the CPU at SF0.01 the way
a traced run reads them: a `jax.profiler` session is the program's only
switch ("no SET"), the requests are the tiers' own calls, and every value is
checked against the report's rows. None of the numbers is a measurement."""

import json
import os
import statistics
import time

import jax
import pytest

import run

from datafusion_distributed_tpu.runtime import tracing
from datafusion_distributed_tpu.sql.context import SessionContext

# metric file -> the cells its BENCHMARK.json entry lists
PROGRAM_METRICS = {
    "prepare_ms": ["direct-q1"],
    "fetch_transfers": ["direct-q1"],
}
REQUESTS = 3


def read(name: str, record: dict):
    return run.load_module("metrics", f"{name}.py").read(record)


@pytest.fixture(scope="module")
def ctx():
    suite = run.load_module("suites", "tpch", "suite.py")
    tables = suite.load(0.01, 7, os.path.join(run.CACHE, "data"))
    ctx = SessionContext()
    for name, arrow in tables.items():
        ctx.register_arrow(name, arrow)
    return ctx, suite.sql("q1")


def traced_window(tmp_path, request_once) -> dict:
    """`request_once` warm, then REQUESTS times under a profiler session.
    -> the part of run.py's record that the readers look at."""
    request_once()
    tracing.DEFAULT_TRACE_STORE.clear()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        queries = []
        for _ in range(REQUESTS):
            start = time.perf_counter()
            request_once()
            queries.append({"start": start, "end": time.perf_counter()})
    finally:
        jax.profiler.stop_trace()
    return {"queries": queries}


@pytest.fixture(scope="module")
def direct_window(ctx, tmp_path_factory):
    ctx, sql = ctx

    def request_once():  # tiers/direct.py run_traced, spans left out
        jax.block_until_ready(ctx.sql(sql).collect_table()).to_pandas()

    record = traced_window(tmp_path_factory.mktemp("direct"), request_once)
    return record, tracing.layer_report()


def test_the_direct_tiers_metrics_read_the_report(direct_window):
    record, rows = direct_window
    assert len(rows) == REQUESTS
    assert 0 < read("prepare_ms", record) == pytest.approx(
        statistics.median(r["total_s"]["prepare"] * 1e3 for r in rows))
    assert read("fetch_transfers", record) == statistics.median(
        r["counters"]["transfers"] for r in rows) >= 11


def test_requests_from_before_the_window_are_left_out(direct_window):
    record, _rows = direct_window
    later = {"queries": [{"start": time.perf_counter()}]}
    assert read("fetch_transfers", later) is None
    assert read("fetch_transfers", {"queries": []}) is None
    assert read("fetch_transfers", record) is not None


@pytest.mark.parametrize("name", sorted(PROGRAM_METRICS))
def test_a_program_without_the_report_reads_as_nothing(name, direct_window,
                                                       monkeypatch):
    """The parent commit has no `layer_report`: the reader returns None
    and does not raise, and the result line leaves the metric out."""
    record, _rows = direct_window
    monkeypatch.delattr(tracing, "layer_report")
    assert read(name, record) is None


def test_benchmark_json_lists_the_cells_that_can_report_them():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, workloads in PROGRAM_METRICS.items():
        module = run.load_module("metrics", f"{name}.py")
        assert module.MOVES == "query_p50_s"
        entry = entries[name]
        assert entry["workloads"] == workloads and set(workloads) <= cells
        assert (entry["unit"], entry["source"], entry["layer"]) == (
            module.UNIT, module.SOURCE, module.LAYER)
    # the accepted metric keeps its entry as it was (no list): giving it
    # one is an edit to an accepted entry, a `benchmark` PR's, and goes
    # with the PR that admits `coord4-q1`, whose tier reports no count
    assert "workloads" not in entries["overflow_retries"]
