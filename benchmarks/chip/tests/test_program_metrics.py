"""The metric files that read the program's own report
(`runtime/tracing.py layer_report`), rehearsed on the CPU at SF0.01 the way
a traced run reads them: a `jax.profiler` session is the program's only
switch ("no SET"), the requests are the tiers' own calls, and every value is
checked against the report's rows. None of the numbers is a measurement."""

import contextlib
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
# (tier-1's tests/test_chip_bench_mesh.py pins both lists, and
# `masked_filters`', to `direct-q1` alone, and a `benchmark` PR may touch no
# test outside this directory: the cells PR 33 admitted read them by hand)
PROGRAM_METRICS = {
    "prepare_ms": ["direct-q1"],
    "fetch_transfers": ["direct-q1"],
}
# the coordinator tier's readers (PR 33), all of `coord4-q1` alone
COORDINATOR_METRICS = ("exchange_host_ms", "exchange_host_mb", "schedule_ms",
                       "admission_wait_ms")
REQUESTS = 3


def read(name: str, record: dict):
    return run.load_module("metrics", f"{name}.py").read(record)


def session(scale: float):
    suite = run.load_module("suites", "tpch", "suite.py")
    tables = suite.load(scale, 7, os.path.join(run.CACHE, "data"))
    ctx = SessionContext()
    for name, arrow in tables.items():
        ctx.register_arrow(name, arrow)
    return ctx, suite


@pytest.fixture(scope="module")
def ctx():
    ctx, suite = session(0.01)
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


@pytest.mark.parametrize("name", sorted(PROGRAM_METRICS)
                         + list(COORDINATOR_METRICS))
def test_a_program_without_the_report_reads_as_nothing(name, direct_window,
                                                       monkeypatch):
    """The parent commit has no `layer_report`: the reader returns None
    and does not raise, and the result line leaves the metric out."""
    record, _rows = direct_window
    monkeypatch.delattr(tracing, "layer_report")
    assert read(name, record) is None


@pytest.fixture(scope="module")
def coordinator_window(tmp_path_factory):
    """`coord4-q1`'s tier over SF0.05, the smallest scale at which the
    planner splits q1 into stages with an exchange between them (at SF0.01
    one task runs the whole plan)."""
    ctx, suite = session(0.05)
    config = run.read_json("configs", "tpch-sf1-coord4.json")
    tier = run.load_module("tiers", "coord.py").Tier(
        ctx, config["tier_args"], suite)
    sql = suite.sql("q1")
    try:
        record = traced_window(
            tmp_path_factory.mktemp("coord"), lambda: tier.run_traced(
                sql, lambda name: contextlib.nullcontext()))
    finally:
        tier.close()
    return record, tracing.layer_report()


def test_the_coordinator_tiers_metrics_read_the_report(coordinator_window):
    record, rows = coordinator_window
    # the tier's `bench.parse` is a `ctx.sql` of the benchmark's own: a
    # request of its own, with no coordinator in it, that every reader skips
    served = [r for r in rows if "schedule" in r["self_s"]]
    assert len(served) == REQUESTS and len(rows) == 2 * REQUESTS

    def ms(kinds):
        return statistics.median(
            sum(r["self_s"].get(k, 0.0) for k in kinds) * 1e3 for r in served)

    exchange = ("exchange", "transfer", "d2h", "regroup", "h2d")
    assert {"exchange", "transfer", "h2d"} <= set(served[0]["self_s"])
    assert 0 < read("exchange_host_ms", record) == pytest.approx(ms(exchange))
    assert 0 < read("schedule_ms", record) == pytest.approx(ms(
        ("query", "schedule", "stage", "task", "attempt", "dispatch",
         "codec", "rpc")))
    assert 0 < read("admission_wait_ms", record) == pytest.approx(
        ms(("queued",)))
    # the partial states q1's four tasks hand the final stage: 392 bytes
    assert read("exchange_host_mb", record) == pytest.approx(
        statistics.median(sum(r["counters"]["bytes"].get(k, 0)
                              for k in ("d2h", "h2d")) for r in served) / 1e6)
    assert 0 < read("exchange_host_mb", record) < 0.01
    # none of them is the stage programs' time
    assert read("schedule_ms", record) < statistics.median(
        r["total_s"]["worker_execute"] for r in served) * 1e3


@pytest.mark.parametrize("name", COORDINATOR_METRICS)
def test_a_request_through_no_coordinator_reads_as_nothing(name,
                                                           direct_window):
    record, _rows = direct_window
    assert read(name, record) is None


def test_benchmark_json_lists_the_cells_that_can_report_them():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    entries = {m["name"]: m for m in bench["per_layer"]}
    listed = dict(PROGRAM_METRICS,
                  **dict.fromkeys(COORDINATOR_METRICS, ["coord4-q1"]))
    for name, workloads in listed.items():
        module = run.load_module("metrics", f"{name}.py")
        assert module.MOVES == "query_p50_s"
        entry = entries[name]
        assert entry["workloads"] == workloads and set(workloads) <= cells
        assert (entry["unit"], entry["source"], entry["layer"]) == (
            module.UNIT, module.SOURCE, module.LAYER)
    # every tier reports its overflow retries (the coordinator tier's file
    # reads `QueryHandle.retry_count` since PR 33), so the entry needs no list
    assert "workloads" not in entries["overflow_retries"]
