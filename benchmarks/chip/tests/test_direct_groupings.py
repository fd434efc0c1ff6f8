"""`metrics/direct_groupings.py` rehearsed on the CPU at SF0.01 the way a
traced run reads it, in the three cells that list it; `direct-q6` is the
bypass cell (no GROUP BY: the count is 0). A
`jax.profiler` session is the program's only switch, the requests are the
tiers' own traced calls, and the value is checked against
`tracing.layer_report`'s rows. Counts only: none of the numbers is a
measurement."""

import contextlib
import json
import os
import time

import jax
import pytest

import run

from datafusion_distributed_tpu.runtime import tracing
from datafusion_distributed_tpu.sql.context import SessionContext

# cell -> the aggregates of one request's programs that group by dictionary
# codes: q1's one (partial and final on the mesh), none without a GROUP BY
CELLS = {"direct-q1": 1, "mesh4-q1": 2, "direct-q6": 0}
REQUESTS = 3


def read(record: dict):
    return run.load_module("metrics", "direct_groupings.py").read(record)


@pytest.fixture(scope="module")
def ctx():
    suite = run.load_module("suites", "tpch", "suite.py")
    tables = suite.load(0.01, 7, os.path.join(run.CACHE, "data"))
    ctx = SessionContext()
    for name, arrow in tables.items():
        ctx.register_arrow(name, arrow)
    return ctx, suite


def traced_window(cell: str, ctx, suite, trace_dir) -> dict:
    """One warm query of the cell, then REQUESTS under a profiler session.
    -> the part of run.py's record that the reader looks at."""
    workload = run.read_json("workloads", f"{cell}.json")
    config = run.read_json("configs", f"{workload['config']}.json")
    (query,) = run.read_json("traffic",
                             f"{workload['traffic']}.json")["queries"]
    tier = run.load_module("tiers", f"{config['tier']}.py").Tier(
        ctx, config["tier_args"], suite)
    sql = suite.sql(query)
    tier.run(sql)
    tracing.DEFAULT_TRACE_STORE.clear()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        queries = []
        for _ in range(REQUESTS):
            start = time.perf_counter()
            tier.run_traced(sql, lambda name: contextlib.nullcontext())
            queries.append({"start": start, "end": time.perf_counter()})
    finally:
        jax.profiler.stop_trace()
        tier.close()
    return {"queries": queries}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_reader_counts_the_direct_groupings_of_a_request(cell, ctx,
                                                             tmp_path,
                                                             monkeypatch):
    ctx, suite = ctx
    record = traced_window(cell, ctx, suite, tmp_path)
    rows = tracing.layer_report()
    assert len(rows) == REQUESTS
    kind = "mesh.execute" if cell.startswith("mesh") else "execute"
    for row in rows:
        assert kind in row["self_s"]
        assert row["counters"]["direct_groupings"] == CELLS[cell]
        assert row["counters"]["new_traces"] == 0  # the cached executable
    assert read(record) == CELLS[cell]
    # requests from before the window, or no request at all: nothing
    assert read({"queries": [{"start": time.perf_counter()}]}) is None
    assert read({"queries": []}) is None
    # a program from before the counter (the parent commit): its rows hold
    # no such count, and the line leaves the metric out
    report = tracing.layer_report

    def before_the_counter():
        rows = report()
        for row in rows:
            del row["counters"]["direct_groupings"]
        return rows

    monkeypatch.setattr(tracing, "layer_report", before_the_counter)
    assert read(record) is None
    monkeypatch.delattr(tracing, "layer_report")
    assert read(record) is None


def test_benchmark_json_lists_the_metric_in_its_three_cells():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    module = run.load_module("metrics", "direct_groupings.py")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "direct_groupings"]
    # `direct-q6` came in with PR 33, appended to the list as it stood
    cells = [w["name"] for w in bench["workloads"]]
    assert entry == {
        "name": "direct_groupings", "unit": module.UNIT, "better": "higher",
        "source": module.SOURCE, "layer": module.LAYER,
        "moves": module.MOVES,
        "workloads": [c for c in cells if c in CELLS]}
    assert set(CELLS) <= set(cells)
    workload = run.read_json("workloads", "direct-q6.json")
    assert (workload["config"], workload["traffic"]) == (
        "tpch-sf1-direct", "q6-closed1")
    assert len(workload["why"]) <= 200
    assert run.read_json("traffic", "q6-closed1.json") == {
        "loop": "closed", "queries": ["q6"], "clients": 1,
        "traced_queries": 3}
    # the sidecar lets `hbm_roofline_share` read in that cell
    suite = run.load_module("suites", "tpch", "suite.py")

    class Rows:
        num_rows = 10

    assert suite.least_bytes("q6", {"lineitem": Rows}) == 10 * 4 * 4
