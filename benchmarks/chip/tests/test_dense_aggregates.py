"""`metrics/dense_aggregates.py` rehearsed on the CPU at SF0.01 the way a
traced run reads it, in every cell whose files stand: q1's one aggregate
over a domain of 12 (partial and final on the mesh) and q6's global
aggregate reduce by dense masked passes, not by scatters (PERF.md, PR 32).
The window, the tables and the requests are `test_direct_groupings.py`'s.
Counts only: none of the numbers is a measurement."""

import json
import os
import time

import pytest

import run
from test_direct_groupings import ctx, traced_window  # noqa: F401

from datafusion_distributed_tpu.runtime import tracing

# cell -> the aggregates of one request's programs that reduce densely
CELLS = {"direct-q1": 1, "mesh4-q1": 2, "direct-q6": 1}


def read(record: dict):
    return run.load_module("metrics", "dense_aggregates.py").read(record)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_reader_counts_the_dense_aggregates_of_a_request(
        cell, ctx, tmp_path, monkeypatch):  # noqa: F811
    ctx, suite = ctx
    record = traced_window(cell, ctx, suite, tmp_path)
    rows = tracing.layer_report()
    kind = "mesh.execute" if cell.startswith("mesh") else "execute"
    for row in rows:
        assert kind in row["self_s"]
        assert row["counters"]["dense_aggregates"] == CELLS[cell]
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
            del row["counters"]["dense_aggregates"]
        return rows

    monkeypatch.setattr(tracing, "layer_report", before_the_counter)
    assert read(record) is None
    monkeypatch.delattr(tracing, "layer_report")
    assert read(record) is None


def test_benchmark_json_lists_the_metric_in_the_cells_that_report_it():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    module = run.load_module("metrics", "dense_aggregates.py")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "dense_aggregates"]
    cells = [w["name"] for w in bench["workloads"]]
    assert entry == {
        "name": "dense_aggregates", "unit": module.UNIT, "better": "higher",
        "source": module.SOURCE, "layer": module.LAYER,
        "moves": module.MOVES,
        "workloads": [c for c in cells if c in CELLS]}
