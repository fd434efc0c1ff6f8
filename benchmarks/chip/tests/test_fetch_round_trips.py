"""`metrics/fetch_round_trips.py` rehearsed on the CPU at SF0.01 the way a
traced run reads it, in the cells whose tiers run at that scale: the
result's buffers come to the host in one round trip (`ops/table.py
fetch_host_buffers`: q1's twenty buffers and q6's three are copied whole
and together, and cut to their rows on the host), on the single-node tier
through `Table.to_pandas` and on the mesh tier through `table_to_arrow`
(PERF.md, PR 35). `coord4-q1`'s tier ends in the mesh tier's call
(`tiers/coord.py`: `table_to_arrow`) and `sf10-direct-q1`'s is
`direct-q1`'s. The window, the tables and the requests are
`test_direct_groupings.py`'s. Counts only: none of the numbers is a
measurement."""

import json
import os
import time

import pytest

import run
from test_direct_groupings import ctx, traced_window  # noqa: F401

from datafusion_distributed_tpu.runtime import tracing

# every cell lists the metric; these are rehearsed here
LISTED = ["direct-q1", "mesh4-q1", "direct-q6", "coord4-q1",
          "sf10-direct-q1"]
REHEARSED = ["direct-q1", "mesh4-q1", "direct-q6"]


def read(record: dict):
    return run.load_module("metrics", "fetch_round_trips.py").read(record)


@pytest.mark.parametrize("cell", REHEARSED)
def test_the_reader_counts_the_round_trips_of_a_fetch(
        cell, ctx, tmp_path, monkeypatch):  # noqa: F811
    ctx, suite = ctx
    record = traced_window(cell, ctx, suite, tmp_path)
    rows = tracing.layer_report()
    for row in rows:
        assert "fetch" in row["self_s"]  # the fetch joined its request
        assert row["counters"]["round_trips"] == 1
        # the buffers copied are what they were: q1's twenty, q6's three
        assert row["counters"]["transfers"] == (
            3 if cell == "direct-q6" else 20)
    assert read(record) == 1
    # requests from before the window, or no request at all: nothing
    assert read({"queries": [{"start": time.perf_counter()}]}) is None
    assert read({"queries": []}) is None
    # a program from before the counter (the parent commit): its rows hold
    # no such count, and the line leaves the metric out
    report = tracing.layer_report

    def before_the_counter():
        rows = report()
        for row in rows:
            del row["counters"]["round_trips"]
        return rows

    monkeypatch.setattr(tracing, "layer_report", before_the_counter)
    assert read(record) is None
    monkeypatch.delattr(tracing, "layer_report")
    assert read(record) is None


def test_benchmark_json_lists_the_metric_in_every_cell():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    module = run.load_module("metrics", "fetch_round_trips.py")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "fetch_round_trips"]
    cells = [w["name"] for w in bench["workloads"]]
    assert entry == {
        "name": "fetch_round_trips", "unit": module.UNIT, "better": "lower",
        "source": module.SOURCE, "layer": module.LAYER,
        "moves": module.MOVES,
        "workloads": [c for c in cells if c in LISTED]}
    assert entry["workloads"] == LISTED
    # the layer is the accepted `fetch_ms` and `fetch_transfers`' own
    assert {m["layer"] for m in bench["per_layer"]
            if m["name"].startswith("fetch_")} == {module.LAYER}
    for cell in LISTED:
        assert "fetch_round_trips" in {
            m["name"] for m in run.cell_metrics(cell, True)}
