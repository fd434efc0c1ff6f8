"""The six readers of the spans inside a program's call and a worker's task
(PR 38: `metrics/launch_ms.py`, `device_wait_ms.py`, `device_syncs.py` in
every cell; `input_wait_ms.py`, `worker_host_ms.py`, `worker_tasks.py` in
`coord4-q1`), rehearsed on the CPU the way a traced run reads them: the
single-node and mesh tiers at SF0.01 through `test_direct_groupings.py`'s
window, the coordinator tier at SF0.05 through `test_program_metrics.py`'s
(the smallest scale at which q1 has an exchange). Every value is checked
against `tracing.layer_report`'s rows. Counts and identities only: none of
the numbers is a measurement."""

import json
import os
import statistics
import time

import pytest

import run
from test_direct_groupings import ctx, traced_window  # noqa: F401
from test_program_metrics import REQUESTS, coordinator_window  # noqa: F401

from datafusion_distributed_tpu.runtime import tracing

CELLS = ["direct-q1", "mesh4-q1", "direct-q6", "coord4-q1", "sf10-direct-q1"]
# metric file -> (unit, source, layer, the cells its entry lists)
EVERY_CELL = {
    "launch_ms": ("ms", "program_span", "execution"),
    "device_wait_ms": ("ms", "program_span", "execution"),
    "device_syncs": ("count", "program_counter", "execution"),
}
COORDINATOR = {
    "input_wait_ms": ("ms", "program_span", "exchange, coordinator tier"),
    "worker_host_ms": ("ms", "program_span", "execution"),
    "worker_tasks": ("count", "program_counter", "coordinator scheduling"),
}


def read(name: str, record: dict):
    return run.load_module("metrics", f"{name}.py").read(record)


def before_the_spans(monkeypatch):
    """`layer_report` as the parent commit's: no `launch`, `sync`, `wait`
    or `worker` span, no `syncs` or `tasks` counter."""
    report = tracing.layer_report

    def parents():
        rows = report()
        for row in rows:
            for kind in ("launch", "sync", "wait", "worker"):
                row["self_s"].pop(kind, None)
            for name in ("launch", "sync", "input_wait", "gate_wait"):
                row["total_s"].pop(name, None)
            for name in ("syncs", "tasks"):
                del row["counters"][name]
        return rows

    monkeypatch.setattr(tracing, "layer_report", parents)


@pytest.mark.parametrize("cell", ["direct-q1", "mesh4-q1", "direct-q6"])
def test_a_program_call_splits_into_launch_and_device_wait(
        cell, ctx, tmp_path, monkeypatch):  # noqa: F811
    ctx, suite = ctx
    record = traced_window(cell, ctx, suite, tmp_path)
    rows = tracing.layer_report()
    kind = "mesh.execute" if cell.startswith("mesh") else "execute"
    for row in rows:
        # the two halves lie inside the tier's one program call
        assert 0 < row["total_s"]["launch"] < row["total_s"][kind]
        assert 0 < row["self_s"]["sync"] < row["total_s"][kind]
        # one blocking read a query: the flag vector (the fetch's round
        # trip is `fetch_round_trips`'); no worker task ran
        assert row["counters"]["syncs"] == 1
        assert row["counters"]["tasks"] == 0
    assert read("launch_ms", record) == pytest.approx(statistics.median(
        r["total_s"]["launch"] for r in rows) * 1e3)
    assert read("device_wait_ms", record) == pytest.approx(statistics.median(
        r["self_s"]["sync"] for r in rows) * 1e3)
    assert read("device_syncs", record) == 1
    # the coordinator tier's three are not these tiers' to report
    for name in COORDINATOR:
        assert read(name, record) is None
    # requests from before the window, or no request at all: nothing
    for name in EVERY_CELL:
        assert read(name, {"queries": [{"start": time.perf_counter()}]}) is None
        assert read(name, {"queries": []}) is None
    # a program from before the spans (the parent commit), or with no
    # report at all: the line leaves the metrics out
    before_the_spans(monkeypatch)
    for name in EVERY_CELL:
        assert read(name, record) is None
    monkeypatch.delattr(tracing, "layer_report")
    for name in EVERY_CELL:
        assert read(name, record) is None


def test_the_coordinator_tier_reads_all_six(coordinator_window,  # noqa: F811
                                            monkeypatch):
    record, rows = coordinator_window
    # the tier's `bench.parse` is a `ctx.sql` of the benchmark's own: a
    # request with no program in it, which every reader skips
    served = [r for r in rows if "schedule" in r["self_s"]]
    assert len(served) == REQUESTS and len(rows) == 2 * REQUESTS

    def median(value):
        return statistics.median(value(r) for r in served)

    tasks = median(lambda r: r["counters"]["tasks"])
    assert read("worker_tasks", record) == tasks >= 3
    # every task pulls its flags, its metric values one by one, its rows
    assert read("device_syncs", record) == median(
        lambda r: r["counters"]["syncs"]) >= 3 * tasks
    assert 0 < read("launch_ms", record) == pytest.approx(median(
        lambda r: r["total_s"]["launch"]) * 1e3)
    assert 0 < read("device_wait_ms", record) == pytest.approx(median(
        lambda r: r["self_s"]["sync"]) * 1e3)
    assert 0 < read("input_wait_ms", record) == pytest.approx(median(
        lambda r: r["self_s"]["wait"]) * 1e3)
    assert 0 < read("worker_host_ms", record) == pytest.approx(median(
        lambda r: sum(r["self_s"][k]
                      for k in ("worker", "prepare", "execute"))) * 1e3)
    for row in served:
        # the sums over threads account for the tasks' summed time: what
        # is left to `worker_execute` itself is little of it
        parts = sum(row["self_s"][k] for k in (
            "launch", "sync", "wait", "h2d", "prepare", "execute", "worker"))
        assert parts == pytest.approx(row["total_s"]["worker_execute"],
                                      rel=0.02)
        # the consumers' wait is inside their `h2d`, which keeps the
        # hand-over alone as its own time
        assert row["self_s"]["h2d"] < row["self_s"]["wait"]
    before_the_spans(monkeypatch)
    for name in list(EVERY_CELL) + list(COORDINATOR):
        assert read(name, record) is None


def test_benchmark_json_lists_the_six():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]] == CELLS
    entries = {m["name"]: m for m in bench["per_layer"]}
    listed = {**{name: (spec, CELLS) for name, spec in EVERY_CELL.items()},
              **{name: (spec, ["coord4-q1"])
                 for name, spec in COORDINATOR.items()}}
    # appended, in this order, after everything the benchmark had
    assert [m["name"] for m in bench["per_layer"]][-6:] == list(listed)
    for name, ((unit, source, layer), cells) in listed.items():
        module = run.load_module("metrics", f"{name}.py")
        assert (module.UNIT, module.SOURCE, module.LAYER, module.MOVES) == (
            unit, source, layer, "query_p50_s")
        assert entries[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": "query_p50_s", "workloads": cells}
        for cell in cells:
            assert name in {m["name"] for m in run.cell_metrics(cell, True)}
    # the layers are ones the benchmark already names
    assert {spec[2] for spec, _ in listed.values()} <= {
        m["layer"] for m in bench["per_layer"][:-6]}
