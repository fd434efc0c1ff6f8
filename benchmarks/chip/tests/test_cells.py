"""Every cell's files, rehearsed on the CPU at SF0.01 through the function a
chip run calls (``run.run_cell``), traced and not: the tier's entry points,
the traffic loop, the comparison with the reference, the metric readers.
None of the numbers is a measurement."""

import gc
import glob
import json
import os
import subprocess
import sys

import pytest

import run

CELLS = sorted(os.path.splitext(os.path.basename(p))[0]
               for p in glob.glob(os.path.join(run.HERE, "workloads",
                                               "*.json")))
SEED = 2500000001  # more than 32 signed bits hold, as the driver's are
# the rehearsal's scale: SF0.05 is the smallest at which the planner splits
# q1 into stages with an exchange between them, which `coord4-q1`'s
# exchange readers read
SCALES = {"coord4-q1": 0.05}


def benchmark_json() -> dict:
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_agrees_with_the_reference(cell, trace, capsys):
    result = run.run_cell(cell, SEED, 0.5, trace,
                          scale=SCALES.get(cell, 0.01))
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    # each number compared beside its limit, under the line's last key
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {
        "rows_off", "columns_off", "cells_differing",
        "float_gap_in_tolerances", "results_missing"}
    assert all(n["value"] <= n["limit"] for n in result["compared"].values())
    assert 0 < result["compared"]["float_gap_in_tolerances"]["value"]
    audit = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert audit[0]["seed"] == SEED and audit[0]["cell"] == cell
    window = next(line for line in audit if "samples" in line)
    assert window["samples"] == result["attempted"]
    assert window["compiles_in_window"] == 0
    # every wall of the window, as measured, and the collector's doings
    assert len(window["walls_s"]) == len(window["starts_s"]) == (
        result["attempted"])
    assert 0 <= min(window["starts_s"]) <= max(window["starts_s"]) < 0.5
    assert min(window["walls_s"]) > 0
    assert all(g in (1, 2) and length >= 0
               for g, _start, length in window["collections"])
    assert window["young_collections"] >= 0
    assert not any(getattr(c, "__name__", "") == "on_collection"
                   for c in gc.callbacks)  # taken off again after the window
    reported = set(result["metrics"])
    wanted = {m["name"] for m in run.cell_metrics(cell, trace)}
    # the CPU reports no device memory and the trace holds no device plane
    absent = {"peak_device_gb", "device_busy_ms", "hbm_roofline_share",
              "collective_ms"}
    assert wanted - absent <= reported <= wanted
    if trace:
        mix = run.read_json("traffic", run.read_json(
            "workloads", f"{cell}.json")["traffic"] + ".json")
        assert result["attempted"] == mix["traced_queries"]
        assert result["device"]["window_s"] > 0
        report = next(line["program_self_ms"] for line in audit
                      if "program_self_ms" in line)
        assert report["fetch"] > 0
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_wrong_answer_is_counted_and_makes_the_run_incorrect(monkeypatch):
    real = run.load_module

    def load_module(*parts):
        module = real(*parts)
        if parts[-1] == "suite.py":
            answer = module.expected

            def expected(query, tables):
                frame = answer(query, tables)
                frame["sum_qty"] = frame["sum_qty"] * 1.001  # 2x the tolerance
                return frame

            module.expected = expected
        return module

    monkeypatch.setattr(run, "load_module", load_module)
    result = run.run_cell("direct-q1", SEED, 0.2, False, scale=0.01)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1


def test_an_answer_altered_where_it_is_produced_makes_the_run_incorrect(
        monkeypatch):
    """The timed path broken underneath: the tier hands back every frame
    with one float off by twice the tolerance."""
    real = run.load_module

    def load_module(*parts):
        module = real(*parts)
        if parts[0] == "tiers":
            tier_run = module.Tier.run

            def altered(self, sql):
                frame, retries = tier_run(self, sql)
                frame.iloc[0, frame.columns.get_loc("revenue")] *= 1.001
                return frame, retries

            module.Tier.run = altered
        return module

    monkeypatch.setattr(run, "load_module", load_module)
    result = run.run_cell("direct-q6", SEED, 0.2, False, scale=0.01)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1
    gap = result["compared"]["float_gap_in_tolerances"]
    assert 1.5 < gap["value"] < 2.5 and gap["limit"] == 1.0
    assert result["compared"]["cells_differing"]["value"] == 0


def test_walls_splits_a_log_into_processes_drift_and_stalls(tmp_path, capsys):
    walls = run.load_module("walls.py")
    log = tmp_path / "cell.log"
    lines = []
    for median in (0.010, 0.011):
        steady = [median] * 100
        steady[50] = 0.5  # one stall, inside the one full collection
        starts = [sum(steady[:i]) for i in range(100)]
        lines.append(json.dumps({
            "window_s": sum(steady), "walls_s": steady, "starts_s": starts,
            "young_collections_s": 0.001,
            "collections": [[2, starts[50] + 0.1, 0.3], [1, 0.2, 0.0005]]}))
    log.write_text("noise\n" + "\n".join(lines) + "\n{\"correct\": true}\n")
    first, second = (walls.one(r) for r in walls.runs([str(log)]))
    assert (first["n"], first["stalls"], first["full_collections"]) == (
        100, 1, 1)
    assert first["stalls_in_full_collection"] == 1
    assert first["median"] == 0.010 and second["median"] == 0.011
    assert first["stall_s"] == pytest.approx(0.49)
    assert first["drift"] == 0 and first["calm_mean"] == pytest.approx(0.010)
    assert first["collector_s"] == pytest.approx(0.3015)
    walls.main([str(log)])
    out = capsys.readouterr().out
    assert "between processes: medians spread" in out
    assert "stalls: 2 of 200 readings" in out and "2 in a full" in out


def test_two_clients_share_one_session():
    loop = run.load_module("traffic", "closed.py")
    mix = {"queries": ["a", "b", "c"], "clients": 2}
    records = loop.run(lambda q: q.upper(), mix, seed=1, seconds=60, limit=6)
    assert len(records) == 12
    for client in (0, 1):
        mine = [r for r in records if r["client"] == client]
        # every cycle sends the whole set, in an order drawn from the seed
        assert sorted(r["query"] for r in mine[:3]) == ["a", "b", "c"]
        assert all(r["result"] == r["query"].upper() for r in mine)
    again = loop.run(lambda q: q, mix, seed=1, seconds=60, limit=6)
    order = lambda rs: [r["query"] for r in rs if r["client"] == 0]  # noqa: E731
    assert order(again) == order(records)


def test_a_query_that_raises_is_a_failed_record():
    loop = run.load_module("traffic", "closed.py")

    def call(query):
        raise RuntimeError("boom")

    records = loop.run(call, {"queries": ["a"]}, seed=1, seconds=60, limit=2)
    assert [r["result"] for r in records] == [None, None]


def test_run_refuses_to_start_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         "direct-q1", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=run.ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout == ""  # no result, and no data was made
    assert "needs 1 TPU chip" in proc.stderr


def test_benchmark_json_agrees_with_the_files():
    bench = benchmark_json()
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        workload = run.read_json("workloads", f"{cell['name']}.json")
        config = run.read_json("configs", f"{workload['config']}.json")
        assert (cell["config"], cell["traffic"], cell["why"]) == (
            workload["config"], workload["traffic"], workload["why"])
        assert cell["chips"] == config["chips"]
        entry = configs[cell["config"]]
        assert entry["source"] == config["source"]
        assert entry["reduced"] == config["reduced"]
        assert entry["file"] == f"benchmarks/chip/configs/{config['name']}.json"
        assert os.path.exists(os.path.join(
            run.HERE, "traffic", f"{workload['traffic']}.json"))
    # every configuration is used, every name in a metric's list is a cell,
    # at most half of the cells ask for 4 chips and one always may
    assert sorted(configs) == sorted({c["config"] for c in bench["workloads"]})
    cells = [c["name"] for c in bench["workloads"]]
    assert sum(c["chips"] == 4 for c in bench["workloads"]) <= max(
        1, len(cells) // 2)
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for kind in ("end_to_end", "per_layer"):
        for entry in bench[kind]:
            assert set(entry.get("workloads", [])) <= set(cells)
            assert entry.get("workloads") != []
            module = run.load_module("metrics", f"{entry['name']}.py")
            assert (module.UNIT, module.SOURCE) == (entry["unit"],
                                                    entry["source"])
            if kind == "per_layer":
                assert (module.LAYER, module.MOVES) == (entry["layer"],
                                                        entry["moves"])
                assert entry["moves"] in end_to_end


def test_all_22_queries_have_a_text_and_an_oracle():
    suite = run.load_module("suites", "tpch", "suite.py")
    for i in range(1, 23):
        assert suite.sql(f"q{i}").strip()
        assert callable(suite.oracle.ORACLES[f"q{i}"])
    assert (suite.oracle.RTOL, suite.oracle.ATOL) == (5e-4, 1e-4)
