"""Every cell's files, rehearsed on the CPU at SF0.01 through the function a
chip run calls (``run.run_cell``), traced and not: the tier's entry points,
the traffic loop, the comparison with the reference, the metric readers.
None of the numbers is a measurement."""

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


def benchmark_json() -> dict:
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_agrees_with_the_reference(cell, trace, capsys):
    result = run.run_cell(cell, SEED, 0.5, trace, scale=0.01)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    audit = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert audit[0]["seed"] == SEED and audit[0]["cell"] == cell
    window = next(line for line in audit if "samples" in line)
    assert window["samples"] == result["attempted"]
    assert window["compiles_in_window"] == 0
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
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for kind in ("end_to_end", "per_layer"):
        for entry in bench[kind]:
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
