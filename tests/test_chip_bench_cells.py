"""`BENCHMARK.json` against the files it names, and three cells rehearsed on
the CPU through the function a chip run calls, `run.run_cell`, traced and
not, against the benchmark's plain reference: `sf10-direct-q1`
(`tpch-sf10-direct`: TPC-H SF10 resident on one chip) at SF0.01,
`cb-direct-q12` (`clickbench-half-direct`: half of ClickBench's hits on one
chip) at 20,000 rows, and `cb-mesh4-q12` (`clickbench-mesh4`: the whole
hits in four partitions, a partition a chip) at 20,000 rows on four of the
virtual devices. Answers and counts only: none of the numbers is a
measurement."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3000000019  # more than 32 signed bits hold, as the driver's are
CELL, CONFIG = "sf10-direct-q1", "tpch-sf10-direct"
CB_CELL, CB_CONFIG = "cb-direct-q12", "clickbench-half-direct"
MESH_CELL, MESH_CONFIG = "cb-mesh4-q12", "clickbench-mesh4"


def _load(name: str, *parts: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmarks", "chip", *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load("chipbench_run_cells", "run.py")


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def as_a_chip_run_is_set_up(tmp_path, monkeypatch):
    """`run_cell` keeps its data under the benchmark's directory and turns
    JAX's persistent cache to the checkout's: here the data goes to a
    temporary directory, and the suite's cache settings come back."""
    import jax

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    before = {name: getattr(jax.config, name) for name in names}
    monkeypatch.setattr(run, "CACHE", str(tmp_path))
    yield
    for name, value in before.items():
        jax.config.update(name, value)


@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_the_sf10_cell_agrees_with_the_reference(trace, capsys,
                                                 as_a_chip_run_is_set_up):
    result = run.run_cell(CELL, SEED, 0.5, trace, scale=0.01)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    assert all(n["value"] <= n["limit"] for n in result["compared"].values())
    assert 0 < result["compared"]["float_gap_in_tolerances"]["value"] < 0.01
    audit = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert (audit[0]["cell"], audit[0]["seed"], audit[0]["scale"]) == (
        CELL, SEED, 0.01)
    window = next(line for line in audit if "samples" in line)
    assert window["compiles_in_window"] == 0
    assert window["rows"]["orders"] == 15_000
    # every end-to-end metric, and every per-layer metric that lists no
    # cells; the CPU reports no device memory and its trace no device plane
    reported = set(result["metrics"])
    wanted = {m["name"] for m in run.cell_metrics(CELL, trace)}
    assert wanted - {"peak_device_gb", "device_busy_ms",
                     "hbm_roofline_share"} <= reported <= wanted
    if trace:
        assert wanted == {
            "generate_s", "register_s", "warmup_s", "compiles_in_window",
            "parse_ms", "execute_ms", "fetch_ms", "overflow_retries",
            "device_busy_ms", "hbm_roofline_share", "fetch_round_trips",
            "launch_ms", "device_wait_ms", "device_syncs"}
        assert result["attempted"] == 3  # the mix's traced_queries
        # the four rows' twenty buffers in one wait (PR 35)
        assert result["metrics"]["fetch_round_trips"]["value"] == 1
        assert result["metrics"]["overflow_retries"]["value"] == 0
        assert result["metrics"]["generate_s"]["value"] > 0
        assert result["metrics"]["register_s"]["value"] > 0
    else:
        assert wanted == {"setup_s", "query_p50_s", "queries_per_s",
                          "peak_device_gb"}


def test_the_sf10_cells_least_bytes_are_seven_columns_of_every_row():
    """`hbm_roofline_share` through q1's sidecar: 7 columns x 4 B x rows."""
    suite = run.load_module("suites", "tpch", "suite.py")

    class Rows:
        num_rows = 59_986_052

    assert suite.least_bytes("q1", {"lineitem": Rows}) == 7 * 4 * 59_986_052


def test_the_uncached_suite_is_the_tpch_suite_but_for_load(tmp_path):
    """`suites/tpch-uncached`: at SF10 a seed's cache is 9.9 GB and a check
    draws a dozen seeds a checkout, more than the chip machine's disk holds,
    so this suite generates in every run and writes nothing."""
    cached = run.load_module("suites", "tpch", "suite.py")
    uncached = run.load_module("suites", "tpch-uncached", "suite.py")
    public = [name for name, value in vars(cached).items()
              if callable(value) and not name.startswith("_")]
    assert {"load", "sql", "expected", "measure", "compare", "frame",
            "least_bytes"} <= set(public)
    for name in public:
        assert hasattr(uncached, name), name
    assert uncached.LIMITS == cached.LIMITS
    assert uncached.sql("q1") == cached.sql("q1")
    made = uncached.load(0.01, SEED, str(tmp_path / "a"))
    kept = cached.load(0.01, SEED, str(tmp_path / "b"))
    assert not (tmp_path / "a").exists() and (tmp_path / "b").exists()
    assert list(made) == list(kept) == sorted(made)
    for name in made:
        assert made[name].equals(kept[name]), name
    uncached.compare(uncached.expected("q6", made),
                     cached.expected("q6", kept))
    assert uncached.least_bytes("q1", made) == cached.least_bytes("q1", kept)


@pytest.mark.parametrize("lacking", ["tpch_cardinalities", "from_arrow"])
def test_the_uncached_suite_refuses_a_program_that_cannot_set_up_in_a_run(
        lacking, monkeypatch, tmp_path):
    """The parent of PR 34 takes 513 s a run at SF10 (420 of them set-up),
    past a run's time limit: under this suite it has to fail at once and
    cleanly, before any data is made, not be cut at the limit."""
    from datafusion_distributed_tpu.data import tpchgen
    from datafusion_distributed_tpu.ops.table import Dictionary

    uncached = run.load_module("suites", "tpch-uncached", "suite.py")
    monkeypatch.delattr(
        tpchgen if lacking == "tpch_cardinalities" else Dictionary, lacking)
    monkeypatch.setattr(tpchgen, "gen_tpch", lambda *a: pytest.fail("made"))
    with pytest.raises(SystemExit) as refused:
        uncached.load(0.01, SEED, str(tmp_path))
    assert refused.value.code not in (None, 0)
    assert "tpch-uncached" in str(refused.value.code)


#: the first configurations and cells of BENCHMARK.json, in their order:
#: the accepted entries, then this PR's; a later PR appends after them
CONFIGS = ["tpch-sf1-direct", "tpch-sf1-mesh4", "tpch-sf1-coord4", CONFIG,
           CB_CONFIG, MESH_CONFIG]
CELLS = ["direct-q1", "mesh4-q1", "direct-q6", "coord4-q1", CELL, CB_CELL,
         MESH_CELL]


def test_benchmark_json_lists_the_sf10_configuration_and_its_cell():
    bench = benchmark_json()
    workload = run.read_json("workloads", f"{CELL}.json")
    config = run.read_json("configs", f"{CONFIG}.json")
    # appended: the accepted entries before them, in their order, and the
    # later ones after them
    assert [c["name"] for c in bench["configs"]][:len(CONFIGS)] == CONFIGS
    assert [w["name"] for w in bench["workloads"]][:len(CELLS)] == CELLS
    assert bench["workloads"][CELLS.index(CELL)] == {
        "name": CELL, "config": CONFIG, "traffic": "q1-closed1", "chips": 1,
        "why": workload["why"]}
    assert bench["configs"][CONFIGS.index(CONFIG)] == {
        "name": CONFIG, "source": config["source"],
        "file": f"benchmarks/chip/configs/{CONFIG}.json", "reduced": [],
        "why": config["deployment"]}
    assert len(config["source"]) <= 200 and "SF10" in config["source"]
    assert len(workload["why"]) <= 200
    # the deployment: the source's own scale, nothing cut, the single-node
    # tier at its defaults, the guarantees of the SF1 configuration word
    # for word
    sf1 = run.read_json("configs", "tpch-sf1-direct.json")
    assert (config["suite"], config["scale"], config["tier"],
            config["tier_args"], config["chips"], config["reduced"]) == (
        "tpch-uncached", 10.0, "direct", {}, 1, [])
    assert config["guarantees"] == sf1["guarantees"]
    assert config["assumed"][:len(sf1["assumed"])] == sf1["assumed"]
    assert run.read_json("traffic", "q1-closed1.json") == {
        "loop": "closed", "queries": ["q1"], "clients": 1,
        "traced_queries": 3}
    # no list of a metric accepted before the cell names it: a `benchmark`
    # PR appends it (PERF.md section 7). `fetch_round_trips` came after it
    # (PR 35), with every cell in its list from the start, and so did the
    # three readers of the `launch` and `sync` spans (PR 38)
    for kind in ("end_to_end", "per_layer"):
        assert [m["name"] for m in bench[kind]
                if CELL in m.get("workloads", [])] == (
            ["fetch_round_trips", "launch_ms", "device_wait_ms",
             "device_syncs"] if kind == "per_layer" else [])


def test_benchmark_json_agrees_with_the_files():
    """The checks of `benchmarks/chip/tests/test_cells.py` (run by hand), as
    a tier-1 test: every cell's files exist and say what its entry says,
    every configuration is used, every metric has its file, every name in a
    `workloads` list is a cell, at most half the cells ask for 4 chips."""
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
        assert entry["file"] == (
            f"benchmarks/chip/configs/{config['name']}.json")
        mix = run.read_json("traffic", f"{workload['traffic']}.json")
        for part in (("traffic", f"{mix['loop']}.py"),
                     ("tiers", f"{config['tier']}.py"),
                     ("suites", config["suite"], "suite.py")):
            assert os.path.exists(os.path.join(run.HERE, *part)), part
    assert sorted(configs) == sorted({c["config"] for c in bench["workloads"]})
    # two deployments from one public benchmark: sources that differ
    assert len({c["source"] for c in configs.values()}) == len(configs)
    cells = [c["name"] for c in bench["workloads"]]
    assert len(cells) == len(set(cells)) and cells[:len(CELLS)] == CELLS
    # `mesh4-q1` and `cb-mesh4-q12`: at most half the cells, rounded down
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 2 <= max(
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
    # every cell reports set-up, one more end-to-end metric and a per-layer
    # metric
    for cell in cells:
        assert {"setup_s", "query_p50_s"} <= {
            m["name"] for m in run.cell_metrics(cell, False)}
        assert run.cell_metrics(cell, True)


# -- cb-direct-q12: ClickBench's hits, this chip's half ----------------------

#: 20,000 rows of hits: ceil(99,997,497 x scale)
CB_SCALE = 0.0002


@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_the_clickbench_cell_agrees_with_the_reference(trace, capsys,
                                                       as_a_chip_run_is_set_up):
    result = run.run_cell(CB_CELL, SEED, 0.5, trace, scale=CB_SCALE)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    assert {name: n["value"] for name, n in result["compared"].items()} == {
        "rows_off": 0, "columns_off": 0, "cells_differing": 0,
        "rows_out_of_order": 0, "results_missing": 0}
    audit = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert (audit[0]["cell"], audit[0]["seed"]) == (CB_CELL, SEED)
    window = next(line for line in audit if "samples" in line)
    assert window["compiles_in_window"] == 0
    assert window["rows"] == {"hits": 20_000}
    reported = set(result["metrics"])
    wanted = {m["name"] for m in run.cell_metrics(CB_CELL, trace)}
    assert wanted - {"peak_device_gb", "device_busy_ms",
                     "hbm_roofline_share"} <= reported <= wanted
    if trace:
        # every per-layer metric that lists no cells, and the two of its own
        assert wanted == {
            "generate_s", "register_s", "warmup_s", "compiles_in_window",
            "parse_ms", "execute_ms", "fetch_ms", "overflow_retries",
            "device_busy_ms", "hbm_roofline_share", "group_slots",
            "scatter_reductions"}
        assert result["attempted"] == 1  # the mix's traced_queries
        metrics = {name: m["value"] for name, m in result["metrics"].items()}
        assert metrics["overflow_retries"] == 0
        # 20,000 rows draw a few thousand phrases: a direct grouping over
        # them, reduced densely (past 4,096 the count is a scatter and the
        # slot presence is read off it: tests/test_dictionary_domain.py)
        assert 2_000 < metrics["group_slots"] <= 4_096
        assert metrics["scatter_reductions"] == 0
    else:
        assert wanted == {"setup_s", "query_p50_s", "queries_per_s",
                          "peak_device_gb"}


def test_the_clickbench_cells_least_bytes_are_one_column_of_every_row():
    """`hbm_roofline_share` through q12's sidecar: SearchPhrase, 4 B a row."""
    suite = run.load_module("suites", "clickbench", "suite.py")

    class Rows:
        num_rows = 49_998_749

    assert suite.least_bytes("q12", {"hits": Rows}) == 4 * 49_998_749
    assert suite.HITS_ROWS == 99_997_497


@pytest.mark.parametrize("lacking", ["SEARCH_PHRASES", "DIRECT_MAX_SLOTS"])
def test_the_clickbench_suite_refuses_a_program_that_cannot_run_it(
        lacking, monkeypatch, tmp_path):
    """A program without the generator's phrase law or the planner's
    domain-wide group tables (the parent of the PR that brought the cell)
    fails at once and cleanly, before any data is made, and does not hang
    in the claim loop."""
    from datafusion_distributed_tpu.data import clickbenchgen
    from datafusion_distributed_tpu.sql import planner

    suite = run.load_module("suites", "clickbench", "suite.py")
    monkeypatch.delattr(
        clickbenchgen if lacking == "SEARCH_PHRASES" else planner, lacking)
    monkeypatch.setattr(clickbenchgen, "gen_clickbench",
                        lambda *a: pytest.fail("made"))
    with pytest.raises(SystemExit) as refused:
        suite.load(0.5, SEED, str(tmp_path))
    assert refused.value.code not in (None, 0)
    assert "suites/clickbench" in str(refused.value.code)


def test_benchmark_json_lists_the_clickbench_configuration_and_its_cell():
    bench = benchmark_json()
    workload = run.read_json("workloads", f"{CB_CELL}.json")
    config = run.read_json("configs", f"{CB_CONFIG}.json")
    assert bench["workloads"][CELLS.index(CB_CELL)] == {
        "name": CB_CELL, "config": CB_CONFIG, "traffic": "cb-q12-closed1",
        "chips": 1, "why": workload["why"]}
    assert bench["configs"][CONFIGS.index(CB_CONFIG)] == {
        "name": CB_CONFIG, "source": config["source"],
        "file": f"benchmarks/chip/configs/{CB_CONFIG}.json",
        "reduced": ["rows", "columns"], "why": config["deployment"]}
    assert len(config["source"]) <= 200 and "ClickBench" in config["source"]
    assert len(workload["why"]) <= 200 and len(config["deployment"]) <= 200
    assert set(config["cuts"]) == set(config["reduced"])
    assert config["cuts"]["rows"].startswith("99,997,497 -> 49,998,749")
    assert config["cuts"]["columns"].startswith("105 -> 25")
    assert (config["suite"], config["scale"], config["tier"],
            config["tier_args"], config["chips"]) == (
        "clickbench", 0.5, "direct", {}, 1)
    assert run.read_json("traffic", "cb-q12-closed1.json") == {
        "loop": "closed", "queries": ["q12"], "clients": 1,
        "traced_queries": 1}
    # the cell's own metrics are the two new ones, each listing it alone;
    # no accepted metric's list names it
    for kind in ("end_to_end", "per_layer"):
        assert [m["name"] for m in bench[kind]
                if CB_CELL in m.get("workloads", [])] == (
            ["group_slots", "scatter_reductions"] if kind == "per_layer"
            else [])
    for name in ("group_slots", "scatter_reductions"):
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert (entry["unit"], entry["layer"], entry["source"],
                entry["moves"], entry["workloads"]) == (
            "count", "operators", "program_counter", "query_p50_s", [CB_CELL])


# -- cb-mesh4-q12: ClickBench's whole hits, a partition a chip ----------------


@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_the_partitioned_clickbench_cell_agrees_with_the_reference(
        trace, capsys, as_a_chip_run_is_set_up):
    """Its suite registers `hits` as four partitions on four of the virtual
    devices, and the mesh tier answers q12 over them: the same comparison
    as `cb-direct-q12`'s, the partitions used where they lie."""
    result = run.run_cell(MESH_CELL, SEED, 0.5, trace, scale=CB_SCALE)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert {name: n["value"] for name, n in result["compared"].items()} == {
        "rows_off": 0, "columns_off": 0, "cells_differing": 0,
        "rows_out_of_order": 0, "results_missing": 0}
    audit = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert (audit[0]["cell"], audit[0]["chips"]) == (MESH_CELL, 4)
    window = next(line for line in audit if "samples" in line)
    assert window["compiles_in_window"] == 0
    assert window["rows"] == {"hits": 20_000}
    reported = set(result["metrics"])
    wanted = {m["name"] for m in run.cell_metrics(MESH_CELL, trace)}
    assert wanted - {"peak_device_gb", "device_busy_ms",
                     "hbm_roofline_share"} <= reported <= wanted
    if trace:
        assert wanted == {
            "generate_s", "register_s", "warmup_s", "compiles_in_window",
            "parse_ms", "execute_ms", "fetch_ms", "overflow_retries",
            "device_busy_ms", "hbm_roofline_share", "partitioned_scans",
            "mesh_exchange_mb"}
        assert result["attempted"] == 1  # the mix's traced_queries
        metrics = {name: m["value"] for name, m in result["metrics"].items()}
        assert metrics["overflow_retries"] == 0
        assert metrics["partitioned_scans"] == 1
        assert metrics["mesh_exchange_mb"] > 0
    else:
        assert wanted == {"setup_s", "query_p50_s", "queries_per_s",
                          "peak_device_gb"}


def test_the_partitioned_suite_is_the_clickbench_suite_but_for_load(tmp_path):
    """`suites/clickbench-partitioned`: `suites/clickbench`'s texts, oracle,
    comparison and least bytes, over four partitions of the whole row
    count; its reference over the partitions' rows is the reference over
    the same rows as one table."""
    whole = run.load_module("suites", "clickbench", "suite.py")
    parted = run.load_module("suites", "clickbench-partitioned", "suite.py")
    assert parted.sql("q12") == whole.sql("q12")
    assert parted.LIMITS == whole.LIMITS and parted.PARTITIONS == 4
    tables = parted.load(CB_SCALE, SEED, str(tmp_path))
    assert not os.listdir(tmp_path)  # nothing cached
    (hits,) = tables.values()
    assert [t.num_rows for t in hits.tables] == [5_000] * 4
    assert parted.least_bytes("q12", tables) == 4 * 20_000
    import pyarrow as pa

    got = parted.expected("q12", tables)
    want = whole.expected("q12", {"hits": pa.concat_tables(hits.tables)})
    assert got[1] == want[1] == 10 and got[0].equals(want[0])


@pytest.mark.parametrize("lacking", ["Partitions",
                                     "gen_clickbench_partitions"])
def test_the_partitioned_suite_refuses_a_program_that_cannot_register_it(
        lacking, monkeypatch, tmp_path):
    """A program that cannot register a table as partitions (one from
    before partitioned registration) fails at once and cleanly, before any
    data is made: the whole `hits` on chip 0 would be 13.4 of its 16 GB
    before the mesh tier sliced it there."""
    from datafusion_distributed_tpu.data import clickbenchgen
    from datafusion_distributed_tpu.io import parquet

    suite = run.load_module("suites", "clickbench-partitioned", "suite.py")
    monkeypatch.delattr(parquet if lacking == "Partitions" else clickbenchgen,
                        lacking)
    monkeypatch.setattr(clickbenchgen, "gen_clickbench",
                        lambda *a: pytest.fail("made"))
    with pytest.raises(SystemExit) as refused:
        suite.load(1.0, SEED, str(tmp_path))
    assert refused.value.code not in (None, 0)
    assert "suites/clickbench-partitioned" in str(refused.value.code)


def test_benchmark_json_lists_the_partitioned_configuration_and_its_cell():
    bench = benchmark_json()
    workload = run.read_json("workloads", f"{MESH_CELL}.json")
    config = run.read_json("configs", f"{MESH_CONFIG}.json")
    assert bench["workloads"][CELLS.index(MESH_CELL)] == {
        "name": MESH_CELL, "config": MESH_CONFIG, "traffic": "cb-q12-closed1",
        "chips": 4, "why": workload["why"]}
    assert bench["configs"][CONFIGS.index(MESH_CONFIG)] == {
        "name": MESH_CONFIG, "source": config["source"],
        "file": f"benchmarks/chip/configs/{MESH_CONFIG}.json",
        "reduced": ["columns"], "why": config["deployment"]}
    for text in (config["source"], workload["why"], config["deployment"]):
        assert 0 < len(text) <= 200 and "\n" not in text
    assert "ClickBench" in config["source"]
    assert set(config["cuts"]) == set(config["reduced"]) == {"columns"}
    assert config["cuts"]["columns"].startswith("105 -> 25")
    assert (config["suite"], config["scale"], config["tier"],
            config["tier_args"], config["chips"]) == (
        "clickbench-partitioned", 1.0, "mesh", {"num_tasks": 4}, 4)
    # the half's guarantees word for word, and its assumptions and more
    half = run.read_json("configs", f"{CB_CONFIG}.json")
    assert config["guarantees"] == half["guarantees"]
    assert config["assumed"][:len(half["assumed"])] == half["assumed"]
    # the cell's own metrics are the two new ones; `mesh_exchange_mb` also
    # reads `mesh4-q1`, the other cell of the mesh tier
    for kind in ("end_to_end", "per_layer"):
        assert [m["name"] for m in bench[kind]
                if MESH_CELL in m.get("workloads", [])] == (
            ["partitioned_scans", "mesh_exchange_mb"]
            if kind == "per_layer" else [])
    metrics = {m["name"]: m for m in bench["per_layer"]}
    assert metrics["partitioned_scans"] == {
        "name": "partitioned_scans", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "mesh input placement",
        "moves": "query_p50_s", "workloads": [MESH_CELL]}
    assert metrics["mesh_exchange_mb"] == {
        "name": "mesh_exchange_mb", "unit": "MB", "better": "lower",
        "source": "program_counter", "layer": "exchange, mesh tier",
        "moves": "query_p50_s", "workloads": [MESH_CELL, "mesh4-q1"]}
    # appended last, after every accepted metric
    assert [m["name"] for m in bench["per_layer"]][-2:] == [
        "partitioned_scans", "mesh_exchange_mb"]
