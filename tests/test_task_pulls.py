"""A stage task waits on the device once (ISSUE 39): `execute_plan` brings
the flag vector and, for a caller that keeps metrics, every metric value
and with them the output's row count in ONE `jax.device_get`, and
`ops/table.py host_view` brings a stage output's buffers and its row count
in one more. The stand-in below counts the calls; the plain reference of
`host_view` kept here is the loop it had before (one `np.asarray` a
buffer, then `int(num_rows)`); what a worker reports (its node metrics,
``rows_out``) is pinned from pandas over the same data, not from a run of
the code under test."""

import datetime

import jax
import numpy as np
import pyarrow as pa
import pytest

from datafusion_distributed_tpu.io.parquet import arrow_to_table
from datafusion_distributed_tpu.ops.aggregate import AggSpec
from datafusion_distributed_tpu.ops.table import (
    Column,
    Table,
    host_view,
    is_host_backed,
)
from datafusion_distributed_tpu.plan.physical import (
    HashAggregateExec,
    MemoryScanExec,
    execute_plan,
)
from datafusion_distributed_tpu.runtime import tracing
from datafusion_distributed_tpu.runtime.errors import CapacityOverflowError
from datafusion_distributed_tpu.runtime.metrics import MetricsStore
from datafusion_distributed_tpu.runtime.worker import Worker
from datafusion_distributed_tpu.schema import DataType, Field, Schema

from test_tracing import TPCH_Q1, TPCH_Q3

TASKS = 4


@pytest.fixture
def pulls(monkeypatch):
    """Every `jax.device_get` made while the test runs, as the number of
    `jax.Array` leaves it was handed: the blocking reads of the paths
    under test, which reach the function through the module."""
    calls: list = []
    real = jax.device_get

    def counting(tree):
        calls.append(sum(isinstance(leaf, jax.Array)
                         for leaf in jax.tree_util.tree_leaves(tree)))
        return real(tree)

    monkeypatch.setattr(jax, "device_get", counting)
    return calls


def _wide_table(columns: int, masked: bool, capacity: int = 64,
                rows: int = 37) -> Table:
    """``columns`` columns of the engine's dtypes in turn, each with a
    validity array or none, on the device."""
    rng = np.random.default_rng(columns)
    kinds = [(DataType.INT64, np.int32), (DataType.FLOAT64, np.float32),
             (DataType.BOOL, bool), (DataType.DATE32, np.int32)]
    fields, data, validity = [], {}, {}
    for i in range(columns):
        dtype, host = kinds[i % len(kinds)]
        name = f"c{i}"
        fields.append(Field(name, dtype, nullable=masked))
        data[name] = rng.integers(0, 2 if host is bool else 1000,
                                  size=rows).astype(host)
        if masked:
            validity[name] = rng.random(rows) > 0.3
    return Table.from_numpy(data, Schema(fields), capacity=capacity,
                            validity=validity)


def _loop_host_view(table: Table) -> Table:
    """`host_view` as it was before ISSUE 39: a pull a buffer."""
    cols = tuple(
        Column(np.asarray(c.data),
               np.asarray(c.validity) if c.validity is not None else None,
               c.dtype, c.dictionary)
        for c in table.columns
    )
    return Table(table.names, cols, np.int32(int(table.num_rows)))


@pytest.mark.parametrize("masked", [True, False], ids=["masked", "maskless"])
@pytest.mark.parametrize("columns", [1, 10, 40])
def test_host_view_is_one_pull_whatever_the_columns(columns, masked, pulls):
    table = _wide_table(columns, masked)
    assert not is_host_backed(table)
    want = _loop_host_view(table)
    del pulls[:]
    with tracing.trace_call("query", {"tracing": "on"},
                            store=tracing.TraceStore()) as call:
        got = host_view(table)
    buffers = columns * (2 if masked else 1) + 1  # and the row count
    assert pulls == [buffers]
    # equal to the loop's, buffer for buffer and dtype for dtype; whole
    # padded buffers (the capacity is kept), read-only, None stays None
    assert is_host_backed(got) and got.names == table.names
    assert got.capacity == table.capacity == 64
    assert isinstance(got.num_rows, np.int32) and int(got.num_rows) == 37
    for have, ref, src in zip(got.columns, want.columns, table.columns):
        assert (have.dtype, have.dictionary) == (src.dtype, src.dictionary)
        assert type(have.data) is np.ndarray
        assert have.data.dtype == ref.data.dtype
        assert have.data.shape == (table.capacity,)
        np.testing.assert_array_equal(have.data, ref.data)
        assert not have.data.flags.writeable
        if masked:
            assert have.validity.dtype == ref.validity.dtype == bool
            np.testing.assert_array_equal(have.validity, ref.validity)
            assert not have.validity.flags.writeable
        else:
            assert have.validity is None and src.validity is None
    # the span says that the mechanism engaged
    (d2h,) = [s for s in call.tracer.trace.span_list() if s.name == "d2h"]
    assert d2h.attrs["buffers"] == buffers
    assert d2h.attrs["round_trips"] == 1
    assert d2h.attrs["bytes"] == tracing.table_nbytes(table)
    assert (d2h.attrs["rows"], d2h.attrs["capacity"]) == (37, 64)
    # a host-backed table is handed back as it is, and nothing is read
    del pulls[:]
    assert host_view(got) is got
    assert pulls == []


def test_host_view_passes_host_buffers_through_a_mixed_table(pulls):
    """A table with some buffers already on the host: those are not
    pulled (the very arrays come back), the rest ride the one pull."""
    device = _wide_table(4, masked=True)
    hosted = host_view(device)
    mixed = Table(
        device.names,
        (hosted.columns[0], device.columns[1], hosted.columns[2],
         device.columns[3]),
        device.num_rows,
    )
    del pulls[:]
    got = host_view(mixed)
    assert pulls == [2 * 2 + 1]
    assert got.columns[0].data is hosted.columns[0].data
    assert got.columns[2].validity is hosted.columns[2].validity
    for have, ref in zip(got.columns, hosted.columns):
        np.testing.assert_array_equal(have.data, ref.data)
        np.testing.assert_array_equal(have.validity, ref.validity)


def test_host_view_on_the_cpu_backend_shares_the_device_buffer():
    """`jax.device_get` of a CPU array is the zero-copy read-only view
    `np.asarray` gave (the view plane's `_merge_views` depends on it)."""
    table = _wide_table(3, masked=True)
    first, again = host_view(table), host_view(table)
    for a, b, src in zip(first.columns, again.columns, table.columns):
        assert np.shares_memory(a.data, b.data)
        assert np.shares_memory(a.data, np.asarray(src.data))
        assert np.shares_memory(a.validity, np.asarray(src.validity))


# ---- one pull a call of `execute_plan`, the store filled from it ----


def _group_plan(n: int = 512, keys: int = 16, num_slots: int = 32):
    rng = np.random.default_rng(3)
    t = arrow_to_table(pa.table({
        "k": rng.integers(0, keys, n),
        "v": rng.normal(size=n),
    }))
    scan = MemoryScanExec([t], t.schema())
    return HashAggregateExec(
        "single", ["k"], [AggSpec("sum", "v", "sv")], scan, num_slots
    ), scan


@pytest.mark.parametrize("keeps", ["store", "no store", "metrics off"])
def test_execute_plan_waits_on_the_device_once(keeps, pulls):
    """With a store: the flags, an ``output_rows`` a node and nothing
    else in one pull, the row count among them; without one (the
    single-node tier) the flag vector alone; a trace that recorded no
    metric sends the output's row count along instead."""
    agg, scan = _group_plan()
    store = MetricsStore() if keeps != "no store" else None
    config = {"collect_metrics": False} if keeps == "metrics off" else None
    execute_plan(agg, metrics_store=store, task_label="t", config=config)
    del pulls[:]
    with tracing.trace_call("query", {"tracing": "on"},
                            store=tracing.TraceStore()) as call:
        out = execute_plan(agg, metrics_store=store, task_label="t",
                           config=config)
    values = {"store": 3, "no store": 1, "metrics off": 2}[keeps]
    assert pulls == [values]
    (sync,) = [s for s in call.tracer.trace.span_list() if s.name == "sync"]
    assert (sync.attrs["what"], sync.attrs["syncs"],
            sync.attrs["values"]) == ("flags", 1, values)
    # the output that may feed another program stays wholly on the device
    assert isinstance(out.num_rows, jax.Array)
    assert all(isinstance(c.data, jax.Array) for c in out.columns)
    if keeps == "no store":
        return
    assert store.rows_out == {"t": 16} and int(out.num_rows) == 16
    assert store.per_task["t"] == ({} if keeps == "metrics off" else {
        agg.node_id: {"output_rows": 16},
        scan.node_id: {"output_rows": 512},
    })
    assert all(type(v) is int for m in store.per_task["t"].values()
               for v in m.values())
    assert type(store.rows_out["t"]) is int


def test_an_overflow_is_raised_before_the_store_is_written(pulls):
    """`raise_flagged` sees the flags of the one pull before anything of
    it is published: an overflowing plan leaves the store empty."""
    agg, _ = _group_plan(n=256, keys=64, num_slots=8)
    store = MetricsStore()
    with pytest.raises(CapacityOverflowError) as err:
        execute_plan(agg, metrics_store=store, task_label="t")
    assert err.value.nodes
    assert pulls == [3]
    assert store.per_task == {} and store.rows_out == {}
    # unchecked, the same values are published
    execute_plan(agg, metrics_store=store, task_label="t",
                 check_overflow=False)
    assert set(store.per_task) == {"t"} and set(store.rows_out) == {"t"}


# ---- what a worker reports, through the served path ----


@pytest.fixture(scope="module")
def tpch():
    from datafusion_distributed_tpu.data.tpchgen import gen_tpch
    from datafusion_distributed_tpu.sql.context import SessionContext

    ctx = SessionContext()
    ctx.config.distributed_options["bytes_per_task"] = 1  # force fan-out
    ctx.config.distributed_options["broadcast_joins"] = False
    arrow = gen_tpch(sf=0.002, seed=7)
    for name, table in arrow.items():
        ctx.register_arrow(name, table)
    return ctx, {name: t.to_pandas() for name, t in arrow.items()}


def _blocks(frame):
    """The contiguous row blocks a table's scan hands the four tasks of
    a leaf stage."""
    step = -(-len(frame) // TASKS)
    return [frame.iloc[i * step:(i + 1) * step] for i in range(TASKS)]


def _expected_q1(frames):
    """q1's stages by pandas: the nodes' ``output_rows`` of the four
    producers from the root down, the groups, the rows that cross."""
    cutoff = datetime.date(1998, 9, 2)
    producers, partial_groups = [], 0
    for block in _blocks(frames["lineitem"]):
        kept = block[block.l_shipdate <= cutoff]
        groups = kept.groupby(["l_returnflag", "l_linestatus"]).ngroups
        partial_groups += groups
        producers.append([groups, len(kept), len(kept), len(block),
                          len(block)])
    li = frames["lineitem"]
    groups = li[li.l_shipdate <= cutoff].groupby(
        ["l_returnflag", "l_linestatus"]).ngroups
    return producers, partial_groups, groups


def _expected_q3(frames):
    day = datetime.date(1995, 3, 15)
    li, orders, cust = (frames[n] for n in ("lineitem", "orders", "customer"))
    leaves = {
        "lineitem": [(len(b[b.l_shipdate > day]), len(b))
                     for b in _blocks(li)],
        "orders": [(len(b[b.o_orderdate < day]), len(b))
                   for b in _blocks(orders)],
        "customer": [(len(b[b.c_mktsegment == "BUILDING"]), len(b))
                     for b in _blocks(cust)],
    }
    # the planner joins the line items to the orders first
    lo = li[li.l_shipdate > day].merge(
        orders[orders.o_orderdate < day], left_on="l_orderkey",
        right_on="o_orderkey")
    loc = lo.merge(cust[cust.c_mktsegment == "BUILDING"],
                   left_on="o_custkey", right_on="c_custkey")
    groups = loc.groupby(
        ["l_orderkey", "o_orderdate", "o_shippriority"]).ngroups
    return leaves, len(lo), len(loc), groups


@pytest.fixture
def reported(monkeypatch):
    """What every worker task of the test noted down (`TaskData.metrics`)
    beside its plan and the row count of the table it returned."""
    seen: list = []
    real = Worker._execute_task_plan

    def noting(self, key, data, phase):
        out = real(self, key, data, phase)
        nodes = data.plan.collect(lambda _n: True)
        seen.append({
            "stage": key.stage_id, "task": key.task_number,
            "kinds": [type(n).__name__ for n in nodes],
            "rows": [data.metrics["nodes"].get(n.node_id) for n in nodes],
            "leaf": nodes[-1].schema().names[0],
            "rows_out": data.metrics["rows_out"],
            "out": out,
            "elapsed_s": data.metrics["elapsed_s"],
        })
        return out

    monkeypatch.setattr(Worker, "_execute_task_plan", noting)
    return seen


def _by_stage(reported) -> dict:
    stages: dict = {}
    for task in sorted(reported, key=lambda t: t["task"]):
        stages.setdefault(task["stage"], []).append(task)
    return stages


@pytest.mark.parametrize("query", ["q1", "q3"])
def test_served_tasks_report_the_oracles_rows_from_one_pull_each(
        query, tpch, reported, pulls):
    from datafusion_distributed_tpu.runtime.serving import ServingSession

    ctx, frames = tpch
    text = {"q1": TPCH_Q1, "q3": TPCH_Q3}[query]
    with ServingSession(ctx, num_workers=TASKS, num_tasks=TASKS) as srv:
        srv.submit(text).result(timeout=600)  # warm
        del reported[:], pulls[:]
        result = srv.submit(text).result(timeout=600)
    tasks = list(reported)
    # one pull a task (flags + a value a metric), one a stage output
    # (`host_view`: the count + its buffers), one for the result's fetch
    small = [1 + len(t["kinds"]) for t in tasks]
    outputs = [
        1 + sum(1 + (c.validity is not None) for c in t["out"].columns)
        for t in tasks if t["stage"] >= 0
    ]
    assert len(pulls) == len(tasks) + len(outputs) + 1
    assert sorted(pulls[:-1]) == sorted(small + outputs)
    for t in tasks:
        # every node reports its rows and nothing else, as an int; the
        # task's ``rows_out`` is its root's, and the table's own count
        assert all(set(m) == {"output_rows"} and type(m["output_rows"]) is int
                   for m in t["rows"]), t
        assert t["rows_out"] == t["rows"][0]["output_rows"]
        assert type(t["rows_out"]) is int
        assert t["rows_out"] == int(np.asarray(t["out"].num_rows))
        assert t["elapsed_s"] > 0
        # the output stayed on the device for whoever consumes it
        assert isinstance(t["out"].num_rows, jax.Array)
    stages = _by_stage(tasks)
    rows = lambda t: [m["output_rows"] for m in t["rows"]]
    (root,) = stages.pop(-1)
    assert root["rows_out"] == len(result)
    if query == "q1":
        producers, partial_groups, groups = _expected_q1(frames)
        (produce,) = [s for s in stages.values()
                      if s[0]["kinds"][-1] == "MemoryScanExec"]
        (consume,) = [s for s in stages.values() if s is not produce]
        assert [rows(t) for t in produce] == producers
        assert sum(rows(t)[-1] for t in consume) == partial_groups
        assert sum(t["rows_out"] for t in consume) == groups == len(result)
        assert rows(root) == [groups, groups]
        return
    leaves, joined_lo, joined_loc, groups = _expected_q3(frames)
    leaf_stages = {s[0]["leaf"].split("_")[0]: s for s in stages.values()
                   if s[0]["kinds"][-1] == "MemoryScanExec"}
    for table, prefix in (("lineitem", "l"), ("orders", "o"),
                          ("customer", "c")):
        got = [(t["rows_out"], rows(t)[-1]) for t in leaf_stages[prefix]]
        assert got == leaves[table], table
    joins = sorted(
        sum(t["rows"][t["kinds"].index("HashJoinExec")]["output_rows"]
            for t in s)
        for s in stages.values() if "HashJoinExec" in s[0]["kinds"])
    assert joins == sorted([joined_lo, joined_loc])
    (final,) = [s for s in stages.values() if s[0]["kinds"][0] == "SortExec"]
    aggs = [t["rows"][t["kinds"].index("HashAggregateExec")]["output_rows"]
            for t in final]
    assert sum(aggs) == groups
    # the LIMIT rides the sort: ten of a task's groups at the most
    assert [t["rows_out"] for t in final] == [min(10, n) for n in aggs]
    assert rows(root)[:2] == [10, 10] and rows(root)[2] == sum(
        t["rows_out"] for t in final)
    assert len(result) == 10
