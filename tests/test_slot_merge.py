"""The mesh tier's slot-range merge of a direct grouping's partial states
(`plan/exchanges.py SlotMergeExchangeExec`, `parallel/exchange.py
slot_merge_exchange`, `planner/distributed.py merge_direct_groupings`):
where a partial aggregate addresses its groups by their dictionary codes,
every task's states are arrays over the same slots, and task i takes the
sum, min or max over the tasks of slot range i in place of the rows a hash
shuffle would send it. Its answers are the single-node tier's (and, for
ClickBench q12, the plain reference's); a grouping by an integer key, and
the coordinator tier, keep the hash shuffle. On the forced host devices of
`conftest.py`; answers and counts only."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from datafusion_distributed_tpu.data.clickbenchgen import (
    gen_clickbench_partitions,
)
from datafusion_distributed_tpu.io.parquet import Partitions
from datafusion_distributed_tpu.parallel.exchange import (
    collective_tally,
    slot_merge_exchange,
)
from datafusion_distributed_tpu.plan.exchanges import (
    ShuffleExchangeExec,
    SlotMergeExchangeExec,
)
from datafusion_distributed_tpu.runtime import tracing
from datafusion_distributed_tpu.runtime.errors import PrecisionRangeError
from datafusion_distributed_tpu.runtime.mesh_executor import make_mesh
from datafusion_distributed_tpu.sql.context import SessionContext

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLICKBENCH = os.path.join(ROOT, "benchmarks", "chip", "suites", "clickbench")
TASKS = 4


def _oracle():
    spec = importlib.util.spec_from_file_location(
        "slot_merge_clickbench_oracle", os.path.join(CLICKBENCH, "oracle.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _table(seed: int, rows: int = 4_000) -> pa.Table:
    """``k``: 7 codes (a domain 4 tasks do not divide), ``kn``: the same
    with NULLs (a digit more), ``k2``: 3 codes, ``wide``: 40 codes; ``v``
    int32 with NULLs, all of them where ``k`` is ``w06`` (a SUM, MIN or
    MAX of NULL), ``f`` float32."""
    rng = np.random.default_rng(seed)
    words = np.array([f"w{i:02d}" for i in range(40)])
    k = rng.choice(words[:7], rows)
    return pa.table({
        "k": pa.array(k),
        "kn": pa.array(rng.choice(words[:7], rows),
                       mask=rng.random(rows) < 0.05),
        "k2": pa.array(rng.choice(["x", "y", "z"], rows)),
        "wide": pa.array(rng.choice(words, rows)),
        "v": pa.array(rng.integers(-1_000, 1_000, rows, dtype=np.int32),
                      mask=(rng.random(rows) < 0.1) | (k == "w06")),
        "f": pa.array(rng.normal(size=rows).astype(np.float32)),
    })


def _context(arrow, traced: bool = True, name: str = "t") -> SessionContext:
    ctx = SessionContext()
    if traced:
        ctx.config.distributed_options["tracing"] = "on"
    ctx.register_arrow(name, arrow)
    return ctx


def _mesh_plan(df, mesh):
    return df.distributed_plan(TASKS, dataclasses.replace(
        df._seeded_distributed_config(TASKS), uniform_stage_tasks=True),
        df.ctx.config.planner, mesh=mesh)


def _counters(kind: str) -> dict:
    (row,) = [r["counters"] for r in tracing.layer_report()
              if kind in r["self_s"]]
    return row


def _sorted(frame: pd.DataFrame, keys) -> pd.DataFrame:
    return frame.sort_values(list(keys), na_position="last").reset_index(
        drop=True)


CASES = {
    # every state the merge knows: a count of rows, a count of values, an
    # int32 SUM and a MIN over a column with NULLs (validity lanes), an AVG
    # (its sum and count) and a MAX over floats (an all_to_all)
    "every_aggregate": (
        "SELECT k, COUNT(*) AS n, COUNT(v) AS cv, SUM(v) AS sv, "
        "AVG(f) AS af, MIN(v) AS mn, MAX(f) AS mx FROM t GROUP BY k",
        ["k"]),
    # no COUNT(*): the used slots ride a presence lane of their own
    "sum_alone": ("SELECT k, SUM(v) AS sv FROM t GROUP BY k", ["k"]),
    # a NULL key is the digit past the dictionary
    "null_key": ("SELECT kn, COUNT(*) AS n, SUM(f) AS sf FROM t "
                 "GROUP BY kn", ["kn"]),
    "two_keys": ("SELECT k, k2, COUNT(*) AS n, MAX(v) AS mx FROM t "
                 "GROUP BY k, k2", ["k", "k2"]),
    # the variance family's (sum, sum of squares, count) states
    "variance": ("SELECT k2, STDDEV(f) AS sd, VAR_POP(v) AS vp FROM t "
                 "GROUP BY k2", ["k2"]),
    # 40 codes in ranges of 16: the groups left by the filter all lie in
    # task 0's range, tasks 1 and 2 hold codes and no group, task 3 none
    "empty_ranges": ("SELECT wide, COUNT(*) AS n, MIN(f) AS mn FROM t "
                     "WHERE wide < 'w10' GROUP BY wide", ["wide"]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_a_direct_grouping_merges_by_slot_range_as_one_node_answers(case):
    sql, keys = CASES[case]
    ctx = _context(_table(seed=len(case)))
    mesh = make_mesh(TASKS)
    want = ctx.sql(sql).to_pandas()
    tracing.DEFAULT_TRACE_STORE.clear()
    got = ctx.sql(sql).collect_distributed(mesh=mesh).to_pandas()
    counters = _counters("mesh.execute")
    assert counters["slot_merges"] == 1
    # below the dense cut the partial reduces densely, and the final has
    # nothing left to reduce
    assert counters["scatter_reductions"] == 0
    plan = _mesh_plan(ctx.sql(sql), mesh)
    assert not plan.collect(lambda n: isinstance(n, ShuffleExchangeExec))
    (merge,) = plan.collect(lambda n: isinstance(n, SlotMergeExchangeExec))
    assert merge.range_slots * TASKS >= merge.domain
    assert len(got) == len(want) > 0
    pd.testing.assert_frame_equal(_sorted(got, keys), _sorted(want, keys),
                                  check_exact=False, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("form", ["partitions", "whole"])
def test_q12_merges_by_slot_range_and_gives_the_references_rows(form):
    """ClickBench q12 over four partitions and over the same rows
    registered whole: the plain reference's rows, one slot merge, and the
    partial count the one scatter left (three with the hash shuffle)."""
    oracle = _oracle()
    q12 = open(os.path.join(CLICKBENCH, "queries", "q12.sql")).read()
    parts = gen_clickbench_partitions(40_000, 2147483649, TASKS)
    ctx = _context(Partitions(parts) if form == "partitions"
                   else pa.concat_tables(parts), name="hits")
    tracing.DEFAULT_TRACE_STORE.clear()
    got = ctx.sql(q12).collect_distributed(mesh=make_mesh(TASKS)).to_pandas()
    counters = _counters("mesh.execute")
    assert counters["slot_merges"] == 1
    assert counters["scatter_reductions"] == 1
    frame = pa.concat_tables(parts).select(["SearchPhrase"]).to_pandas()
    assert oracle.measure_results(got, (oracle.q12({"hits": frame}), 10)) == {
        "rows_off": 0, "columns_off": 0, "cells_differing": 0,
        "rows_out_of_order": 0}


@pytest.mark.parametrize("where", ["integer_key_on_the_mesh",
                                   "coordinator_tier"])
def test_the_hash_shuffle_stays_where_the_rule_does_not_hold(where):
    """A grouping by an integer key builds its table with the claim loop,
    so its partial states are not slot-addressed; the coordinator tier's
    exchanges go through the host. Both keep the hash shuffle: the plan
    holds a `ShuffleExchangeExec` and no slot merge, and nothing counts
    one."""
    arrow = _table(seed=5)
    ctx = _context(arrow)
    if where == "integer_key_on_the_mesh":
        sql = "SELECT v, COUNT(*) AS n FROM t GROUP BY v"
        mesh = make_mesh(TASKS)
        plan = _mesh_plan(ctx.sql(sql), mesh)
        tracing.DEFAULT_TRACE_STORE.clear()
        got = ctx.sql(sql).collect_distributed(mesh=mesh).to_pandas()
        assert _counters("mesh.execute")["slot_merges"] == 0
    else:
        sql = "SELECT k, COUNT(*) AS n FROM t GROUP BY k"
        plan = ctx.sql(sql).distributed_plan(TASKS)
        tracing.DEFAULT_TRACE_STORE.clear()
        got = ctx.sql(sql).collect_coordinated_table(
            num_workers=2, num_tasks=TASKS).to_pandas()
        (row,) = [r["counters"] for r in tracing.layer_report()]
        # its aggregates address their groups by the codes all the same
        assert row["slot_merges"] == 0 and row["direct_groupings"] > 0
    assert plan.collect(lambda n: isinstance(n, ShuffleExchangeExec))
    assert not plan.collect(lambda n: isinstance(n, SlotMergeExchangeExec))
    keys = list(got.columns[:1])
    pd.testing.assert_frame_equal(_sorted(got, keys),
                                  _sorted(ctx.sql(sql).to_pandas(), keys))


def test_an_int32_sum_whose_merge_leaves_its_range_raises():
    """Each task's partial SUM (two rows of 5e8) lies inside int32's
    range; the merge of four does not, and the float32 sum beside it
    says so, as a final merge's check does."""
    arrow = pa.table({"k": pa.array(["a"] * 8),
                      "v": pa.array([500_000_000] * 8, type=pa.int32())})
    ctx = _context(arrow, traced=False)
    sql = "SELECT k, SUM(v) AS s FROM t GROUP BY k"
    mesh = make_mesh(TASKS)
    assert _mesh_plan(ctx.sql(sql), mesh).collect(
        lambda n: isinstance(n, SlotMergeExchangeExec))
    with pytest.raises(PrecisionRangeError):
        ctx.sql(sql).collect_distributed(mesh=mesh)


def test_the_slot_merge_reduces_each_range_over_the_tasks():
    """`slot_merge_exchange` on four devices: task i gets slots
    [i * R, (i + 1) * R) of the sum, min and max over the tasks; the sums
    ride one `psum_scatter` a dtype, the min and the max one `all_to_all`
    each, and each books its operand on every chip."""
    mesh = make_mesh(TASKS)
    r = 8
    rng = np.random.default_rng(3)
    counts = rng.integers(0, 9, (TASKS, TASKS * r)).astype(np.int32)
    sums = rng.normal(size=(TASKS, TASKS * r)).astype(np.float32)
    lows = rng.integers(-50, 50, (TASKS, TASKS * r)).astype(np.int32)
    highs = rng.normal(size=(TASKS, TASKS * r)).astype(np.float32)

    def run(c, s, lo, hi):
        merged = slot_merge_exchange(
            [("sum", c[0]), ("sum", s[0]), ("min", lo[0]), ("max", hi[0]),
             ("sum", c[0] * 2)], "tasks", TASKS)
        return tuple(m[None] for m in merged)

    program = shard_map(run, mesh=mesh, in_specs=(P("tasks"),) * 4,
                        out_specs=(P("tasks"),) * 5, check_rep=False)
    with collective_tally() as carried:
        out = jax.jit(program)(*map(jnp.asarray, (counts, sums, lows, highs)))
    want = [counts.sum(0), sums.sum(0), lows.min(0), highs.max(0),
            2 * counts.sum(0)]
    for got, expected in zip(out, want):
        got = np.asarray(got).reshape(-1)
        np.testing.assert_allclose(got, expected, rtol=1e-6, atol=1e-6)
    lane = TASKS * r * 4  # one 4-byte lane over the padded domain
    # int32 sums (two lanes), float32 sums, the min's and the max's blocks
    assert carried == [TASKS * lane * (2 + 1 + 1 + 1)]
