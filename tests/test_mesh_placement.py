"""A mesh plan's inputs are put on their chips once (PR 36): task i's slice
of every leaf buffer lives on device i under `NamedSharding(mesh,
P("tasks"))`, the placement is kept on the cached plan's leaves and reused
while `load` hands back the same tables for the same devices, and it dies
with the plan. On the forced host devices `tests/test_chip_bench_mesh.py`
uses; answers and counts only."""

import dataclasses
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from datafusion_distributed_tpu.ops.table import Table
from datafusion_distributed_tpu.plan.physical import MemoryScanExec
from datafusion_distributed_tpu.runtime import mesh_executor, tracing
from datafusion_distributed_tpu.runtime.mesh_executor import (
    execute_on_mesh,
    make_mesh,
)
from datafusion_distributed_tpu.sql.context import SessionContext

SQL = ("select k, sum(v) as total, count(*) as n from t "
       "where v > 10 group by k order by k")


def arrow_rows(n: int, scale: float = 1.0):
    rng = np.random.default_rng(n)
    return pa.table({
        "k": pa.array(rng.choice(["a", "b", "c"], n)),
        "v": pa.array((rng.integers(0, 100, n) * scale).astype(np.float32)),
    })


def expected(arrow):
    frame = arrow.to_pandas()
    frame = frame[frame.v > 10].groupby("k").agg(
        total=("v", "sum"), n=("v", "size")).reset_index()
    return frame.sort_values("k").reset_index(drop=True)


def agrees(got, want):
    assert list(got.k) == list(want.k)
    assert list(got.n) == list(want.n)
    np.testing.assert_allclose(got.total, want.total, rtol=1e-5)


@pytest.fixture
def ctx():
    session = SessionContext()
    session.register_arrow("t", arrow_rows(1000))
    session.config.distributed_options["tracing"] = "on"
    return session


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(4)


def collect(ctx, mesh, sql=SQL):
    """-> (frame, the request's `mesh.stack_inputs` span)."""
    tracing.DEFAULT_TRACE_STORE.clear()
    frame = ctx.sql(sql).collect_distributed(mesh=mesh).to_pandas()
    (span,) = [s for trace in tracing.DEFAULT_TRACE_STORE.finished_traces()
               for s in trace.span_list() if s.kind == "mesh.stack_inputs"]
    return frame, span


def scans(ctx, mesh, sql=SQL):
    """The loadable leaves of the plan `execute_on_mesh` runs."""
    from datafusion_distributed_tpu.plan.fingerprint import prepare_plan

    df = ctx.sql(sql)
    tasks = mesh.shape["tasks"]
    # `collect_distributed_table`'s own call: the session's cached plan
    plan = df.distributed_plan(
        tasks, dataclasses.replace(df._seeded_distributed_config(tasks),
                                   uniform_stage_tasks=True),
        ctx.config.planner, mesh=mesh)
    return [leaf for leaf in prepare_plan(plan).plan.collect(
        lambda n: not n.children()) if hasattr(leaf, "load")]


def placement(leaf):
    return getattr(leaf, mesh_executor._PLACEMENT_ATTR)


def buffers(table):
    return jax.tree.leaves(table)


def test_the_second_execution_of_a_cached_plan_places_nothing(ctx, mesh):
    first, placed = collect(ctx, mesh)
    (leaf,) = scans(ctx, mesh)
    held = placement(leaf)
    assert placed.attrs["bytes"] == tracing.table_nbytes(held.table) > 0
    assert (placed.attrs["tasks"], placed.attrs["reused"]) == (4, 0)
    second, reused = collect(ctx, mesh)
    assert (reused.attrs["bytes"], reused.attrs["tasks"],
            reused.attrs["reused"]) == (0, 4, 1)
    # the placed arrays are the first run's objects
    assert placement(leaf) is held
    agrees(second, first)
    agrees(first, expected(arrow_rows(1000)))
    agrees(ctx.sql(SQL).collect_table().to_pandas(), first)


def test_every_placed_buffer_is_sharded_by_task_with_shard_i_on_device_i(
        ctx, mesh):
    collect(ctx, mesh)
    (leaf,) = scans(ctx, mesh)
    held = placement(leaf)
    devices = list(mesh.devices.flat)
    assert held.devices == tuple(devices)
    for placed, *slices in zip(buffers(held.table),
                               *map(buffers, held.sources)):
        assert placed.sharding == NamedSharding(mesh, P("tasks"))
        assert placed.shape == (4,) + np.shape(slices[0])
        assert [s.device for s in placed.addressable_shards] == devices
        for shard, task_slice in zip(placed.addressable_shards, slices):
            np.testing.assert_array_equal(
                np.asarray(shard.data)[0], np.asarray(task_slice))


def test_nothing_is_stacked_on_the_way_to_the_chips(ctx, mesh, monkeypatch):
    """`jnp.stack` raises while a fingerprint-equal plan (same SQL, fresh
    plan cache: new leaves, nothing placed) runs on the compiled program:
    the placement does not stack, and a program-cache hit does not trace."""
    first, _ = collect(ctx, mesh)
    ctx._plans.clear()

    def refuse(*args, **kwargs):
        raise AssertionError("jnp.stack on the mesh tier's input path")

    monkeypatch.setattr(jnp, "stack", refuse)
    again, placed = collect(ctx, mesh)
    assert placed.attrs["bytes"] > 0 and placed.attrs["reused"] == 0
    agrees(again, first)


def test_a_table_registered_anew_is_placed_anew_and_answers_its_rows(
        ctx, mesh):
    first, _ = collect(ctx, mesh)
    (old_leaf,) = scans(ctx, mesh)
    other = arrow_rows(1000, scale=3.0)
    ctx.register_arrow("t", other)
    second, placed = collect(ctx, mesh)
    assert placed.attrs["bytes"] > 0 and placed.attrs["reused"] == 0
    (new_leaf,) = scans(ctx, mesh)
    assert new_leaf is not old_leaf
    assert placement(new_leaf) is not placement(old_leaf)
    agrees(second, expected(other))
    assert list(second.total) != list(first.total)
    _, reused = collect(ctx, mesh)
    assert (reused.attrs["bytes"], reused.attrs["reused"]) == (0, 1)


def test_the_old_placement_dies_with_the_re_registered_tables_plan(ctx,
                                                                    mesh):
    collect(ctx, mesh)
    (leaf,) = scans(ctx, mesh)
    shard = weakref.ref(buffers(placement(leaf).table)[0])
    del leaf
    ctx.register_arrow("t", arrow_rows(1000, scale=3.0))
    collect(ctx, mesh)  # re-plans; the older generation's plan goes
    gc.collect()
    assert shard() is None


def test_a_two_device_mesh_does_not_reuse_a_four_device_placement(ctx, mesh):
    """Same leaf object, other devices: a hand-built plan over one
    replicated scan, run on four devices, then on two, then on four."""
    ctx.config.distributed_options.pop("tracing")
    table = ctx.catalog.tables["t"]
    leaf = MemoryScanExec([table], table.schema(), replicated=True)
    on_four = execute_on_mesh(leaf, mesh)
    four = placement(leaf)
    assert len(four.devices) == 4
    two_mesh = make_mesh(2)
    on_two = execute_on_mesh(leaf, two_mesh)
    two = placement(leaf)
    assert two is not four and two.devices == tuple(two_mesh.devices.flat)
    assert buffers(two.table)[0].shape[0] == 2
    execute_on_mesh(leaf, two_mesh)
    assert placement(leaf) is two
    execute_on_mesh(leaf, mesh)
    assert len(placement(leaf).devices) == 4
    for out in (on_four, on_two):
        assert out.to_pandas().equals(table.to_pandas())


def test_plan_cache_eviction_frees_the_placement(ctx, mesh):
    collect(ctx, mesh)
    (leaf,) = scans(ctx, mesh)
    held = weakref.ref(placement(leaf))
    shard = weakref.ref(buffers(placement(leaf).table)[0])
    del leaf
    gc.collect()
    assert held() is not None  # the cached plan keeps it
    ctx._plans.clear()  # what LRU eviction does to the entry
    gc.collect()
    # the compiled program's cache entry does not pin the plan it traced
    assert held() is None and shard() is None


class FreshScan(MemoryScanExec):
    """A leaf whose `load` returns other objects every time, as a
    `ParquetScanExec` or a scan of fresh exchange output does."""

    def load(self, task):
        table = super().load(task)
        return Table(table.names, table.columns, table.num_rows)


@pytest.mark.parametrize("leaf_class, replicated, reuses", [
    (MemoryScanExec, True, True), (FreshScan, False, False),
], ids=["replicated", "fresh-objects"])
def test_a_leaf_stays_correct_over_three_runs(ctx, mesh, leaf_class,
                                              replicated, reuses):
    """A replicated scan (`load` returns `tasks[0]` for every task) is
    placed once, the same table on every device; a leaf that loads fresh
    objects is placed anew each time and the old placement dropped."""
    from datafusion_distributed_tpu.parallel.exchange import partition_table

    ctx.config.distributed_options.pop("tracing")
    table = ctx.catalog.tables["t"]
    tasks = [table] if replicated else partition_table(table, 4)
    leaf = leaf_class(tasks, table.schema(), replicated=replicated)
    seen = []
    for _ in range(3):
        out = execute_on_mesh(leaf, mesh)
        held = placement(leaf)
        seen.append(held)
        for i, shard in enumerate(
                buffers(held.table)[0].addressable_shards):
            np.testing.assert_array_equal(
                np.asarray(shard.data)[0],
                np.asarray(tasks[0 if replicated else i].columns[0].data))
        if replicated:
            assert out.to_pandas().equals(table.to_pandas())
    assert (seen[0] is seen[1] is seen[2]) == reuses
    if not reuses:
        first = weakref.ref(seen.pop(0))
        gc.collect()
        assert first() is None
