"""A table registered as row partitions, a partition a device
(`io/parquet.py Partitions`, `SessionContext.register_arrow`): each
partition's buffers on its own device, one dictionary a string column, the
mesh tier using the partitions where they lie as its shards, the answers of
the plain reference (ClickBench q12, `benchmarks/chip/suites/clickbench/
oracle.py`) and of the whole-table registration (TPC-H q1), and every other
tier refusing the table rather than answering over one partition. On the
forced host devices of `conftest.py`; answers and counts only."""

import dataclasses
import hashlib
import importlib.util
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from datafusion_distributed_tpu.data.clickbenchgen import (
    gen_clickbench,
    gen_clickbench_partitions,
    phrase_texts,
)
from datafusion_distributed_tpu.io.parquet import Partitions
from datafusion_distributed_tpu.ops.table import PartitionedTable
from datafusion_distributed_tpu.plan.physical import PartitionedTableError
from datafusion_distributed_tpu.runtime import mesh_executor, tracing
from datafusion_distributed_tpu.runtime.mesh_executor import make_mesh
from datafusion_distributed_tpu.runtime.serving import ServingSession
from datafusion_distributed_tpu.sql.context import SessionContext

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
ROWS = 60_000
# two past 32 signed bits, as a benchmark run's seed may be
SEEDS = [7, 2147483649, 3000000019]


def _load(name: str, *parts: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(CHIP, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


oracle = _load("partitioned_clickbench_oracle", "suites", "clickbench",
               "oracle.py")
Q12 = open(os.path.join(CHIP, "suites", "clickbench", "queries",
                        "q12.sql")).read()


def registered(parts, traced: bool = False) -> SessionContext:
    ctx = SessionContext()
    if traced:
        ctx.config.distributed_options["tracing"] = "on"
    ctx.register_arrow("hits", Partitions(parts))
    return ctx


@pytest.fixture(scope="module")
def hits():
    return gen_clickbench_partitions(ROWS, SEEDS[0], 4)


def test_each_partition_lies_on_its_own_device_with_one_dictionary(hits):
    ctx = registered(hits)
    table = ctx.catalog.tables["hits"]
    assert isinstance(table, PartitionedTable)
    import jax

    devices = jax.devices()
    assert table.rows == tuple(p.num_rows for p in hits)
    assert table.num_rows == ctx.catalog.table_rows("hits") == ROWS
    for part, device in zip(table.parts, devices):
        for column in part.columns:
            assert column.data.devices() == {device}
            assert column.validity is None  # no column holds a NULL
        assert part.capacity == table.capacity == 16_384
    for name in ("SearchPhrase", "URL", "Title"):
        # one Dictionary object, built once over every partition's rows:
        # equal strings are equal codes on every device
        assert len({id(p.column(name).dictionary) for p in table.parts}) == 1
        assert ctx.catalog.column_domain("hits", name) == len(
            pc.unique(pa.chunked_array([p[name] for p in hits])))
    assert ctx.table_masks("hits") == 0


def test_more_partitions_than_devices_are_refused():
    import jax

    parts = Partitions.split(gen_clickbench(100, 1), len(jax.devices()) + 1)
    with pytest.raises(ValueError, match="a device a partition"):
        SessionContext().register_arrow("hits", parts)


def test_a_registration_spans_one_encode_a_string_column_and_one_h2d_a_part(
        hits):
    tracing.DEFAULT_TRACE_STORE.clear()
    registered(hits, traced=True)
    (trace,) = tracing.DEFAULT_TRACE_STORE.finished_traces()
    spans = trace.span_list()
    (root,) = [s for s in spans if s.name == "register"]
    assert (root.attrs["rows"], root.attrs["partitions"]) == (ROWS, 4)
    encoded = sorted(s.attrs["column"] for s in spans if s.name == "encode")
    assert encoded == sorted(name for name in hits[0].column_names
                             if pa.types.is_string(hits[0].schema.field(
                                 name).type))
    import jax

    # the partitions go up side by side, a thread each: their spans end in
    # any order
    h2d = {s.attrs["device"]: s for s in spans if s.name == "h2d"}
    devices = [str(d) for d in jax.devices()[:4]]
    assert sorted(h2d) == sorted(devices)
    assert [h2d[d].attrs["rows"] for d in devices] == [
        p.num_rows for p in hits]
    assert all(s.parent_id == root.span_id
               and s.attrs["bytes"] == 25 * 16_384 * 4 for s in h2d.values())


def q12_reference(parts):
    frame = pa.concat_tables(parts).select(["SearchPhrase"]).to_pandas()
    return oracle.q12({"hits": frame}), 10


@pytest.mark.parametrize("seed", SEEDS)
def test_q12_over_four_partitions_on_the_mesh_agrees_with_the_reference(seed):
    parts = gen_clickbench_partitions(ROWS, seed, 4)
    ctx = registered(parts)
    got = ctx.sql(Q12).collect_distributed(mesh=make_mesh(4)).to_pandas()
    assert oracle.measure_results(got, q12_reference(parts)) == {
        "rows_off": 0, "columns_off": 0, "cells_differing": 0,
        "rows_out_of_order": 0}


def test_tpch_q1_over_partitioned_lineitem_is_the_whole_tables_answer(
        tmp_path):
    """The mesh tier's answer over `lineitem` registered as four partitions
    is the answer over it registered whole and sliced on chip 0: the same
    rows in the same slices, the same dictionaries, so the same sums."""
    suite = _load("partitioned_tpch_suite", "suites", "tpch", "suite.py")
    tables = suite.load(0.01, SEEDS[1], str(tmp_path))
    whole, parted = SessionContext(), SessionContext()
    for name, arrow in tables.items():
        whole.register_arrow(name, arrow)
        parted.register_arrow(
            name, Partitions.split(arrow, 4) if name == "lineitem" else arrow)
    assert isinstance(parted.catalog.tables["lineitem"], PartitionedTable)
    mesh = make_mesh(4)
    got = parted.sql(suite.sql("q1")).collect_distributed(mesh=mesh)
    want = whole.sql(suite.sql("q1")).collect_distributed(mesh=mesh)
    pd.testing.assert_frame_equal(got.to_pandas(), want.to_pandas())
    suite.compare(got.to_pandas(), suite.expected("q1", tables))


@pytest.mark.parametrize("call", ["collect_table", "to_pandas", "collect"])
def test_a_collect_on_one_device_is_refused_and_names_the_mesh_tier(hits,
                                                                    call):
    df = registered(hits).sql(Q12)
    with pytest.raises(PartitionedTableError,
                       match=r"collect_distributed\(mesh=make_mesh\(4\)\)"):
        getattr(df, call)()


def test_the_coordinator_and_the_serving_tier_refuse_it(hits):
    ctx = registered(hits)
    with pytest.raises(PartitionedTableError, match="coordinator tier"):
        ctx.sql(Q12).collect_coordinated_table(num_workers=2, num_tasks=4)
    session = ServingSession(ctx, num_workers=2, num_tasks=4)
    try:
        with pytest.raises(PartitionedTableError, match="serving tier"):
            session.submit(Q12)
        assert session.stats()["queued"] == 0
    finally:
        session.close()


def stack_spans():
    return [span.attrs
            for trace in tracing.DEFAULT_TRACE_STORE.finished_traces()
            for span in trace.span_list()
            if span.name == "mesh.stack_inputs"]


def test_the_partitions_are_the_mesh_shards_from_the_first_collect(hits):
    """Nothing is copied or stacked on the way to the chips, on the first
    collect too: the placed array's shard i IS partition i's buffer. A
    second collect of the cached plan reuses the placement."""
    ctx = registered(hits, traced=True)
    mesh = make_mesh(4)
    tracing.DEFAULT_TRACE_STORE.clear()
    for _ in range(2):
        ctx.sql(Q12).collect_distributed(mesh=mesh)
    first, second = stack_spans()
    assert (first["bytes"], first["reused"]) == (0, 0)
    assert (second["bytes"], second["reused"]) == (0, 1)
    # the cached plan the two collects ran, and its leaf's placement
    df = ctx.sql(Q12)
    plan = df.distributed_plan(4, dataclasses.replace(
        df._seeded_distributed_config(4), uniform_stage_tasks=True),
        ctx.config.planner, mesh=mesh)
    (leaf,) = plan.collect(lambda n: getattr(n, "partitioned", False))
    held = getattr(leaf, mesh_executor._PLACEMENT_ATTR)
    assert held.resident and held.copied == 0
    (column,) = held.table.columns
    assert column.data.shape == (4 * 16_384,)
    parts = ctx.catalog.tables["hits"].parts
    devices = list(mesh.devices.flat)
    for shard in column.data.addressable_shards:
        own = parts[devices.index(shard.device)].column("SearchPhrase")
        assert (shard.data.unsafe_buffer_pointer()
                == own.data.unsafe_buffer_pointer())


def test_q12_reads_one_partitioned_scan_and_the_exchange_bytes(hits):
    """`partitioned_scans` 1 and `mesh_exchange_bytes` > 0 for q12 over the
    partitions; over the same rows registered whole, the mesh tier slices
    the table (0 such scans), and its exchanges carry bytes too."""
    mesh = make_mesh(4)
    rows = {}
    for form in ("partitions", "whole"):
        ctx = SessionContext()
        ctx.config.distributed_options["tracing"] = "on"
        ctx.register_arrow("hits", Partitions(hits) if form == "partitions"
                           else pa.concat_tables(hits))
        tracing.DEFAULT_TRACE_STORE.clear()
        ctx.sql(Q12).collect_distributed(mesh=mesh)
        rows[form] = [row["counters"] for row in tracing.layer_report()
                      if "mesh.execute" in row["self_s"]][-1]
    assert rows["partitions"]["partitioned_scans"] == 1
    assert rows["whole"]["partitioned_scans"] == 0
    assert rows["partitions"]["mesh_exchange_bytes"] > 0
    assert rows["whole"]["mesh_exchange_bytes"] > 0
    # one-chip programs carry nothing across chips
    ctx = SessionContext()
    ctx.config.distributed_options["tracing"] = "on"
    ctx.register_arrow("hits", pa.concat_tables(hits))
    tracing.DEFAULT_TRACE_STORE.clear()
    ctx.sql(Q12).to_pandas()
    (row,) = [r for r in tracing.layer_report() if "execute" in r["self_s"]]
    assert row["counters"]["mesh_exchange_bytes"] == 0
    assert row["counters"]["partitioned_scans"] == 0


def _digest(table) -> str:
    h = hashlib.sha256()
    for name in table.column_names:
        col = table.column(name).combine_chunks()
        h.update(name.encode())
        h.update(str(col.type).encode())
        if pa.types.is_string(col.type):
            h.update("\x00".join(col.to_pylist()).encode())
        else:
            h.update(np.asarray(col.to_numpy(zero_copy_only=False)).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("rows, seed, digest", [
    (20_000, 3000000019,
     "0677e772a78a1eb002fe75591178d73d1948f719440d6d7b4990c34dc125e091"),
    (60_000, 7,
     "02fed54b54a8fcb954ae4a3161080b848c91d0b6f676a1b73b78254007764cd7"),
])
def test_the_whole_table_generator_is_unchanged_byte_for_byte(rows, seed,
                                                              digest):
    """`gen_clickbench(rows, seed)` makes the table it made before the
    partitioned form came (digests taken before it), so `cb-direct-q12`'s
    data does not move."""
    assert _digest(gen_clickbench(rows, seed)) == digest


def test_the_partitions_write_one_text_a_rank(hits):
    """Each partition draws its own rows from ``(seed, i)``; a popularity
    rank is one text in all of them (`phrase_texts` from the table's
    seed): the three most popular phrases are in every partition, and the
    most popular is each one's top phrase."""
    assert [p.num_rows for p in hits] == [15_000] * 4
    assert not hits[0].equals(hits[1])
    top = phrase_texts(np.array([1, 2, 3]), SEEDS[0]).to_pylist()
    for part in hits:
        phrases = part["SearchPhrase"]
        kept = pc.filter(phrases, pc.not_equal(phrases, ""))
        counts = pc.value_counts(kept).to_pylist()
        assert max(counts, key=lambda c: c["counts"])["values"] == top[0]
        assert set(top) <= set(kept.to_pylist())
    # ceil(rows / 4) a partition, the last one the rest: at full size
    # 24,999,375 x 3 and 24,999,372
    sizes = [p.num_rows for p in gen_clickbench_partitions(99_997, 1, 4)]
    assert sizes == [25_000, 25_000, 25_000, 24_997]


def test_a_collective_books_its_operand_on_every_chip_of_its_axis():
    """`parallel/exchange.py collective_tally`: an `all_to_all` of a
    ``[4, 8, 2]`` uint32 buffer on each of four chips carries 4 x 256 B,
    the shuffle's row counts 4 x 16 B and its overflow flag 4 x 4 B; shapes
    only, at trace time."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    from datafusion_distributed_tpu.ops.table import Column, Table
    from datafusion_distributed_tpu.parallel.exchange import (
        collective_tally,
        shuffle_exchange,
    )
    from datafusion_distributed_tpu.schema import DataType

    mesh = make_mesh(4)

    def run(keys, values):
        table = Table(("k", "v"), (Column(keys, None, DataType.INT32),
                                   Column(values, None, DataType.INT32)),
                      jnp.int32(keys.shape[0]))
        out, _ = shuffle_exchange(table, ["k"], "tasks", 4, 8)
        return out.num_rows[None]

    program = shard_map(run, mesh=mesh, in_specs=(P("tasks"), P("tasks")),
                        out_specs=P("tasks"), check_rep=False)
    keys = jnp.arange(32, dtype=jnp.int32)
    with collective_tally() as carried:
        rows = jax.jit(program)(keys, keys)
    assert carried == [4 * (4 * 8 * 2 * 4) + 4 * (4 * 4) + 4 * 4]
    assert int(rows.sum()) == 32
    # a call that jax does not trace again books nothing: the count is the
    # trace's, and `trace_plan` keeps it with the program it traced
    with collective_tally() as again:
        jax.jit(program)(keys, keys)
    assert again == [0]


def test_a_wider_mesh_reads_empty_tasks_past_the_partitions(hits):
    """Eight tasks over four partitions: tasks 4-7 read empty tables,
    copied to their chips (the placement's only bytes), the four
    partitions stay where they lie, and the answer is the reference's. A
    narrower mesh than the partitions is refused: it would drop rows."""
    ctx = registered(hits, traced=True)
    tracing.DEFAULT_TRACE_STORE.clear()
    got = ctx.sql(Q12).collect_distributed(mesh=make_mesh(8)).to_pandas()
    assert oracle.measure_results(got, q12_reference(hits))[
        "cells_differing"] == 0
    (placed,) = stack_spans()
    assert placed["bytes"] == 4 * 16_384 * 4  # SearchPhrase, four tasks
    with pytest.raises(PartitionedTableError, match="make_mesh\\(4\\)"):
        ctx.sql(Q12).collect_distributed(mesh=make_mesh(2))


def test_partitions_agree_in_structure_whatever_their_own_nulls_hold():
    """A column with a NULL in one partition has a validity array in every
    partition, a partition with no rows has none the others lack, and a
    string column's codes index the one dictionary of all partitions (each
    partition hash-encoded alone: a string array of every partition's rows
    can pass the 2 GiB its offsets address)."""
    tables = [
        pa.table({"k": pa.array(["b", "a", None]),
                  "v": pa.array([1, 2, 3], type=pa.int32())}),
        pa.table({"k": pa.array(["c", "a", "d"]),
                  "v": pa.array([4, None, 6], type=pa.int32())}),
        pa.table({"k": pa.array(["a"]), "v": pa.array([7], type=pa.int32())}),
        pa.table({"k": pa.array([], type=pa.string()),
                  "v": pa.array([], type=pa.int32())}),
    ]
    ctx = SessionContext()
    ctx.register_arrow("t", Partitions(tables))
    parts = ctx.catalog.tables["t"].parts
    assert [p.validity_masks for p in parts] == [2, 2, 2, 2]
    (dictionary,) = {id(p.column("k").dictionary) for p in parts}
    values = list(parts[0].column("k").dictionary.values)
    assert values == ["a", "b", "c", "d"]
    decoded = [
        [values[c] if ok else None for c, ok in zip(
            np.asarray(p.column("k").data)[:n],
            np.asarray(p.column("k").validity)[:n])]
        for p, n in zip(parts, (3, 3, 1, 0))]
    assert decoded == [["b", "a", None], ["c", "a", "d"], ["a"], []]
    got = ctx.sql("select k, count(*) as n, sum(v) as s from t group by k "
                  "order by k").collect_distributed(mesh=make_mesh(4))
    assert got.to_pydict() == {"k": ["a", "b", "c", "d", None],
                               "n": [3, 1, 1, 1, 1], "s": [9, 1, 4, 6, 3]}
