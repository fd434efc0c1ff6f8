"""The result fetch: every host materialization of a device `Table`
(`Table.to_pandas`, `Table.to_numpy`, `io/parquet.py table_to_arrow` in its
plain and its wire shape) gets its buffers through `ops/table.py
fetch_host_buffers`, which waits on the device once (whole buffers, cut on
the host) or, over `_FETCH_WHOLE_MAX_BYTES`, twice (the row count, then
every buffer cut on its device). The plain reference kept here is the loop
the three had before: `int(num_rows)`, then a slice and a pull a buffer.
The answer must be that loop's, cell for cell and dtype for dtype."""

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from datafusion_distributed_tpu import spans
from datafusion_distributed_tpu.io.parquet import table_to_arrow
from datafusion_distributed_tpu.ops import table as table_mod
from datafusion_distributed_tpu.ops.table import (
    Dictionary,
    Table,
    fetch_counters,
    fetch_host_buffers,
    host_view,
)
from datafusion_distributed_tpu.runtime import tracing
from datafusion_distributed_tpu.schema import DataType, Field, Schema

SMALL, LARGE = 32, 1 << 18
SCHEMA = Schema([
    Field("f", DataType.FLOAT64, nullable=True),
    Field("g", DataType.FLOAT32, nullable=False),
    Field("i", DataType.INT64, nullable=True),
    Field("j", DataType.INT32, nullable=False),
    Field("s", DataType.STRING, nullable=True),
    Field("t", DataType.STRING, nullable=False),
    Field("d", DataType.DATE32, nullable=True),
    Field("b", DataType.BOOL, nullable=True),
])
NULLABLE = [f.name for f in SCHEMA.fields if f.nullable]
WORDS = ["apple", "fig", "kiwi", "lime", "pear"]

COMPILES: list = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, duration, **_: COMPILES.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None)


def make_table(capacity: int, rows: int, seed: int = 7) -> Table:
    """Eight columns, a null in every nullable one where there is a row to
    hold it, strings through two dictionaries (one kept in Arrow, of which
    the rows use a part), garbage past ``rows`` left as zeros."""
    rng = np.random.default_rng(seed)
    data = {
        "f": rng.normal(size=rows),
        "g": rng.normal(size=rows).astype(np.float32),
        "i": rng.integers(-1000, 1000, size=rows),
        "j": rng.integers(0, 9, size=rows).astype(np.int32),
        "s": rng.integers(0, 3, size=rows).astype(np.int32),
        "t": rng.integers(0, len(WORDS), size=rows).astype(np.int32),
        "d": rng.integers(8000, 11000, size=rows).astype(np.int32),
        "b": rng.integers(0, 2, size=rows).astype(bool),
    }
    validity = {}
    for k, name in enumerate(NULLABLE):
        valid = rng.random(rows) > 0.3
        if rows:
            valid[k % rows] = False
        validity[name] = valid
    dictionaries = {"s": Dictionary.from_arrow(pa.array(WORDS)),
                    "t": Dictionary.from_strings(WORDS)}
    return Table.from_numpy(data, SCHEMA, capacity=capacity,
                            validity=validity, dictionaries=dictionaries)


# ---- the plain reference: the parent's three loops, kept as they were ----


def loop_to_numpy(table: Table, decode_strings: bool = True) -> dict:
    n = int(table.num_rows)
    out = {}
    for name, col in zip(table.names, table.columns):
        vals = np.asarray(col.data[:n])
        if col.dtype == DataType.STRING and decode_strings:
            vals = col.dictionary.decode(vals)
        if col.validity is not None:
            mask = np.asarray(col.validity[:n])
            if vals.dtype == object:
                vals = vals.copy()
                vals[~mask] = None
            elif np.issubdtype(vals.dtype, np.floating):
                vals = vals.astype(np.float64, copy=True)
                vals[~mask] = np.nan
            else:
                vals = np.ma.masked_array(vals, mask=~mask)
        out[name] = vals
    return out


def loop_to_pandas(table: Table) -> pd.DataFrame:
    n = int(table.num_rows)
    cols = {}
    for name, col in zip(table.names, table.columns):
        vals = np.asarray(col.data[:n])
        if col.dtype == DataType.STRING:
            vals = col.dictionary.decode(vals)
        s = pd.Series(vals)
        if col.validity is not None:
            mask = np.asarray(col.validity[:n])
            s = s.where(pd.Series(mask), other=None)
        cols[name] = s
    return pd.DataFrame(cols)


def loop_to_arrow(table: Table, dictionary_gc: bool) -> pa.Table:
    n = int(table.num_rows)
    arrays = []
    for col in table.columns:
        vals = np.asarray(col.data[:n])
        mask = None
        if col.validity is not None:
            mask = ~np.asarray(col.validity[:n])
        if col.dtype == DataType.STRING and dictionary_gc:
            codes = vals.astype(np.int64)
            valid = np.ones(n, dtype=bool) if mask is None else ~mask
            live = valid & (codes >= 0) & (codes < len(col.dictionary))
            used = np.unique(codes[live])
            subset = col.dictionary.values[used]
            fill = used[0] if len(used) else 0
            new_codes = np.searchsorted(
                used, np.where(live, codes, fill)).astype(np.int32)
            arrays.append(pa.DictionaryArray.from_arrays(
                pa.array(new_codes, mask=~live),
                pa.array(subset.tolist(), type=pa.string())))
        elif col.dtype == DataType.STRING:
            decoded = col.dictionary.decode(vals)
            if mask is not None:
                decoded = decoded.copy()
                decoded[mask] = None
            arrays.append(pa.array(decoded.tolist(), type=pa.string()))
        elif col.dtype == DataType.DATE32:
            arr = pa.array(vals.astype(np.int32), type=pa.int32(), mask=mask)
            arrays.append(arr.cast(pa.date32()))
        else:
            arrays.append(pa.array(vals, mask=mask))
    out = pa.table(dict(zip(table.names, arrays)))
    if dictionary_gc:
        # since PR 37 the wire shape says which columns carry a validity
        # array (the field's ``nullable``); the values are the loop's
        out = pa.Table.from_arrays(out.columns, schema=pa.schema([
            f.with_nullable(c.validity is not None)
            for f, c in zip(out.schema, table.columns)]))
    return out


# ---- one comparison a shape ----------------------------------------------


def same_numpy(got: dict, want: dict) -> None:
    assert list(got) == list(want)
    for name in want:
        g, w = got[name], want[name]
        assert type(g) is type(w), name
        assert g.dtype == w.dtype and g.shape == w.shape, name
        if isinstance(w, np.ma.MaskedArray):
            assert np.array_equal(np.ma.getmaskarray(g),
                                  np.ma.getmaskarray(w)), name
            assert np.array_equal(g.filled(0), w.filled(0)), name
        elif w.dtype == object:
            assert g.tolist() == w.tolist(), name
        else:
            assert np.array_equal(g, w, equal_nan=w.dtype.kind == "f"), name


def same_frame(got: pd.DataFrame, want: pd.DataFrame) -> None:
    assert list(got.dtypes) == list(want.dtypes)
    pd.testing.assert_frame_equal(got, want, check_exact=True)


def same_arrow(got: pa.Table, want: pa.Table) -> None:
    assert got.schema.equals(want.schema, check_metadata=True)
    assert got.equals(want)


ENTRIES = {
    "to_pandas": (lambda t: t.to_pandas(), loop_to_pandas, same_frame),
    "to_numpy": (lambda t: t.to_numpy(), loop_to_numpy, same_numpy),
    "to_numpy_codes": (lambda t: t.to_numpy(decode_strings=False),
                       lambda t: loop_to_numpy(t, False), same_numpy),
    "arrow_plain": (table_to_arrow, lambda t: loop_to_arrow(t, False),
                    same_arrow),
    "arrow_wire": (lambda t: table_to_arrow(t, dictionary_gc=True),
                   lambda t: loop_to_arrow(t, True), same_arrow),
}
SHAPES = {  # name -> (capacity, rows)
    "small": (SMALL, 5), "small_empty": (SMALL, 0), "small_full": (SMALL, 32),
    "large": (LARGE, 5), "large_empty": (LARGE, 0),
    "large_full": (LARGE, LARGE),
}


def device_bytes(table: Table) -> int:
    return sum(b.nbytes for b in jax.tree_util.tree_leaves(table)
               if isinstance(b, jax.Array))


@pytest.fixture(scope="module")
def tables():
    made = {name: make_table(*shape) for name, shape in SHAPES.items()}
    cut = table_mod._FETCH_WHOLE_MAX_BYTES
    for name, table in made.items():
        assert (device_bytes(table) > cut) == name.startswith("large")
    return made


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_a_fetch_gives_what_the_per_column_loop_gave(entry, shape, tables):
    fetch, loop, same = ENTRIES[entry]
    table = tables[shape]
    same(fetch(table), loop(table))
    # a host-backed table passes through, and is the same answer
    same(fetch(host_view(table)), loop(table))


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_round_trips_one_under_the_cut_two_over_it_none_on_the_host(
        shape, tables):
    table = tables[shape]
    capacity, want_rows = SHAPES[shape]
    rows, columns, round_trips = fetch_host_buffers(table)
    assert rows == want_rows
    assert round_trips == (2 if shape.startswith("large") else 1)
    for col, (data, validity) in zip(table.columns, columns):
        assert isinstance(data, np.ndarray) and len(data) == rows
        assert data.dtype == col.data.dtype
        assert np.array_equal(data, np.asarray(col.data)[:rows])
        assert (validity is None) == (col.validity is None)
        if validity is not None:
            assert validity.dtype == np.bool_ and len(validity) == rows
            assert np.array_equal(validity, np.asarray(col.validity)[:rows])
    counters = fetch_counters(table, rows, round_trips)
    assert counters["transfers"] == 1 + len(SCHEMA.fields) + len(NULLABLE)
    assert counters["round_trips"] == round_trips
    hosted = host_view(table)
    rows, columns, round_trips = fetch_host_buffers(hosted)
    assert (rows, round_trips) == (want_rows, 0)
    for col, (data, validity) in zip(hosted.columns, columns):
        assert np.shares_memory(data, col.data) or rows == 0
    assert fetch_counters(hosted, rows, round_trips)["transfers"] == 0
    # the row count alone on a device is a wait of its own kind: one
    counted = Table(hosted.names, hosted.columns, jnp.int32(want_rows))
    assert fetch_host_buffers(counted)[::2] == (want_rows, 1)


def test_a_buffer_shorter_than_the_row_count_comes_whole():
    """`slice_rows` hands out tail views shorter than their row count says
    (rows past the buffer are garbage by contract): the loop's ``[:n]``
    gave the whole buffer, and so does the cut on the host."""
    table = make_table(SMALL, 20)
    short = Table(table.names, tuple(
        table_mod.Column(c.data[:8], None if c.validity is None
                         else c.validity[:8], c.dtype, c.dictionary)
        for c in table.columns), jnp.int32(20))
    rows, columns, _ = fetch_host_buffers(short)
    assert rows == 20 and {len(d) for d, _v in columns} == {8}
    same_numpy(short.to_numpy(), loop_to_numpy(short))


def test_the_small_path_compiles_nothing_for_a_new_row_count():
    """The loop compiled a slice program for every new (capacity, rows,
    dtype): a way into `compiles_in_window` for any query whose row count
    varies. Whole buffers cut on the host run no program at all."""
    table = make_table(24, 13)
    jax.block_until_ready(table)
    before = len(COMPILES)
    for fetch, _loop, _same in ENTRIES.values():
        fetch(table)
    assert len(COMPILES) == before
    loop_to_pandas(table)
    assert len(COMPILES) > before  # the contrast: (24,)[:13] was new


def test_the_fetch_span_carries_round_trips_beside_transfers(tables):
    for shape, want in (("small", 1), ("large", 2)):
        tracing.DEFAULT_TRACE_STORE.clear()
        # a table object of the test's own, carrying a request as a traced
        # collect's result does
        made = tables[shape]
        table = spans.tag_request(
            Table(made.names, made.columns, made.num_rows), "r-" + shape)
        for fetch in (Table.to_pandas, table_to_arrow):
            fetch(table)
        (row,) = tracing.layer_report()
        assert row["counters"]["round_trips"] == 2 * want
        assert row["counters"]["transfers"] == 2 * (
            1 + len(SCHEMA.fields) + len(NULLABLE))
        for trace in tracing.DEFAULT_TRACE_STORE.finished_traces():
            attrs = trace.root_span().attrs
            assert (attrs["round_trips"], attrs["rows"]) == (want, 5)
    tracing.DEFAULT_TRACE_STORE.clear()


# ---- several devices: the forced 8-device CPU platform of conftest.py ----


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.asarray(jax.devices()[:4]), ("x",))


@pytest.fixture()
def pulled(monkeypatch):
    """Every leaf the fetch hands to `jax.device_get`."""
    seen = []
    device_get = jax.device_get

    def spy(tree):
        seen.extend(jax.tree_util.tree_leaves(tree))
        return device_get(tree)

    monkeypatch.setattr(table_mod.jax, "device_get", spy)
    return seen


@pytest.mark.parametrize("shape", ["small", "large", "large_full"])
def test_a_replicated_result_is_read_from_one_device(shape, tables, mesh,
                                                     pulled):
    """As the mesh tier's result is: every buffer whole on all four
    devices. Nothing the fetch runs or pulls spans more than one device
    (the large path's slices run on the first device's copy alone)."""
    table = tables[shape]
    replicated = jax.device_put(table, NamedSharding(mesh, PartitionSpec()))
    for leaf in jax.tree_util.tree_leaves(replicated):
        assert len(leaf.sharding.device_set) == 4 and leaf.is_fully_replicated
    for name, (fetch, loop, same) in ENTRIES.items():
        del pulled[:]
        same(fetch(replicated), loop(table))
        arrays = [leaf for leaf in pulled if isinstance(leaf, jax.Array)]
        assert arrays, name
        for leaf in arrays:
            assert leaf.sharding.device_set == {mesh.devices.flat[0]}, name
    assert fetch_host_buffers(replicated)[2] == fetch_host_buffers(table)[2]


@pytest.mark.parametrize("shape", ["small", "large", "large_full"])
def test_a_sharded_result_still_assembles(shape, tables, mesh, pulled):
    table = tables[shape]
    rows_sharded = NamedSharding(mesh, PartitionSpec("x"))
    sharded = Table(
        table.names,
        tuple(jax.device_put(c, rows_sharded) for c in table.columns),
        jax.device_put(table.num_rows,
                       NamedSharding(mesh, PartitionSpec())))
    assert not sharded.columns[0].data.is_fully_replicated
    for fetch, loop, same in ENTRIES.values():
        same(fetch(sharded), loop(table))
    # the sharded buffers were left to `device_get` as they were
    assert any(isinstance(leaf, jax.Array)
               and len(leaf.sharding.device_set) == 4 for leaf in pulled)
