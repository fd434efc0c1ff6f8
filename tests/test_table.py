"""Columnar substrate tests: Table/Column pytrees, compaction, IO round-trip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from datafusion_distributed_tpu.ops.table import (
    Column,
    Dictionary,
    Table,
    concat_tables,
)
from datafusion_distributed_tpu.schema import DataType, Field, Schema


def make_simple_table(n=10, capacity=16):
    schema = Schema(
        [
            Field("a", DataType.INT64, nullable=False),
            Field("b", DataType.FLOAT64, nullable=False),
        ]
    )
    data = {
        "a": np.arange(n, dtype=np.int64),
        "b": np.arange(n, dtype=np.float64) * 0.5,
    }
    return Table.from_numpy(data, schema, capacity=capacity)


def test_table_roundtrip():
    t = make_simple_table()
    out = t.to_numpy()
    np.testing.assert_array_equal(out["a"], np.arange(10))
    np.testing.assert_allclose(out["b"], np.arange(10) * 0.5)
    assert t.capacity == 16
    assert int(t.num_rows) == 10


def test_table_is_pytree():
    t = make_simple_table()
    leaves = jax.tree_util.tree_leaves(t)
    assert len(leaves) == 3  # a.data, b.data, num_rows

    @jax.jit
    def bump(table):
        col = table.column("a")
        return table.with_column("a", Column(col.data + 1, col.validity, col.dtype))

    t2 = bump(t)
    np.testing.assert_array_equal(t2.to_numpy()["a"], np.arange(10) + 1)


def test_compact_under_jit():
    t = make_simple_table()

    @jax.jit
    def keep_even(table):
        keep = table.column("a").data % 2 == 0
        return table.compact(keep)

    t2 = keep_even(t)
    assert int(t2.num_rows) == 5
    np.testing.assert_array_equal(t2.to_numpy()["a"], [0, 2, 4, 6, 8])
    assert t2.capacity == t.capacity  # static shape preserved


def test_dictionary_column():
    d = Dictionary.from_strings(["apple", "banana", "cherry"])
    assert d.code_of("banana") == 1
    assert d.code_of("zzz") == -1
    schema = Schema([Field("s", DataType.STRING, nullable=False)])
    codes = np.array([2, 0, 1, 0], dtype=np.int32)
    t = Table.from_numpy({"s": codes}, schema, capacity=8, dictionaries={"s": d})
    out = t.to_numpy()
    assert list(out["s"]) == ["cherry", "apple", "banana", "apple"]


def test_validity_nulls():
    schema = Schema([Field("x", DataType.INT32, nullable=True)])
    t = Table.from_numpy(
        {"x": np.array([1, 2, 3], dtype=np.int32)},
        schema,
        capacity=8,
        validity={"x": np.array([True, False, True])},
    )
    out = t.to_numpy()
    assert out["x"][0] == 1 and out["x"][2] == 3
    assert np.ma.is_masked(out["x"][1])


def test_concat_tables():
    t1 = make_simple_table(n=3, capacity=8)
    t2 = make_simple_table(n=4, capacity=8)
    out = concat_tables([t1, t2], capacity=16)
    assert int(out.num_rows) == 7
    np.testing.assert_array_equal(out.to_numpy()["a"], [0, 1, 2, 0, 1, 2, 3])


def test_head_limit():
    t = make_simple_table()
    t2 = t.head(4)
    assert int(t2.num_rows) == 4
    np.testing.assert_array_equal(t2.to_numpy()["a"], [0, 1, 2, 3])


def test_parquet_roundtrip(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    from datafusion_distributed_tpu.io.parquet import read_parquet, table_to_arrow

    arrow = pa.table(
        {
            "id": pa.array([1, 2, 3, 4], type=pa.int64()),
            "name": pa.array(["x", "y", None, "x"], type=pa.string()),
            "val": pa.array([1.5, None, 3.5, 4.0], type=pa.float64()),
        }
    )
    p = tmp_path / "t.parquet"
    pq.write_table(arrow, p)
    t = read_parquet(str(p))
    out = t.to_numpy()
    np.testing.assert_array_equal(out["id"], [1, 2, 3, 4])
    assert list(out["name"]) == ["x", "y", None, "x"]
    back = table_to_arrow(t)
    assert back.column("name").to_pylist() == ["x", "y", None, "x"]
    assert back.column("val").to_pylist()[0] == 1.5
    # NULL val survived the round trip
    assert back.column("val").to_pylist()[1] is None


@pytest.mark.parametrize("case", ["mixed", "all_null", "empty", "chunked",
                                  "no_nulls"])
def test_string_ingest_builds_sorted_dictionary(case):
    """Arrow strings -> (codes, sorted dictionary): held to the plain
    reference (sorted set of the non-null values, code = position). A
    column without a NULL comes with no validity array at all (PR 37)."""
    import pyarrow as pa

    from datafusion_distributed_tpu.io.parquet import arrow_to_host_columns

    rng = np.random.default_rng(3)
    pool = ["", "a", "B", "b", "ab", "é", "zé", "中", "~", " a",
            "Z", "aa", "\U0001f600"]
    if case == "all_null":
        vals = [None] * 5
    elif case == "empty":
        vals = []
    else:
        vals = [pool[i] for i in rng.integers(0, len(pool), 400)]
        for i in rng.integers(0, 400, 0 if case == "no_nulls" else 40):
            vals[i] = None
    col = pa.array(vals, type=pa.string())
    if case == "chunked":
        col = pa.chunked_array([col[:150], col[150:]])
    data, validity, dicts, _ = arrow_to_host_columns(pa.table({"s": col}))
    expected = sorted({v for v in vals if v is not None})
    assert list(dicts["s"].values) == expected
    assert dicts["s"].is_sorted()
    assert data["s"].dtype == np.int32
    assert ("s" in validity) == (case != "no_nulls")
    valid = validity.get("s", np.ones(len(vals), dtype=bool))
    assert list(valid) == [v is not None for v in vals]
    assert [expected[c] if ok else None
            for c, ok in zip(data["s"], valid)] == vals


def _parents_encoding(col, null_mask):
    """`io/parquet.py _encode_sorted_dictionary` as it stood before PR 34:
    the sorted distinct values turned into a numpy array of `str`."""
    import pyarrow.compute as pc

    enc = pc.dictionary_encode(col)
    order = pc.sort_indices(enc.dictionary).to_numpy()
    rank = np.empty(len(order), dtype=np.int32)
    rank[order] = np.arange(len(order), dtype=np.int32)
    idx = pc.fill_null(enc.indices, 0).to_numpy(zero_copy_only=False)
    codes = np.where(null_mask, rank[idx], 0).astype(np.int32, copy=False)
    return codes, enc.dictionary.take(order).to_numpy(zero_copy_only=False)


@pytest.mark.parametrize("kind", ["repeats", "nulls", "near_unique"])
def test_string_ingest_keeps_the_dictionary_in_arrow(kind):
    """The codes and the sorted order are the parent's; the dictionary's
    values stay in Arrow through registration and through a fetch (a
    column of millions of distinct comments costs no Python object a value,
    only the rows a result decodes), and `Dictionary.values` still serves
    every reader of the object array."""
    import pyarrow as pa

    from datafusion_distributed_tpu.io.parquet import (
        arrow_to_table,
        table_to_arrow,
    )

    rng = np.random.default_rng(11)
    n = 3000
    letters = np.array(list("abcXYZ é中~"))
    if kind == "near_unique":
        vals = ["".join(letters[rng.integers(0, len(letters), 12)])
                for _ in range(n)]
        vals[17] = vals[5]
    else:
        vals = ["".join(letters[rng.integers(0, len(letters), 2)])
                for _ in range(n)]
    if kind == "nulls":
        for i in rng.integers(0, n, n // 3):
            vals[i] = None
    col = pa.array(vals, type=pa.string())
    table = arrow_to_table(pa.table({"s": col, "i": np.arange(n)}))
    column = table.columns[0]
    d = column.dictionary
    assert d._values is None  # nothing decoded at registration
    want_codes, want_values = _parents_encoding(col, np.asarray(col.is_valid()))
    np.testing.assert_array_equal(np.asarray(column.data[:n]), want_codes)
    assert len(d) == len(want_values) and d.is_sorted()
    assert repr(d) == f"Dictionary(id={d.dict_id}, n={len(want_values)})"
    assert d.code_of(want_values[3]) == 3 and d.code_of("no such") == -1
    # both fetches decode the rows back, and only the rows
    assert table_to_arrow(table).column("s").to_pylist() == vals
    frame = table.to_pandas()
    assert [None if v != v or v is None else v for v in frame["s"]] == vals
    decoded = d.decode(np.array([0, len(d) - 1, -1, len(d)]))
    assert list(decoded) == [want_values[0], want_values[-1], None, None]
    assert d._values is None
    # the object array, made on first use, is the parent's
    assert d.values.dtype == object and list(d.values) == list(want_values)
    assert list(want_values) == sorted(set(v for v in vals if v is not None))
    assert d.index()[want_values[-1]] == len(d) - 1
    assert d.code_of(want_values[3]) == 3 and d.is_sorted()


def test_dictionary_from_arrow_notices_an_unsorted_array():
    import pyarrow as pa

    from datafusion_distributed_tpu.ops.table import Dictionary

    assert not Dictionary.from_arrow(pa.array(["b", "a"])).is_sorted()
    assert Dictionary.from_arrow(pa.array(["a", "a", "b"])).is_sorted()
    assert Dictionary.from_arrow(pa.array([], pa.string())).is_sorted()
    assert len(Dictionary.from_arrow(pa.array([], pa.string())).values) == 0


def test_gather_with_nonzero_pattern():
    t = make_simple_table(n=6, capacity=8)

    @jax.jit
    def pick(table):
        idx = jnp.array([5, 3, 1, 0, 0, 0, 0, 0], dtype=jnp.int32)
        return table.gather(idx, 3)

    t2 = pick(t)
    np.testing.assert_array_equal(t2.to_numpy()["a"], [5, 3, 1])
