"""Host-side Parquet/Arrow -> device Table loading.

The reference's scan path is DataFusion's `DataSourceExec` over Parquet
(SURVEY.md L0) with per-task file-group slicing
(`/root/reference/src/distributed_planner/task_estimator.rs:235-300`). On TPU
the decode stays on the host (pyarrow), and the upload pads each batch to a
static capacity; string columns are dictionary-encoded against a per-dataset
unified dictionary so device-side codes are comparable across files and tasks.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import numpy as np

from datafusion_distributed_tpu import spans
from datafusion_distributed_tpu.ops.table import (
    Column,
    Dictionary,
    PartitionedTable,
    Table,
    fetch_counters,
    fetch_host_buffers,
    round_up_pow2,
)
from datafusion_distributed_tpu.schema import DataType, Field, Schema


# a table's string columns are dictionary-encoded on this many threads
_ENCODE_THREADS = min(8, os.cpu_count() or 1)


def _arrow_type_to_dtype(t) -> DataType:
    import pyarrow as pa

    if pa.types.is_int8(t) or pa.types.is_int16(t) or pa.types.is_int32(t):
        return DataType.INT32
    if pa.types.is_int64(t) or pa.types.is_uint32(t) or pa.types.is_uint64(t):
        return DataType.INT64
    if pa.types.is_uint8(t) or pa.types.is_uint16(t):
        return DataType.INT32
    if pa.types.is_float32(t):
        return DataType.FLOAT32
    if pa.types.is_float64(t):
        return DataType.FLOAT64
    if pa.types.is_decimal(t):
        return DataType.FLOAT64
    if pa.types.is_boolean(t):
        return DataType.BOOL
    if pa.types.is_date(t):
        return DataType.DATE32
    if pa.types.is_timestamp(t):
        return DataType.INT64
    if pa.types.is_string(t) or pa.types.is_large_string(t) or (
        pa.types.is_dictionary(t)
    ):
        return DataType.STRING
    raise NotImplementedError(f"unsupported arrow type: {t}")


def schema_from_arrow(arrow_schema) -> Schema:
    return Schema(
        [
            Field(f.name, _arrow_type_to_dtype(f.type), nullable=f.nullable)
            for f in arrow_schema
        ]
    )


def _encode_sorted_dictionary(col, null_mask) -> tuple:
    """Arrow string array -> (int32 codes, its distinct non-null values as a
    SORTED Arrow array: a fresh dictionary's values); null rows get code 0
    (their validity masks them; ``null_mask`` is None where the column holds
    no null). Arrow and numpy calls only, which release the interpreter's
    lock: a table's columns are encoded side by side.

    Arrow hash-encodes the rows and only the DISTINCT values are sorted —
    bytewise on UTF-8, which is code-point order, the order numpy and
    Python compare strings in. Sorting all N rows as fixed-width unicode
    (np.unique + searchsorted) cost 72 s for TPC-H SF1's l_comment alone."""
    import pyarrow.compute as pc

    enc = pc.dictionary_encode(col)
    distinct = enc.dictionary
    if len(distinct) == 0:
        return np.zeros(len(col), dtype=np.int32), distinct
    order = pc.sort_indices(distinct).to_numpy()
    rank = np.empty(len(order), dtype=np.int32)
    rank[order] = np.arange(len(order), dtype=np.int32)
    # a column with no nulls pays for no mask pass: at 60M rows every pass
    # is a quarter of a gigabyte of fresh host memory
    has_nulls = null_mask is not None
    idx = pc.fill_null(enc.indices, 0) if has_nulls else enc.indices
    codes = rank[idx.to_numpy(zero_copy_only=False)]
    if has_nulls:
        codes = np.where(null_mask, codes, 0).astype(np.int32, copy=False)
    return codes, distinct.take(order)


def _encode_under_span(tracer, parent, name, col, null_mask) -> tuple:
    with tracer.span("encode", "encode", parent=parent, column=name) as span:
        codes, values = _encode_sorted_dictionary(col, null_mask)
        if tracer.active:
            span.set(rows=len(col), distinct=len(values), bytes=col.nbytes)
    return codes, values


def arrow_to_host_columns(
    arrow_table,
    dictionaries: Optional[dict[str, Dictionary]] = None,
    tracer=spans.NULL_TRACER,
    mask_nullable_fields: bool = False,
) -> tuple[dict[str, np.ndarray], dict[str, np.ndarray], dict[str, Dictionary], Schema]:
    """Arrow table -> (host data arrays, validity arrays, dictionaries, schema).

    A column that holds no NULL gets NO validity array: its name is left out
    of the validity dict, `Table.from_numpy` makes it with ``validity`` None,
    and no all-true mask is built, uploaded or read by any operator. What
    decides is the data itself, the Arrow column's ``null_count`` (and, for a
    string column encoded against a provided dictionary, whether a value
    missing from it came out NULL); a column with one NULL has its mask, and
    so has one with no rows (a padding slot's code 0 must not read as a valid
    index into a dictionary that may be empty). A caller whose output must
    agree in tree structure with conversions of OTHER data (a task's file
    group, a shipped slice: each task sees only its own rows) passes
    ``mask_nullable_fields=True``: then a column has a mask, all-true or
    not, where its Arrow field is nullable (or it holds a NULL against its
    field's word), so the structure follows from the schema alone. A caller
    that sees the whole table (`SessionContext.register_arrow`,
    `register_parquet`) leaves it False.

    String columns become int32 code arrays. If ``dictionaries`` supplies a
    Dictionary for a column, codes are produced against it (values missing
    from the dictionary become -1/null); otherwise a fresh sorted dictionary
    is built from the column's values, under an ``encode`` span of
    ``tracer`` (`SessionContext.register_arrow` hands its own in). Those
    encodings run on threads beside the other columns' conversion: at
    TPC-H SF10 `l_comment` alone hashes 60M strings for 11 s.
    """
    schema = schema_from_arrow(arrow_table.schema)
    meta = arrow_table.schema.metadata or {}
    if b"dftpu_logical" in meta:
        # wire payloads carry their LOGICAL dtypes (runtime/codec.py): the
        # physical arrow width reflects the sender's precision mode, not
        # the column's logical type
        import json as _json

        logical = _json.loads(meta[b"dftpu_logical"].decode())
        schema = Schema([
            Field(f.name, DataType(logical.get(f.name, f.dtype.value)),
                  f.nullable)
            for f in schema.fields
        ])
    data: dict[str, np.ndarray] = {}
    validity: dict[str, np.ndarray] = {}
    dicts: dict[str, Dictionary] = {}
    encoding: dict = {}  # column -> future of (codes, sorted values)
    with ThreadPoolExecutor(_ENCODE_THREADS) as pool:
        _convert_columns(arrow_table, schema, dictionaries, tracer, pool,
                         data, validity, dicts, encoding)
        # in the schema's order, so that dictionary ids are handed out in
        # one order whatever the threads did; the sorted values stay in
        # Arrow: `Dictionary.values` makes the Python strings when a reader
        # first asks for them, not at registration
        for name, future in encoding.items():
            data[name], values = future.result()
            dicts[name] = Dictionary.from_arrow(values)
    # `_convert_columns` leaves None for a column without NULLs: an all-true
    # mask all the same where the schema decides and the field is nullable,
    # or where the data does and there are no rows; else no entry at all
    rows = arrow_table.num_rows
    for f in schema.fields:
        if validity[f.name] is None:
            if f.nullable if mask_nullable_fields else rows == 0:
                validity[f.name] = np.ones(rows, dtype=np.bool_)
            else:
                del validity[f.name]
    return data, validity, dicts, schema


def _convert_columns(arrow_table, schema, dictionaries, tracer, pool,
                     data, validity, dicts, encoding) -> None:
    import pyarrow as pa
    import pyarrow.compute as pc

    parent = tracer.current_id()
    for f in schema.fields:
        col = arrow_table.column(f.name)
        if isinstance(col, pa.ChunkedArray):
            col = col.combine_chunks()
        # None: the column holds no NULL, and no pass over its rows is
        # spent on saying so (at 60M rows an all-true mask is 60 MB of
        # fresh host memory, and as much again on the device)
        null_mask = np.asarray(col.is_valid()) if col.null_count else None
        if f.dtype == DataType.STRING:
            provided = dictionaries.get(f.name) if dictionaries else None
            if pa.types.is_dictionary(col.type) and provided is None:
                # wire fast path: a dictionary array arriving from
                # encode_table carries a GC'd, SORTED dictionary — adopt it
                # and its codes directly instead of decoding + re-uniquing
                # (the receive half of the reference's dictionary handling,
                # `impl_execute_task.rs:184-201` DictionaryHandling::Resend)
                dvals = np.asarray(
                    col.dictionary.to_numpy(zero_copy_only=False),
                    dtype=object,
                )
                sv = dvals.astype(str)
                # STRICTLY ascending == sorted AND duplicate-free: a
                # user-supplied dictionary array with repeated values must
                # fall through to the canonicalizing decode+re-unique path
                # (duplicate entries would give equal strings distinct
                # codes, splitting their groups)
                if len(sv) < 2 or bool(np.all(sv[:-1] < sv[1:])):
                    idx = col.indices
                    if null_mask is not None:
                        idx = pc.fill_null(idx, 0)
                    codes = np.asarray(
                        idx.to_numpy(zero_copy_only=False)
                    ).astype(np.int32)
                    if null_mask is not None:
                        codes = np.where(null_mask, codes, 0).astype(np.int32)
                    data[f.name] = codes
                    dicts[f.name] = Dictionary(dvals)
                    validity[f.name] = null_mask
                    continue
            if pa.types.is_dictionary(col.type):
                col = col.cast(pa.string())
            if provided is None:
                encoding[f.name] = pool.submit(
                    _encode_under_span, tracer, parent, f.name, col,
                    null_mask)
                validity[f.name] = null_mask
                continue
            values = np.asarray(col.to_numpy(zero_copy_only=False), dtype=object)
            if null_mask is not None:
                values = np.where(null_mask, values, "")
            strs = values.astype(str)
            d = provided
            # Vectorized encode: a sorted dictionary admits searchsorted with
            # an equality check for absent values; unsorted (caller-provided)
            # dictionaries fall back to the exact hash-map path.
            if len(d.values) == 0:
                codes = np.full(len(strs), -1, dtype=np.int32)
            elif d.is_sorted():
                sorted_vals = d.values.astype(str)
                pos = np.searchsorted(sorted_vals, strs)
                pos_c = np.clip(pos, 0, len(sorted_vals) - 1).astype(np.int32)
                found = sorted_vals[pos_c] == strs
                codes = np.where(found, pos_c, -1).astype(np.int32)
            else:
                idx = d.index()
                codes = np.asarray(
                    [idx.get(v, -1) for v in strs], dtype=np.int32
                )
            # a value the dictionary lacks is a NULL of this column: the
            # one case where a source without nulls still needs its mask
            found = codes >= 0
            if null_mask is not None:
                null_mask = null_mask & found
            elif not found.all():
                null_mask = found
            codes = np.where(found, codes, 0)
            data[f.name] = codes
            dicts[f.name] = d
        elif f.dtype == DataType.DATE32:
            # date32 is days since the epoch as int32 already
            days = col.cast(pa.date32()).cast(pa.int32())
            if days.null_count:
                days = pc.fill_null(days, 0)
            data[f.name] = days.to_numpy(zero_copy_only=False)
        elif f.dtype == DataType.BOOL:
            arr = col.to_numpy(zero_copy_only=False)
            if null_mask is not None:
                arr = np.where(null_mask, np.asarray(arr, dtype=object),
                               False)
            data[f.name] = np.asarray(arr).astype(np.bool_)
        else:
            # Fill nulls inside Arrow first: pyarrow's to_numpy converts
            # nullable int columns through float64, which silently rounds
            # int64 values above 2^53 — fatal for join keys. fill_null keeps
            # the column in its native width. Timestamps flow through int64
            # epoch values (cast), dates already handled above. Real (valid)
            # NaN payloads in float columns are preserved as-is.
            if pa.types.is_timestamp(col.type):
                col = col.cast(pa.int64())
            elif pa.types.is_decimal(col.type):
                col = col.cast(pa.float64())
            if null_mask is not None:
                col = pc.fill_null(col, 0)
            arr = col.to_numpy(zero_copy_only=False)
            # Keep the column's native (wide) width here: Column.from_numpy
            # owns the narrowing and range-checks it loudly in tpu precision
            # mode. An astype here would wrap int64 join keys / timestamps
            # silently before the guard could see the wide dtype.
            if np.issubdtype(np.asarray(arr).dtype, np.integer):
                data[f.name] = np.asarray(arr)
            else:
                data[f.name] = np.asarray(arr).astype(
                    f.dtype.logical_np_dtype, copy=False
                )
        validity[f.name] = null_mask


def read_parquet(
    paths: str | Sequence[str],
    columns: Optional[Sequence[str]] = None,
    capacity: Optional[int] = None,
    dictionaries: Optional[dict[str, Dictionary]] = None,
) -> Table:
    """Read parquet file(s) into a single padded device Table. The files
    are one task's share of a scan, so a nullable field keeps its validity
    array whether these files hold a NULL or not: every task of the stage
    makes the same tree (`arrow_to_host_columns`, `Table.empty`)."""
    import pyarrow.parquet as pq
    import pyarrow as pa

    if isinstance(paths, (str, os.PathLike)):
        paths = [paths]
    tables = [pq.read_table(p, columns=list(columns) if columns else None) for p in paths]
    arrow_table = pa.concat_tables(tables) if len(tables) > 1 else tables[0]
    return arrow_to_table(arrow_table, capacity=capacity,
                          dictionaries=dictionaries,
                          mask_nullable_fields=True)


def arrow_to_table(
    arrow_table,
    capacity: Optional[int] = None,
    dictionaries: Optional[dict[str, Dictionary]] = None,
    tracer=spans.NULL_TRACER,
    mask_nullable_fields: bool = False,
) -> Table:
    """Arrow table -> padded device Table; which columns get a validity
    array is `arrow_to_host_columns`' rule. The ``h2d`` span says what went
    up: ``bytes``, and ``masks``, the validity arrays among them."""
    data, validity, dicts, schema = arrow_to_host_columns(
        arrow_table, dictionaries, tracer, mask_nullable_fields)
    n = arrow_table.num_rows
    cap = capacity or round_up_pow2(max(n, 1))
    with tracer.span("h2d", "h2d") as hsp:
        table = Table.from_numpy(
            data, schema, capacity=cap, validity=validity, dictionaries=dicts
        )
        if tracer.active:
            hsp.set(bytes=spans.table_nbytes(table),
                    masks=table.validity_masks,
                    rows=n, capacity=cap)
    return table


class Partitions:
    """A table given as row partitions, pyarrow Tables of one schema:
    `SessionContext.register_arrow` registers partition i resident on
    ``jax.devices()[i]`` (DataFusion's `MemTable` holds its partitions the
    same way). ``num_rows`` and ``schema`` are the whole table's."""

    def __init__(self, tables):
        self.tables = tuple(tables)
        if not self.tables:
            raise ValueError("Partitions needs at least one table")
        schema = self.tables[0].schema
        if any(not t.schema.equals(schema) for t in self.tables):
            raise ValueError("the partitions of a table differ in schema")

    @classmethod
    def split(cls, arrow_table, parts: int) -> "Partitions":
        """``arrow_table`` cut into ``parts`` row ranges of
        ``ceil(rows / parts)`` rows (the last one the rest), zero-copy."""
        per = -(-arrow_table.num_rows // parts)
        return cls(arrow_table.slice(i * per, per) for i in range(parts))

    @property
    def num_rows(self) -> int:
        return sum(t.num_rows for t in self.tables)

    @property
    def schema(self):
        return self.tables[0].schema


def _encode_partitions_under_span(tracer, parent, name, cols) -> tuple:
    """A string column given a chunk a partition -> (int32 codes a
    partition, the distinct non-null values of all of them as ONE sorted
    Arrow array: the dictionary every partition's codes index), under one
    ``encode`` span. Each partition is hash-encoded alone and its own
    dictionary's values are looked up in the sorted union: one string
    array of every partition's rows can pass the 2 GiB its 32-bit offsets
    address (ClickBench's URL column of 100M rows does)."""
    import pyarrow as pa
    import pyarrow.compute as pc

    with tracer.span("encode", "encode", parent=parent, column=name) as span:
        encoded = [pc.dictionary_encode(col) for col in cols]
        values = pc.unique(pa.chunked_array(
            [e.dictionary for e in encoded], type=cols[0].type))
        values = values.take(pc.sort_indices(values))
        codes = []
        for col, enc in zip(cols, encoded):
            if len(enc.dictionary) == 0:  # every row NULL, or none
                codes.append(np.zeros(len(col), dtype=np.int32))
                continue
            rank = pc.index_in(enc.dictionary, value_set=values).to_numpy(
                zero_copy_only=False).astype(np.int32)
            idx = pc.fill_null(enc.indices, 0) if col.null_count else (
                enc.indices)
            part = rank[idx.to_numpy(zero_copy_only=False)]
            if col.null_count:
                part = np.where(np.asarray(col.is_valid()), part,
                                0).astype(np.int32, copy=False)
            codes.append(part)
        if tracer.active:
            span.set(rows=sum(len(c) for c in cols), distinct=len(values),
                     bytes=sum(c.nbytes for c in cols))
    return codes, values


def arrow_to_partitions(
    partitions: Partitions,
    devices: Sequence,
    capacity: Optional[int] = None,
    tracer=spans.NULL_TRACER,
) -> PartitionedTable:
    """`Partitions` -> a `PartitionedTable` whose partition i is resident on
    ``devices[i]``. A string column is encoded once over every partition,
    under one ``encode`` span, into ONE Dictionary that every partition
    shares; a column that holds a NULL in any partition has a validity
    array in all of them, so that the partitions agree in tree structure.
    Then each partition's rows, converted on the host alone
    (`arrow_to_host_columns`) and padded to one capacity, go to their
    device, a thread a partition, under an ``h2d`` span a partition
    (``device``, ``bytes``, ``rows``, ``capacity``, ``masks``)."""
    tables = partitions.tables
    if len(tables) > len(devices):
        raise ValueError(
            f"a table of {len(tables)} partitions needs a device "
            f"a partition; {len(devices)} device(s) are available")
    schema = schema_from_arrow(partitions.schema)
    strings = [f.name for f in schema.fields if f.dtype == DataType.STRING]
    parent = tracer.current_id()
    with ThreadPoolExecutor(_ENCODE_THREADS) as pool:
        encoding = {
            name: pool.submit(
                _encode_partitions_under_span, tracer, parent, name,
                [t.column(name).combine_chunks() for t in tables])
            for name in strings}
        encoded = {name: future.result() for name, future in encoding.items()}
    dicts = {name: Dictionary.from_arrow(values)
             for name, (_, values) in encoded.items()}
    # the columns with a validity array: `arrow_to_host_columns`' rule for
    # the whole table, in every partition alike
    masked = {f.name for f in schema.fields
              if partitions.num_rows == 0
              or any(t.column(f.name).null_count for t in tables)}
    rows = tuple(t.num_rows for t in tables)
    cap = capacity or round_up_pow2(max(max(rows), 1))

    def place(i: int) -> Table:
        table, device = tables[i], devices[i]
        data, found, _, _ = arrow_to_host_columns(
            table.drop_columns(strings))
        for name in strings:
            data[name] = encoded[name][0][i]
            column = table.column(name)
            if column.null_count:
                found[name] = np.asarray(column.combine_chunks().is_valid())
        validity = {name: found[name] if name in found
                    else np.ones(table.num_rows, dtype=np.bool_)
                    for name in masked}
        with tracer.span("h2d", "h2d", parent=parent,
                         device=str(device)) as hsp:
            part = Table.from_numpy(data, schema, capacity=cap,
                                    validity=validity, dictionaries=dicts,
                                    device=device)
            if tracer.active:
                hsp.set(bytes=spans.table_nbytes(part),
                        masks=part.validity_masks, rows=table.num_rows,
                        capacity=cap)
        return part

    with ThreadPoolExecutor(len(tables)) as pool:
        parts = tuple(pool.map(place, range(len(tables))))
    return PartitionedTable(parts, rows)


def table_to_arrow(table: Table, dictionary_gc: bool = False,
                   logical_metadata: bool = False):
    """Device Table -> Arrow table (host materialization).

    Both shapes get the table's buffers through `ops/table.py
    fetch_host_buffers`: copied together in one round trip and cut to the
    rows on the host, or in two for a large result. A traced fetch's span
    says so: ``transfers`` the buffers copied, ``round_trips`` the times
    the device was waited on.

    Default shape decodes strings to plain arrays (pandas-friendly). The
    WIRE shape (``dictionary_gc=True``) instead ships string columns as
    dictionary arrays whose dictionaries are garbage-collected to only the
    values the live rows reference — the reference's dictionary/view-array
    GC before Flight encode (`impl_execute_task.rs:244-274`): a slice
    referencing 10 of a 100k-value dictionary ships 10 values, and
    repeated strings ship as int32 codes. The GC'd subset of a sorted
    dictionary stays sorted, so the receiver adopts it directly
    (arrow_to_host_columns fast path). ``logical_metadata=True`` attaches
    the columns' LOGICAL dtypes as schema metadata: physical arrow widths
    narrow in tpu precision mode (FLOAT64 logical -> f32 device data), and
    a consumer inferring dtypes from the wire would otherwise disagree
    with a same-worker bypass pull of the identical table."""
    if dictionary_gc:  # the wire shape is the codec's work, not a fetch
        n, buffers, _round_trips = fetch_host_buffers(table)
        return _table_to_arrow(table, n, buffers, True, logical_metadata)
    with spans.fetch_call(table) as call:
        n, buffers, round_trips = fetch_host_buffers(table)
        out = _table_to_arrow(table, n, buffers, False, logical_metadata)
        if call.tracer.active:
            call.span.set(**fetch_counters(table, n, round_trips))
        return out


def _table_to_arrow(table: Table, n: int, buffers: list,
                    dictionary_gc: bool, logical_metadata: bool):
    """``buffers``: `fetch_host_buffers`' (data, validity) a column, cut
    to the table's ``n`` rows."""
    import pyarrow as pa

    arrays = []
    names = []
    for name, col, (vals, validity) in zip(table.names, table.columns,
                                           buffers):
        mask = None if validity is None else ~validity
        if col.dtype == DataType.STRING and dictionary_gc:
            assert col.dictionary is not None
            codes = vals.astype(np.int64)
            valid = np.ones(n, dtype=bool) if mask is None else ~mask
            live = valid & (codes >= 0) & (
                codes < len(col.dictionary)
            )
            used = np.unique(codes[live])
            subset = col.dictionary.values[used]
            fill = used[0] if len(used) else 0
            new_codes = np.searchsorted(
                used, np.where(live, codes, fill)
            ).astype(np.int32)
            arrays.append(pa.DictionaryArray.from_arrays(
                pa.array(new_codes, mask=~live),
                pa.array(subset.tolist(), type=pa.string()),
            ))
        elif col.dtype == DataType.STRING:
            assert col.dictionary is not None
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
        names.append(name)
    if dictionary_gc:
        # the wire says which columns carry a validity array, as the
        # field's ``nullable``: the receiver (`codec.decode_table`) makes a
        # mask for exactly those, so a table crosses the wire, or comes
        # back from a spill, with the tree structure it had
        out = pa.Table.from_arrays(arrays, schema=pa.schema([
            pa.field(name, arr.type, nullable=col.validity is not None)
            for name, arr, col in zip(names, arrays, table.columns)
        ]))
    else:
        out = pa.table(dict(zip(names, arrays)))
    if logical_metadata:
        import json as _json

        out = out.replace_schema_metadata({
            b"dftpu_logical": _json.dumps({
                name: col.dtype.value
                for name, col in zip(table.names, table.columns)
            }).encode()
        })
    return out
