"""Device-resident columnar batches (the Arrow `RecordBatch` analogue).

The reference engine streams Arrow `RecordBatch`es between operators and across
the network (see `/root/reference/src/worker/impl_execute_task.rs` Flight
encode loop). On TPU, XLA requires static shapes, so the equivalent unit here
is a **padded** columnar batch:

- every column is a fixed-`capacity` device array (power-of-two friendly),
- the number of live rows is a *traced* scalar ``num_rows`` (so filters and
  joins can change it under ``jit`` without recompiling),
- rows at index >= num_rows are garbage and masked out by ``row_mask()``,
- null semantics ride in per-column validity bitmaps (bool arrays),
- strings live as int32 dictionary codes; the dictionaries themselves stay on
  the host in a registry keyed by small ints so they never enter jit cache
  keys (the analogue of the reference's dictionary GC before the wire,
  `impl_execute_task.rs:244-274`: the device only ever sees compact codes).

`Table` and `Column` are registered pytrees, so they flow through ``jit``,
``shard_map``, ``lax.scan`` etc. unchanged.
"""

from __future__ import annotations

import functools
import itertools
import os
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from datafusion_distributed_tpu import spans
from datafusion_distributed_tpu.schema import DataType, Field, Schema

# ---------------------------------------------------------------------------
# Host-side dictionary registry
# ---------------------------------------------------------------------------

import threading
import weakref

_DICT_COUNTER = itertools.count()
# Weak registry: a Dictionary lives as long as some Column references it
# (the analogue of the reference's dictionary GC before the wire — unused
# dictionaries must not accumulate in a long-running worker process).
_DICT_REGISTRY: "weakref.WeakValueDictionary[int, Dictionary]" = (
    weakref.WeakValueDictionary()
)
# (sorted input dict ids) -> union Dictionary; see unify_dictionaries
_UNION_DICT_CACHE: dict = {}
_DICT_CACHE_LOCK = threading.Lock()


_PIN_DEPTH = 0  # guarded by _DICT_CACHE_LOCK
_PINNED: dict = {}  # id(cache) -> set of keys untouchable by eviction


import contextlib


@contextlib.contextmanager
def pin_dictionary_caches():
    """Entries touched while ANY pin context is active are exempt from LRU
    eviction until the last context exits. IsolatedArmExec wraps its probe +
    lax.cond branch traces in this: LRU recency alone cannot protect an
    entry from heavy cross-thread churn between the two traces, and a
    re-minted Dictionary diverges the branches' pytree metadata (loud trace
    error). Nesting-safe; caches may transiently exceed their cap while
    everything in them is pinned."""
    global _PIN_DEPTH
    with _DICT_CACHE_LOCK:
        _PIN_DEPTH += 1
    try:
        yield
    finally:
        with _DICT_CACHE_LOCK:
            _PIN_DEPTH -= 1
            if _PIN_DEPTH == 0:
                _PINNED.clear()


def lru_get_or_create(cache: dict, key, mint, cap: int):
    """Thread-safe get-or-mint with LRU eviction (python dicts preserve
    insertion order; move-to-end on hit). Shared by the dictionary
    memoization caches: identity stability across re-traces requires that
    a hit NEVER returns a different object than a concurrent or recent
    call for the same key, and that eviction only removes cold entries
    (never one pinned by an in-progress trace, see pin_dictionary_caches)."""
    with _DICT_CACHE_LOCK:
        if key in cache:
            val = cache.pop(key)
            cache[key] = val  # move to end = most recently used
        else:
            val = mint()
            cache[key] = val
        if _PIN_DEPTH > 0:
            _PINNED.setdefault(id(cache), set()).add(key)
        pinned = _PINNED.get(id(cache), ())
        while len(cache) > cap:
            victim = next((k for k in cache if k not in pinned), None)
            if victim is None:
                break  # everything live-pinned: transient over-cap is fine
            cache.pop(victim)
        return val


class Dictionary:
    """A host-side sorted string dictionary, identified by a small int.

    Identity (and therefore jit-cache equality) is by ``dict_id``, so huge
    dictionaries cost nothing at trace time. Dictionaries are sorted at
    construction so that code order == lexicographic order; this lets ORDER
    BY / MIN / MAX / comparisons run directly on int32 codes on device.

    The values are a 1-D numpy array of ``str`` objects, or (`from_arrow`)
    a pyarrow string array that is kept as it is: a column of millions of
    distinct strings (TPC-H's comments) then costs no Python object a value
    at registration. ``values`` decodes it into the object array on first
    use, and every reader is served from that as before; the length, the
    sort check, ``code_of`` and ``decode`` read the Arrow array itself.
    """

    __slots__ = ("dict_id", "_values", "_arrow", "_index", "__weakref__")

    def __init__(self, values: Optional[np.ndarray], arrow=None):
        if arrow is None:
            values = np.asarray(values, dtype=object)
            if values.ndim != 1:
                raise ValueError("dictionary must be 1-D")
        self.dict_id = next(_DICT_COUNTER)
        self._values: Optional[np.ndarray] = values
        self._arrow = arrow
        self._index: Optional[dict] = None
        _DICT_REGISTRY[self.dict_id] = self

    @staticmethod
    def from_strings(values: Iterable[str]) -> "Dictionary":
        return Dictionary(np.asarray(list(values), dtype=object))

    @staticmethod
    def from_arrow(values) -> "Dictionary":
        """Over a pyarrow string array with no nulls, not copied."""
        return Dictionary(None, arrow=values)

    @property
    def values(self) -> np.ndarray:
        if self._values is None:
            self._values = self._arrow.to_numpy(zero_copy_only=False)
        return self._values

    def __len__(self) -> int:
        return len(self._arrow if self._values is None else self._values)

    def code_of(self, value: str) -> int:
        """Host-side lookup: string -> code, or -1 if absent."""
        if self._values is None:
            import pyarrow.compute as pc

            return pc.index(self._arrow, value).as_py()
        return self.index().get(value, -1)

    def index(self) -> dict:
        """Cached str -> code map."""
        if self._index is None:
            self._index = {v: i for i, v in enumerate(self.values)}
        return self._index

    def decode(self, codes: np.ndarray) -> np.ndarray:
        out = np.empty(len(codes), dtype=object)
        valid = (codes >= 0) & (codes < len(self))
        if self._values is None:
            # only the rows asked for become Python strings
            import pyarrow as pa

            out[valid] = self._arrow.take(
                pa.array(codes[valid])).to_numpy(zero_copy_only=False)
        else:
            out[valid] = self._values[codes[valid]]
        out[~valid] = None
        return out

    def is_sorted(self) -> bool:
        if len(self) < 2:
            return True
        if self._values is None:
            import pyarrow.compute as pc

            return pc.all(pc.less_equal(self._arrow[:-1],
                                        self._arrow[1:])).as_py()
        v = self._values.astype(str)
        return bool(np.all(v[:-1] <= v[1:]))

    def __eq__(self, other: object) -> bool:
        return isinstance(other, Dictionary) and other.dict_id == self.dict_id

    def __hash__(self) -> int:
        return hash(("Dictionary", self.dict_id))

    def __repr__(self) -> str:
        return f"Dictionary(id={self.dict_id}, n={len(self)})"


def get_dictionary(dict_id: int) -> Dictionary:
    return _DICT_REGISTRY[dict_id]


def build_sorted_dictionary(values: Iterable[str]) -> tuple[Dictionary, dict]:
    """Build a sorted dictionary from unique values; returns (dict, str->code)."""
    uniq = sorted(set(values))
    d = Dictionary.from_strings(uniq)
    return d, {v: i for i, v in enumerate(uniq)}


# ---------------------------------------------------------------------------
# Column
# ---------------------------------------------------------------------------


@dataclass
class Column:
    """A single padded device column.

    ``data``: [capacity] jnp array (dtype per DataType; strings = int32 codes)
    ``validity``: [capacity] bool jnp array, or None: no row is NULL. None
    reads as all true everywhere (`valid_mask`), costs no buffer and no
    pass, and is what ingestion gives a column without NULLs.
    ``dtype``/``dictionary``: static metadata (pytree aux).
    """

    data: jnp.ndarray
    validity: Optional[jnp.ndarray]
    dtype: DataType
    dictionary: Optional[Dictionary] = None

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.data, self.validity), (self.dtype, self.dictionary)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, validity = children
        dtype, dictionary = aux
        return cls(data=data, validity=validity, dtype=dtype, dictionary=dictionary)

    # -- constructors -------------------------------------------------------
    @staticmethod
    def from_numpy(
        values: np.ndarray,
        dtype: DataType,
        capacity: int,
        validity: Optional[np.ndarray] = None,
        dictionary: Optional[Dictionary] = None,
        device=None,
    ) -> "Column":
        """``values`` padded to ``capacity`` on the device: the default
        one, uncommitted, or ``device``, committed to it. ``validity``
        None makes a column WITHOUT a validity array ("no NULLs": what
        `io/parquet.py arrow_to_host_columns` hands over for a column whose
        source holds none); an array is padded with False and uploaded
        beside the data, one byte a slot."""
        def upload(buf):
            # uncommitted on the default device (shard_map re-places scan
            # inputs freely), in one H2D copy
            return jnp.asarray(buf) if device is None else jax.device_put(
                buf, device)

        n = len(values)
        if n > capacity:
            raise ValueError(f"{n} values > capacity {capacity}")
        np_dtype = np.dtype(dtype.np_dtype)
        vals = np.asarray(values)
        # tpu precision mode stores logical 64-bit ints as int32; narrowing
        # must be loud, never a silent wrap (join keys at huge scale factors
        # are the realistic overflow case — see precision.py).
        if (
            n
            and np.issubdtype(vals.dtype, np.integer)
            and np.issubdtype(np_dtype, np.integer)
            and vals.dtype.itemsize > np_dtype.itemsize
        ):
            info = np.iinfo(np_dtype)
            lo, hi = vals.min(), vals.max()
            if lo < info.min or hi > info.max:
                raise OverflowError(
                    f"int values [{lo}, {hi}] exceed {np_dtype} device "
                    "storage; run with DFTPU_PRECISION=x64 for 64-bit keys"
                )
        if n == capacity and vals.ndim == 1 and vals.dtype == np_dtype:
            # a buffer that already satisfies the capacity (the wire decode
            # path when table_caps == live rows) enters the device as-is —
            # no zero-fill + pad copy (ownership transfers: the caller
            # must not mutate it afterwards)
            data = upload(np.ascontiguousarray(vals))
        else:
            buf = np.zeros(capacity, dtype=np_dtype)
            buf[:n] = vals
            data = upload(buf)
        col_validity = None
        if validity is not None:
            v = np.zeros(capacity, dtype=np.bool_)
            v[:n] = validity
            col_validity = upload(v)
        return Column(data, col_validity, dtype, dictionary)

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    def valid_mask(self, capacity: Optional[int] = None) -> jnp.ndarray:
        """Per-row null mask (True = non-null). Does NOT account for num_rows."""
        if self.validity is not None:
            return self.validity
        return jnp.ones(capacity or self.capacity, dtype=jnp.bool_)

    def gather(self, idx: jnp.ndarray) -> "Column":
        data = jnp.take(self.data, idx, axis=0)
        validity = (
            jnp.take(self.validity, idx, axis=0) if self.validity is not None else None
        )
        return Column(data, validity, self.dtype, self.dictionary)

    def with_validity(self, validity: Optional[jnp.ndarray]) -> "Column":
        return Column(self.data, validity, self.dtype, self.dictionary)


jax.tree_util.register_pytree_node(
    Column,
    lambda c: c.tree_flatten(),
    Column.tree_unflatten,
)


def scoped(name: str):
    """Run the kernel under `jax.named_scope(name)`: every XLA op it
    lowers to carries the name in its ``op_name`` metadata, which is where
    a profile of the compiled program finds it again. Trace time only;
    nothing at run time, and the optimized HLO differs in metadata alone.
    The kernels' names (lower-case, dotted) are listed in PERF.md."""
    def decorate(fn):
        @functools.wraps(fn)
        def kernel(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return kernel
    return decorate


# ---------------------------------------------------------------------------
# Table
# ---------------------------------------------------------------------------


@dataclass
class Table:
    """A padded columnar batch: named columns + traced live-row count."""

    names: tuple[str, ...]
    columns: tuple[Column, ...]
    num_rows: jnp.ndarray  # traced int32 scalar

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.columns, self.num_rows), self.names

    @classmethod
    def tree_unflatten(cls, names, children):
        columns, num_rows = children
        return cls(names=names, columns=tuple(columns), num_rows=num_rows)

    # -- construction -------------------------------------------------------
    @staticmethod
    def make(columns: dict[str, Column], num_rows) -> "Table":
        names = tuple(columns.keys())
        cols = tuple(columns.values())
        caps = {c.capacity for c in cols}
        if len(caps) > 1:
            raise ValueError(f"column capacities differ: {caps}")
        return Table(names, cols, jnp.asarray(num_rows, dtype=jnp.int32))

    @staticmethod
    def from_numpy(
        data: dict[str, np.ndarray],
        schema: Schema,
        capacity: Optional[int] = None,
        validity: Optional[dict[str, np.ndarray]] = None,
        dictionaries: Optional[dict[str, Dictionary]] = None,
        device=None,
    ) -> "Table":
        """Build a device Table from host arrays (string columns must already
        be int32 codes with a matching entry in ``dictionaries``), on the
        default device or, committed, on ``device`` (`Column.from_numpy`)."""
        if not data:
            raise ValueError("from_numpy needs at least one column")
        n = len(next(iter(data.values())))
        cap = capacity if capacity is not None else max(1, _round_up(n))
        cols: dict[str, Column] = {}
        for f in schema.fields:
            vals = data[f.name]
            if len(vals) != n:
                raise ValueError(f"column {f.name} length {len(vals)} != {n}")
            v = validity.get(f.name) if validity else None
            d = dictionaries.get(f.name) if dictionaries else None
            if f.dtype == DataType.STRING and d is None:
                raise ValueError(f"string column {f.name} needs a dictionary")
            cols[f.name] = Column.from_numpy(vals, f.dtype, cap, v, d, device)
        if device is None:
            return Table.make(cols, n)
        return Table(tuple(cols), tuple(cols.values()),
                     jax.device_put(np.int32(n), device))

    @staticmethod
    def empty(schema: Schema, capacity: int, dictionaries=None) -> "Table":
        cols = {}
        for f in schema.fields:
            d = dictionaries.get(f.name) if dictionaries else None
            cols[f.name] = Column(
                jnp.zeros(capacity, dtype=f.dtype.np_dtype),
                jnp.zeros(capacity, dtype=jnp.bool_) if f.nullable else None,
                f.dtype,
                d,
            )
        return Table.make(cols, 0)

    # -- introspection ------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else 0

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def column(self, name: str) -> Column:
        try:
            return self.columns[self.names.index(name)]
        except ValueError:
            raise KeyError(f"no column {name!r}; have {list(self.names)}") from None

    def __contains__(self, name: str) -> bool:
        return name in self.names

    def as_dict(self) -> dict[str, Column]:
        return dict(zip(self.names, self.columns))

    @property
    def validity_masks(self) -> int:
        """How many columns carry a validity array (None reads as "no
        NULLs" and costs no buffer: `Column.from_numpy`)."""
        return sum(c.validity is not None for c in self.columns)

    def schema(self) -> Schema:
        return Schema(
            [
                Field(n, c.dtype, nullable=c.validity is not None)
                for n, c in zip(self.names, self.columns)
            ]
        )

    def row_mask(self) -> jnp.ndarray:
        """[capacity] bool: True for live (non-padding) rows."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows

    # -- transforms (all jit-safe) ------------------------------------------
    def select(self, names: Sequence[str]) -> "Table":
        return Table(
            tuple(names), tuple(self.column(n) for n in names), self.num_rows
        )

    def rename(self, mapping: dict[str, str]) -> "Table":
        names = tuple(mapping.get(n, n) for n in self.names)
        return Table(names, self.columns, self.num_rows)

    def with_column(self, name: str, col: Column) -> "Table":
        d = self.as_dict()
        d[name] = col
        return Table(tuple(d.keys()), tuple(d.values()), self.num_rows)

    @scoped("table.gather")
    def gather(self, idx: jnp.ndarray, num_rows) -> "Table":
        cols = tuple(c.gather(idx) for c in self.columns)
        return Table(self.names, cols, jnp.asarray(num_rows, dtype=jnp.int32))

    @scoped("table.compact")
    def compact(self, keep: jnp.ndarray) -> "Table":
        """Select rows where ``keep`` is True, packed to the front (jit-safe).

        ``keep`` is a [capacity] bool mask; padding rows must already be False
        in it. This is the TPU analogue of Arrow's ``filter`` kernel: a
        static-size ``nonzero`` + gather keeps shapes fixed while num_rows
        becomes the popcount.
        """
        keep = keep & self.row_mask()
        (idx,) = jnp.nonzero(keep, size=self.capacity, fill_value=0)
        n = jnp.sum(keep, dtype=jnp.int32)
        # rows past n are copies of row 0, validity included: garbage by
        # the contract (data beyond num_rows may hold anything)
        return self.gather(idx, n)

    def head(self, limit: int | jnp.ndarray) -> "Table":
        n = jnp.minimum(self.num_rows, jnp.asarray(limit, dtype=jnp.int32))
        return Table(self.names, self.columns, n)

    def slice_rows(self, lo: int, count: int) -> "Table":
        """Row-range slice [lo, lo+count) as a compact table (NOT jit-safe:
        static python offsets). The chunking primitive of the streaming
        data plane — each chunk's buffers are views of this table, so
        slicing is free until a consumer materializes the chunk."""
        n = int(self.num_rows)
        lo = max(0, min(lo, n))
        count = max(0, min(count, n - lo))
        cap = max(_round_up(count), 8)
        cols = tuple(
            Column(
                c.data[lo:lo + cap],
                c.validity[lo:lo + cap] if c.validity is not None else None,
                c.dtype, c.dictionary,
            )
            for c in self.columns
        )
        # short tail: buffer views may be < cap; pad via head-room contract
        # (rows past num_rows are garbage by contract, so a short buffer is
        # only a problem for fixed-capacity consumers; re-pad those lazily)
        return Table(self.names, cols, jnp.asarray(count, dtype=jnp.int32))

    # -- host materialization (NOT jit-safe) --------------------------------
    def to_numpy(self, decode_strings: bool = True) -> dict[str, np.ndarray]:
        _n, buffers, _round_trips = fetch_host_buffers(self)
        out: dict[str, np.ndarray] = {}
        for name, col, (vals, mask) in zip(self.names, self.columns,
                                           buffers):
            if col.dtype == DataType.STRING and decode_strings:
                assert col.dictionary is not None
                vals = col.dictionary.decode(vals)
            if mask is not None:
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

    def to_pandas(self):
        import pandas as pd

        with spans.fetch_call(self) as call:
            n, buffers, round_trips = fetch_host_buffers(self)
            cols = {}
            for name, col, (vals, mask) in zip(self.names, self.columns,
                                               buffers):
                if col.dtype == DataType.STRING:
                    assert col.dictionary is not None
                    vals = col.dictionary.decode(vals)
                s = pd.Series(vals)
                if mask is not None:
                    s = s.where(pd.Series(mask), other=None)
                cols[name] = s
            if call.tracer.active:
                call.span.set(**fetch_counters(self, n, round_trips))
            return pd.DataFrame(cols)

    def __repr__(self) -> str:
        cols = ", ".join(
            f"{n}:{c.dtype.value}" for n, c in zip(self.names, self.columns)
        )
        return f"Table(capacity={self.capacity}, cols=[{cols}])"


jax.tree_util.register_pytree_node(
    Table,
    lambda t: t.tree_flatten(),
    Table.tree_unflatten,
)


@dataclass(frozen=True)
class PartitionedTable:
    """A table registered as row partitions (`io/parquet.py
    arrow_to_partitions`): ``parts[i]`` is a Table resident on device i,
    every part of one capacity and one tree structure, a string column's
    ONE Dictionary shared by all of them, so that equal strings are equal
    codes on every device. ``rows``: each part's row count, on the host.
    What a catalog asks of a table it answers for the whole (`column` is
    part 0's: its dictionary and its validity array, or none, are every
    part's)."""

    parts: tuple
    rows: tuple

    @property
    def num_rows(self) -> int:
        return sum(self.rows)

    @property
    def names(self) -> tuple:
        return self.parts[0].names

    @property
    def capacity(self) -> int:
        return self.parts[0].capacity

    @property
    def validity_masks(self) -> int:
        return self.parts[0].validity_masks

    def schema(self) -> Schema:
        return self.parts[0].schema()

    def column(self, name: str) -> Column:
        return self.parts[0].column(name)

    def __contains__(self, name: str) -> bool:
        return name in self.parts[0]


def _round_up(n: int, multiple: int = 8) -> int:
    """Round up to a TPU-lane-friendly size (min sublane granularity)."""
    return ((n + multiple - 1) // multiple) * multiple


def round_up_pow2(n: int, minimum: int = 8) -> int:
    cap = minimum
    while cap < n:
        cap *= 2
    return cap


# ---------------------------------------------------------------------------
# Zero-copy host data plane: view-based staging primitives
# ---------------------------------------------------------------------------
#
# The distributed data plane moves tables between stages as host-side slices
# (chunk streams, per-destination shuffle partitions, broadcast fan-out).
# Doing that with eager jax ops costs one device dispatch + buffer copy per
# slice; these primitives instead rebind staged tables to HOST numpy buffers
# once (`host_view` — zero-copy on the CPU backend, one unavoidable D2H on a
# real accelerator), after which every slice is a numpy VIEW (`slice_view`)
# and contiguous views of one buffer reassemble without a copy
# (`concat_tables`' host fast path). Buffers are immutable by contract —
# every consumer shares them by reference.


def parse_bool_knob(value) -> bool:
    """One parser for boolean `SET distributed.*` knobs ("off"/"false"/
    "0"/"" are false, everything else truthy) — SET-time validation
    (sql/context.py) and runtime interpretation share it so the accepted
    spellings cannot drift apart."""
    if isinstance(value, str):
        return value.strip().lower() not in ("0", "false", "off", "")
    return bool(value)


def zero_copy_enabled(config: Optional[dict] = None) -> bool:
    """Effective `SET distributed.zero_copy` (default ON). The env override
    ``DFTPU_ZERO_COPY`` wins over session config — the A/B escape hatch for
    whole-suite comparison runs without touching session options."""
    env = os.environ.get("DFTPU_ZERO_COPY")
    if env is not None:
        return parse_bool_knob(env)
    return parse_bool_knob((config or {}).get("zero_copy", True))


def fetch_counters(table: "Table", rows: int, round_trips: int) -> dict:
    """What a result fetch (`to_pandas`, `table_to_arrow`) moved:
    ``transfers``, the buffers copied from a device to the host (the row
    count, then each column's data and validity: one each that lives on a
    device), their ``bytes`` cut to ``rows``, and ``round_trips``, the
    times the fetch blocked on the device for them
    (`fetch_host_buffers`)."""
    on_device = [b for b in _fetched_buffers(table)
                 if isinstance(b, jax.Array)]
    return {
        "transfers": len(on_device),
        "round_trips": round_trips,
        "bytes": sum(
            b.dtype.itemsize * (min(rows, b.shape[0]) if b.ndim else 1)
            for b in on_device
        ),
        "rows": rows,
    }


def _fetched_buffers(table: "Table") -> list:
    """The buffers a fetch brings to the host: the row count, then each
    column's data and, where it has one, validity."""
    buffers = [table.num_rows]
    for col in table.columns:
        buffers.append(col.data)
        if col.validity is not None:
            buffers.append(col.validity)
    return buffers


def _column_buffers(table: "Table", host) -> list:
    """`_fetched_buffers`' order read back: ``host`` holds the buffers
    after the row count; -> (data, validity or None) a column."""
    host = iter(host)
    return [
        (next(host), next(host) if col.validity is not None else None)
        for col in table.columns
    ]


#: A result whose device buffers together hold at most this many bytes is
#: copied to the host whole, in one round trip, and cut to its rows there;
#: a larger one costs a second round trip (the row count first) so that
#: only its live rows cross. On the v5e (my chip runs, PERF.md, PR 35), ms
#: a fetch of fresh buffers holding 4 live rows, medians of 15-25: "loop"
#: is what the three fetches did before (a slice and a pull a buffer),
#: "whole" and "two-step" the two paths of `fetch_host_buffers`:
#:   buffers' bytes  4 KiB  64 KiB  1 MiB  4 MiB  8 MiB  16 MiB  32 MiB  64 MiB
#:   q1's shape: 10 columns, 20 buffers with the count
#:     loop           24.1    23.7   23.7   22.9   24.3    24.8    25.3    24.6
#:     whole          1.76    1.71   1.83   1.86   2.10    2.23    3.25    5.74
#:     two-step       6.80    6.65   6.76   6.57   7.06    6.98    7.20    7.10
#:   2 columns, 4 buffers
#:     loop           4.03       -   3.97      -   3.98    4.09    4.19    4.73
#:     whole          0.54       -   0.62      -   1.42    2.20    3.99    33.9
#:     two-step       1.67       -   1.68      -   1.69    1.67    1.76    2.15
#:   40 columns, 80 buffers
#:     loop          102.3       -  100.1      -  105.6   103.5    97.8    97.5
#:     whole          6.56       -   6.86      -   7.31    7.62    7.21    7.48
#:     two-step       26.5       -   26.4      -   27.6    28.0    25.9    26.1
#: A whole pull costs ~0.085 ms a buffer and its bytes (8 GB/s, until one
#: buffer passes some 20 MB: then 2 GB/s; another machine read 22.4 for
#: q1's shape at 64 MiB), a slice ~0.33 ms a buffer whatever its size, a
#: round trip 0.4. So the two cross where the bytes outweigh the slices:
#: near 10 MiB with 4 buffers, past 64 MiB with 20, nowhere measured with
#: 80. 8 MiB is the largest power of two at which no shape loses (at 16
#: MiB two columns pay 0.5 ms), and both paths beat the loop at every size.
_FETCH_WHOLE_MAX_BYTES = 8 << 20


def _one_replica(buf):
    """A buffer replicated whole on several devices, as the first
    addressable device's own single-device array, so that nothing run on
    it (a slice) runs on every device and nothing gathers; a sharded, a
    single-device or a host buffer as it is."""
    if (isinstance(buf, jax.Array) and len(buf.sharding.device_set) > 1
            and buf.is_fully_replicated):
        return buf.addressable_data(0)
    return buf


def fetch_host_buffers(table: "Table"):
    """The buffers of a concrete ``table`` on the host, for every host
    materialization (`Table.to_numpy`, `Table.to_pandas`,
    `io/parquet.py table_to_arrow`).
    -> (rows, [(data, validity or None) a column], round_trips): numpy
    arrays cut to ``rows`` (views: not to be written), and the times the
    device was waited on.

    Up to `_FETCH_WHOLE_MAX_BYTES` of device buffers, one `jax.device_get`
    brings the row count and every whole buffer (every copy is started
    before the first is waited on) and the cut to ``rows`` is a numpy
    view: one round trip, and no program is run or compiled, whatever the
    row count. Over it, the row count comes first, every buffer is sliced
    to it on its device without a wait in between, and one `device_get`
    brings the slices: two round trips. Host (numpy) buffers pass through:
    a host-backed table waits on nothing."""
    buffers = [_one_replica(b) for b in _fetched_buffers(table)]
    on_device = [b for b in buffers if isinstance(b, jax.Array)]
    round_trips = 1 if on_device else 0
    if sum(b.nbytes for b in on_device) > _FETCH_WHOLE_MAX_BYTES:
        if isinstance(buffers[0], jax.Array):
            round_trips += 1
        rows = int(buffers[0])
        buffers[1:] = [
            jax.lax.slice_in_dim(b, 0, rows)
            if isinstance(b, jax.Array) and rows < b.shape[0] else b
            for b in buffers[1:]
        ]
    num_rows, *host = jax.device_get(buffers)
    rows = int(num_rows)
    columns = [
        (data[:rows], validity[:rows] if validity is not None else None)
        for data, validity in _column_buffers(table, host)
    ]
    return rows, columns, round_trips


def is_host_backed(table: Table) -> bool:
    """True when every buffer is a host numpy array and num_rows is
    concrete — the staging representation the view-based data plane can
    slice and reassemble without device dispatches or copies."""
    if isinstance(table.num_rows, jax.core.Tracer):
        return False
    for c in table.columns:
        if not isinstance(c.data, np.ndarray):
            return False
        if c.validity is not None and not isinstance(c.validity, np.ndarray):
            return False
    return True


def host_view(table: Table) -> Table:
    """Rebind a table's buffers to host numpy arrays: whole padded buffers
    (the table keeps its capacity), ``validity`` None stays None, a host
    (numpy) buffer passes through. The device buffers and the row count
    come in ONE `jax.device_get` (every copy is started before the first
    is waited on, as on `fetch_host_buffers`' whole path), whatever the
    column count: the ``d2h`` span's ``buffers`` says how many crossed,
    its ``round_trips`` (1) how often the device was waited on. WITHOUT
    copying where the backend allows: a jax CPU array shares its buffer
    with numpy, and the pull returns a readonly view; an accelerator pays
    its one unavoidable D2H here, once, instead of per slice."""
    if isinstance(table.num_rows, jax.core.Tracer):
        raise ValueError("host_view of a traced table")
    if is_host_backed(table):
        return table
    tr = spans.current()
    with tr.span("d2h", "d2h") as sp:
        buffers = [_one_replica(b) for b in _fetched_buffers(table)]
        num_rows, *host = jax.device_get(buffers)
        cols = tuple(
            Column(data, validity, c.dtype, c.dictionary)
            for c, (data, validity) in zip(
                table.columns, _column_buffers(table, host))
        )
        rows = int(num_rows)
        if tr.active:
            sp.set(bytes=spans.table_nbytes(table), rows=rows,
                   capacity=table.capacity, round_trips=1,
                   buffers=sum(isinstance(b, jax.Array) for b in buffers))
        return Table(table.names, cols, np.int32(rows))


def slice_view(table: Table, lo: int, count: int) -> Table:
    """Zero-copy row-range view [lo, lo+count) of a table: numpy views of
    the same buffers, capacity == count exactly (no pad copy). Device-backed
    tables are host-rebound first (free on CPU); traced tables fall back to
    the copying `slice_rows`."""
    if not is_host_backed(table):
        if isinstance(table.num_rows, jax.core.Tracer):
            return table.slice_rows(lo, count)
        table = host_view(table)
    n = int(table.num_rows)
    lo = max(0, min(lo, n))
    count = max(0, min(count, n - lo))
    cols = tuple(
        Column(
            c.data[lo:lo + count],
            c.validity[lo:lo + count] if c.validity is not None else None,
            c.dtype,
            c.dictionary,
        )
        for c in table.columns
    )
    return Table(table.names, cols, np.int32(count))


def _base_buffer(arr: np.ndarray):
    """Walk the numpy view chain to the owning object: an ndarray (one
    that owns its bytes, or the one `jax.device_get` hands back for a jax
    CPU buffer, whose own base is an opaque capsule), or the memoryview
    such a buffer exports to `np.asarray`."""
    base = arr
    while isinstance(base, np.ndarray) and isinstance(
            base.base, (np.ndarray, memoryview)):
        base = base.base
    return base


def _buffer_ptr(arr: np.ndarray) -> int:
    return arr.__array_interface__["data"][0]


def _buffer_extent(base) -> Optional[tuple[int, int]]:
    """(start pointer, nbytes) of an owning buffer, or None if unknowable."""
    if isinstance(base, np.ndarray):
        return _buffer_ptr(base), int(base.nbytes)
    if isinstance(base, memoryview):
        flat = np.frombuffer(base, dtype=np.uint8)
        return _buffer_ptr(flat), int(flat.nbytes)
    return None


def _merge_views(arrs: list, want_len: int):
    """Exact-length contiguous 1-D views that abut in ONE base buffer ->
    a single view of length ``want_len`` over that buffer (reading past the
    last view only if the base has the room), else None."""
    nz = [a for a in arrs if len(a)]
    if not nz:
        return None
    base = _base_buffer(nz[0])
    start = _buffer_ptr(nz[0])
    end = start + nz[0].nbytes
    for a in nz[1:]:
        if _base_buffer(a) is not base or _buffer_ptr(a) != end:
            return None
        end += a.nbytes
    itemsize = nz[0].itemsize
    have = (end - start) // itemsize
    if want_len > have:
        extent = _buffer_extent(base)
        if extent is None:
            return None
        b_start, b_nbytes = extent
        if start + want_len * itemsize > b_start + b_nbytes:
            return None  # base buffer too short for the requested capacity
    return np.lib.stride_tricks.as_strided(
        nz[0], shape=(want_len,), strides=nz[0].strides
    )


def _concat_host(tables: Sequence[Table], names, total_cap: int):
    """Host (numpy) concat fast path: one memcpy per column at memory
    bandwidth instead of one eager device scatter per input — and when the
    inputs are contiguous views of ONE base buffer (the chunk streams of the
    zero-copy data plane), NO copy at all: the result is a view of the base.
    Returns None when any input is device-backed (the caller's jax path
    handles those)."""
    for t in tables:
        if not is_host_backed(t):
            return None
    ns = [int(t.num_rows) for t in tables]
    total = sum(ns)
    ncols = len(names)
    unified = [
        unify_dictionaries([t.columns[ci].dictionary for t in tables])
        for ci in range(ncols)
    ]
    view = _concat_contiguous(tables, names, ns, unified, total_cap)
    if view is not None:
        return view
    out_cols = []
    for ci in range(ncols):
        cols = [t.columns[ci] for t in tables]
        dtype = cols[0].dtype
        d, luts = unified[ci]
        has_validity = any(c.validity is not None for c in cols)
        data = np.zeros(total_cap, dtype=dtype.np_dtype)
        validity = (
            np.zeros(total_cap, dtype=np.bool_) if has_validity else None
        )
        off = 0
        for t, c, lut, n in zip(tables, cols, luts, ns):
            if n:
                vals = c.data[:n]
                if lut is not None:
                    lut = np.asarray(lut)
                    if len(lut) == 0:
                        vals = np.zeros(n, dtype=data.dtype)
                    else:
                        vals = lut[np.clip(vals, 0, len(lut) - 1)]
                data[off:off + n] = vals
                if has_validity:
                    validity[off:off + n] = (
                        c.validity[:n] if c.validity is not None else True
                    )
            off += n
        # same pad semantics as the device path: zeros (data) / False
        # (validity) beyond the live rows
        out_cols.append(Column(data, validity, dtype, d))
    return Table(tuple(names), tuple(out_cols), np.int32(total))


def _concat_contiguous(tables, names, ns, unified, total_cap: int):
    """Pure-view reassembly: every column of every chunk is an exact-length
    contiguous view, consecutive chunks abut in the same base buffer, no
    dictionary re-encode is needed, and the base can honor the requested
    capacity — then concat is a VIEW of the base buffer (rows past num_rows
    are garbage by the Table contract)."""
    total = sum(ns)
    if total == 0:
        return None
    for _d, luts in unified:
        if any(lut is not None for lut in luts):
            return None
    out_cols = []
    for ci in range(len(names)):
        cols = [t.columns[ci] for t in tables]
        if len({c.validity is not None for c in cols}) > 1:
            return None
        for c, n in zip(cols, ns):
            if len(c.data) != n or not c.data.flags.c_contiguous:
                return None  # not an exact-length contiguous view
            if c.validity is not None and (
                len(c.validity) != n or not c.validity.flags.c_contiguous
            ):
                return None
        merged = _merge_views([c.data for c in cols], total_cap)
        if merged is None:
            return None
        merged_validity = None
        if cols[0].validity is not None:
            merged_validity = _merge_views(
                [c.validity for c in cols], total_cap
            )
            if merged_validity is None:
                return None
        d, _ = unified[ci]
        out_cols.append(Column(merged, merged_validity, cols[0].dtype, d))
    return Table(tuple(names), tuple(out_cols), np.int32(total))


def concat_tables(tables: Sequence[Table], capacity: Optional[int] = None) -> Table:
    """Concatenate same-schema tables into one padded table (jit-safe when
    ``capacity`` is given; rows are packed via cumulative offsets)."""
    if not tables:
        raise ValueError("concat of zero tables")
    first = tables[0]
    total_cap = capacity or sum(t.capacity for t in tables)
    names = first.names
    for t in tables[1:]:
        if t.names != names:
            raise ValueError(f"concat schema mismatch: {t.names} vs {names}")
        for ci in range(len(names)):
            a, b = first.columns[ci], t.columns[ci]
            if a.dtype != b.dtype:
                raise ValueError(
                    f"concat dtype mismatch on {names[ci]!r}: {a.dtype} vs {b.dtype}"
                )
    # Overflow check when row counts are concrete (host path); under jit the
    # caller owns capacity sizing, as everywhere else in the engine.
    concrete = [t.num_rows for t in tables if not isinstance(t.num_rows, jax.core.Tracer)]
    if len(concrete) == len(tables):
        total = int(sum(int(n) for n in concrete))
        if total > total_cap:
            raise ValueError(f"concat overflow: {total} rows > capacity {total_cap}")
        # zero-copy data plane: host-backed inputs (chunk views, staged
        # slices) concat in numpy — one memcpy per column, or NO copy when
        # the chunks are contiguous views of one base buffer
        host = _concat_host(tables, names, total_cap)
        if host is not None:
            return host
        # Meshes-as-workers: inputs committed to DIFFERENT device sets
        # (slices pulled from two worker-owned meshes) cannot feed one op;
        # rebase through host first — the DCN hop a real multi-host
        # deployment pays at exactly this merge point.
        device_sets = set()
        for t in tables:
            for c in t.columns:
                s = getattr(c.data, "sharding", None)
                if s is not None:
                    device_sets.add(frozenset(s.device_set))
        if len(device_sets) > 1:
            tables = [_rebase_to_host(t) for t in tables]
            first = tables[0]
    out_cols = []
    # Destination index for each source row: offset of its table + local idx.
    offsets = []
    acc = jnp.asarray(0, dtype=jnp.int32)
    for t in tables:
        offsets.append(acc)
        acc = acc + t.num_rows
    total_rows = acc
    for ci, name in enumerate(names):
        src_dtype = first.columns[ci].dtype
        dictionary, luts = unify_dictionaries(
            [t.columns[ci].dictionary for t in tables]
        )
        has_validity = any(t.columns[ci].validity is not None for t in tables)
        data = jnp.zeros(total_cap, dtype=src_dtype.np_dtype)
        validity = jnp.zeros(total_cap, dtype=jnp.bool_) if has_validity else None
        for t, off, lut in zip(tables, offsets, luts):
            col = t.columns[ci]
            live = t.row_mask()
            dst = jnp.where(live, off + jnp.arange(t.capacity, dtype=jnp.int32), total_cap)
            vals = col.data
            if lut is not None:
                vals = jnp.asarray(lut)[jnp.clip(vals, 0, len(lut) - 1)]
            data = data.at[dst].set(vals, mode="drop")
            if has_validity:
                v = col.valid_mask()
                validity = validity.at[dst].set(v, mode="drop")
        out_cols.append(Column(data, validity, src_dtype, dictionary))
    return Table(names, tuple(out_cols), total_rows)


def _rebase_to_host(t: Table) -> Table:
    """Detach a table's arrays from their committed devices (host round
    trip); the next consumer places them wherever it computes."""
    import numpy as _np

    def move(x):
        return jnp.asarray(_np.asarray(x))

    return Table(
        t.names,
        tuple(
            Column(
                move(c.data),
                move(c.validity) if c.validity is not None else None,
                c.dtype,
                c.dictionary,
            )
            for c in t.columns
        ),
        move(t.num_rows),
    )


def unify_dictionaries(dicts):
    """Pick a common dictionary for a set of string columns and per-input
    code-remap LUTs (None = codes pass through). The union is SORTED, so
    remapped codes preserve lexicographic order — callers use this for
    concat, cross-dictionary comparison, and COALESCE alike.

    Different Dictionary objects arise legitimately: each worker task's
    SUBSTRING/UPPER/CONCAT evaluation derives its own dictionary, and SQL
    NULL literals (ROLLUP arms, FULL OUTER padding) carry none at all. Codes
    only compare under one vocabulary, so concat re-encodes into the sorted
    union (the host-side analogue of the reference's dictionary re-encode
    before the wire, `impl_execute_task.rs:244-274`)."""
    present = [d for d in dicts if d is not None]
    if not present:
        return None, [None] * len(dicts)
    unique = {d.dict_id: d for d in present}
    if len(unique) == 1:
        return present[0], [None] * len(dicts)
    vals = [d.values.astype(str) for d in unique.values()]
    if all(np.array_equal(v, vals[0]) for v in vals[1:]):
        # same vocabulary, distinct objects (per-task derivations): codes
        # already agree
        return present[0], [None] * len(dicts)
    union_vals = np.unique(np.concatenate(vals))
    # memoize by input dict ids: re-tracing the same concat (e.g. the arm
    # probe + lax.cond branch of IsolatedArmExec) must see the SAME union
    # Dictionary object, or the traces' pytree metadata diverges
    union = lru_get_or_create(
        _UNION_DICT_CACHE, tuple(sorted(unique)),
        lambda: Dictionary(union_vals.astype(object)), cap=256,
    )
    luts = []
    for d in dicts:
        if d is None or len(d) == 0:
            luts.append(None if d is None else np.zeros(1, dtype=np.int32))
            continue
        luts.append(
            np.searchsorted(union_vals, d.values.astype(str)).astype(np.int32)
        )
    return union, luts
