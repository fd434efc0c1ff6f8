"""Exchange collectives: the TPU-native data plane.

The reference moves rows between stage tasks over gRPC/Arrow-Flight streams
(`NetworkShuffleExec`/`NetworkCoalesceExec`/`NetworkBroadcastExec`,
`/root/reference/src/execution_plans/`, and the WorkerConnectionPool demux,
SURVEY.md §2.10). On a TPU pod the equivalent fabric is ICI, and the idiomatic
primitive set is XLA collectives inside one `shard_map`ped program:

    hash shuffle (N:M re-shard)  -> `lax.all_to_all`   (NetworkShuffleExec)
    broadcast (replicate build)  -> `lax.all_gather`   (NetworkBroadcastExec)
    coalesce (N -> 1 concat)     -> `lax.all_gather`   (NetworkCoalesceExec)
    slot-range merge             -> `lax.psum_scatter` (sums, counts);
      (a direct grouping's          `lax.all_to_all` + a reduce over the
       partial states)              sources (MIN, MAX)

The slot-range merge replaces the hash shuffle between a partial and a
final aggregate whose groups are addressed by their dictionary codes: every
task's partial state is then an array indexed by the same slots, so task i
takes the sum (min, max) over the tasks of slot range i, and no row, key or
hash crosses the axis (`slot_merge_exchange`).

Everything here runs *inside* shard_map: `table` holds this task's local
shard (padded capacity C, traced num_rows), and `axis` is the mesh axis name.
Whole multi-stage queries therefore compile into ONE XLA program where
compute fuses around the collectives — there is no per-stage host round-trip
at all inside a mesh (the reference's per-batch Flight encode/decode loop
disappears).

Each function returns (table, overflow_flag): the fixed per-destination
buffer bound replaces the reference's 64 MiB connection buffer budget
(worker_connection_pool.rs backpressure); exceeding it is reported, and the
planner re-plans with a bigger bound — the pending->ready analogue.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from datafusion_distributed_tpu.ops.hash import hash_columns
from datafusion_distributed_tpu.ops.table import Column, Table, scoped

# trace time: the bytes the collectives traced on this thread carry, while
# a `collective_tally` is open (None: none is)
_TALLY = threading.local()


@contextlib.contextmanager
def collective_tally():
    """Open while a program is traced: -> a one-element list that holds,
    once the block ends, the bytes its collectives carry, each one's
    operand on every chip of its axis (`_collective`). Shapes only: a
    count of the planned buffers, kept with the program it was traced
    for (`plan/physical.py trace_plan`)."""
    outer = getattr(_TALLY, "bytes", None)
    _TALLY.bytes = 0
    carried = [0]
    try:
        yield carried
    finally:
        carried[0] = _TALLY.bytes
        _TALLY.bytes = outer


def _collective(op, x, axis, *args, **kwargs):
    """``op(x, axis, ...)``, a collective, its operand's bytes on every
    chip of ``axis`` booked to the open `collective_tally`."""
    if getattr(_TALLY, "bytes", None) is not None:
        # the operand's static shape, not its values
        _TALLY.bytes += (math.prod(jnp.shape(x)) * jnp.result_type(x).itemsize
                         * jax.lax.axis_size(axis))
    return op(x, axis, *args, **kwargs)


@scoped("exchange.shuffle")
def shuffle_exchange(
    table: Table,
    key_names: Sequence[str],
    axis: str,
    num_tasks: int,
    per_dest_capacity: int,
) -> tuple[Table, jnp.ndarray]:
    """Hash-repartition rows across all tasks of the mesh axis.

    Row -> destination task = hash(keys) % num_tasks (the arithmetic of the
    reference's hash RepartitionExec + partition-range reads,
    `network_shuffle.rs`: consumer i reads partition range [i*P,(i+1)*P) of
    every producer — here the all_to_all does exactly that swap in one ICI
    step). Output capacity = num_tasks * per_dest_capacity.

    Bucketing is SORT-based (one stable argsort by destination), not the
    O(C*T) one-hot cumsum matrix, so cost is ~flat in task count; and the
    wire payload is ONE fused all_to_all per element-width class (every
    column bit-cast to uint lanes and stacked), not one collective per
    column — latency is ~flat in column count.
    """
    live = table.row_mask()
    cols = [table.column(k).data for k in key_names]
    valids = [table.column(k).validity for k in key_names]
    h = hash_columns(cols, valids)
    dest = (h % np.uint32(num_tasks)).astype(jnp.int32)
    dest = jnp.where(live, dest, num_tasks)  # dead rows go nowhere
    return _route_by_dest(table, dest, axis, num_tasks, per_dest_capacity)


def _route_by_dest(
    table: Table,
    dest: jnp.ndarray,
    axis: str,
    num_tasks: int,
    per_dest_capacity: int,
) -> tuple[Table, jnp.ndarray]:
    """Move each live row to mesh task `dest[row]` (dead rows carry
    dest == num_tasks). Shared routing core of the hash and range shuffles:
    sort-based bucketing + ONE fused all_to_all per element-width class."""
    cap = table.capacity

    # sort-based bucketing: rows grouped by destination, dead rows last
    order = jnp.argsort(dest, stable=True).astype(jnp.int32)  # [C]
    sorted_dest = dest[order]
    # start offset of each destination bucket in the sorted order
    starts = jnp.searchsorted(
        sorted_dest, jnp.arange(num_tasks + 1, dtype=jnp.int32)
    ).astype(jnp.int32)  # [T+1]
    bucket_counts = starts[1:] - starts[:-1]  # [T]
    overflow = jnp.any(bucket_counts > per_dest_capacity)
    ranks = jnp.arange(cap, dtype=jnp.int32) - starts[
        jnp.clip(sorted_dest, 0, num_tasks)
    ]  # position within own bucket, for rows in sorted order
    flat_idx = jnp.where(
        (sorted_dest < num_tasks) & (ranks < per_dest_capacity),
        sorted_dest * per_dest_capacity
        + jnp.minimum(ranks, per_dest_capacity - 1),
        num_tasks * per_dest_capacity,  # dropped
    )

    # fuse every column (and validity lane) into stacked uint payloads,
    # grouped by element width; ONE all_to_all per width class
    lanes: list[tuple[int, jnp.ndarray]] = []  # (width, u-lane in sorted order)
    layout: list[tuple[str, int, int]] = []  # (kind, col_idx, lane_idx)
    for ci, col in enumerate(table.columns):
        u = _bitcast_unsigned(col.data)[order]
        layout.append(("data", ci, len(lanes)))
        lanes.append((u.dtype.itemsize, u))
        if col.validity is not None:
            v = col.validity[order].astype(jnp.uint8)
            layout.append(("valid", ci, len(lanes)))
            lanes.append((1, v))

    recv_by_lane: dict[int, jnp.ndarray] = {}
    for width in sorted({w for w, _ in lanes}):
        idxs = [i for i, (w, _) in enumerate(lanes) if w == width]
        stack = jnp.stack([lanes[i][1] for i in idxs], axis=1)  # [C, L]
        nl = len(idxs)
        send = jnp.zeros(
            (num_tasks * per_dest_capacity, nl), dtype=stack.dtype
        ).at[flat_idx].set(stack, mode="drop")
        send = send.reshape(num_tasks, per_dest_capacity, nl)
        recv = _collective(jax.lax.all_to_all, send, axis, 0, 0,
                           tiled=False)
        recv = recv.reshape(num_tasks * per_dest_capacity, nl)
        for li, i in enumerate(idxs):
            recv_by_lane[i] = recv[:, li]

    new_cols = []
    data_of: dict[int, jnp.ndarray] = {}
    valid_of: dict[int, jnp.ndarray] = {}
    for kind, ci, lane_idx in layout:
        if kind == "data":
            data_of[ci] = recv_by_lane[lane_idx]
        else:
            valid_of[ci] = recv_by_lane[lane_idx].astype(jnp.bool_)
    for ci, col in enumerate(table.columns):
        data = _bitcast_back(data_of[ci], col.data.dtype)
        validity = valid_of.get(ci)
        new_cols.append(Column(data, validity, col.dtype, col.dictionary))

    # received per-source counts -> liveness mask + compaction
    my_counts = _collective(
        jax.lax.all_to_all, bucket_counts.reshape(num_tasks, 1), axis, 0, 0
    ).reshape(num_tasks)  # rows from each source task
    local = jnp.arange(per_dest_capacity, dtype=jnp.int32)
    live_mask = (local[None, :] < my_counts[:, None]).reshape(-1)
    out = Table(table.names, tuple(new_cols), jnp.sum(my_counts))
    out = _compact_with_mask(out, live_mask)
    overflow = _collective(jax.lax.pmax, overflow.astype(jnp.int32),
                           axis) > 0
    return out, overflow


@scoped("exchange.slot_merge")
def slot_merge_exchange(lanes: Sequence[tuple[str, jnp.ndarray]], axis: str,
                        num_tasks: int) -> list[jnp.ndarray]:
    """Reduce per-slot arrays across the mesh axis by slot range. Each lane
    is an ``(op, [num_tasks * R])`` pair, ``op`` one of "sum", "min",
    "max", over slots that name the same thing on every task; -> each
    lane's ``[R]``: on task i, the op over every task of the slots
    ``[i * R, (i + 1) * R)``. Lanes of one op and dtype are stacked into
    ONE collective: a `psum_scatter` for the sums, for a min or a max one
    `all_to_all` of ``[num_tasks, R]`` blocks and a reduce over the
    sources."""
    out: list = [None] * len(lanes)
    groups: dict = {}
    for i, (op, x) in enumerate(lanes):
        groups.setdefault((op, jnp.dtype(x.dtype)), []).append(i)
    for (op, _), idxs in groups.items():
        stack = jnp.stack([lanes[i][1] for i in idxs], axis=1)  # [T*R, L]
        if op == "sum":
            merged = _collective(jax.lax.psum_scatter, stack, axis,
                                 scatter_dimension=0, tiled=True)
        else:
            blocks = stack.reshape(num_tasks, -1, len(idxs))
            recv = _collective(jax.lax.all_to_all, blocks, axis, 0, 0,
                               tiled=False)  # [T, R, L]: one a source
            merged = (jnp.min if op == "min" else jnp.max)(recv, axis=0)
        for li, i in enumerate(idxs):
            out[i] = merged[:, li]
    return out


def _bitcast_unsigned(a: jnp.ndarray) -> jnp.ndarray:
    """Bit-preserving view as a same-width unsigned integer lane."""
    w = a.dtype.itemsize
    if a.dtype == jnp.bool_:
        return a.astype(jnp.uint8)
    target = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[w]
    if a.dtype == target:
        return a
    return a.view(target)


def _bitcast_back(u: jnp.ndarray, dtype) -> jnp.ndarray:
    if dtype == jnp.bool_:
        return u.astype(jnp.bool_)
    if u.dtype == dtype:
        return u
    return u.view(dtype)


def _order_encode(col: Column, ascending: bool, nulls_first: bool):
    """Order-isomorphic unsigned encoding of a sort-key column: for the
    TRUE sort order (incl. direction and null placement), a < b implies
    e(a) <= e(b). Nulls map to the dtype's extremes, so a null can only
    FALSE-TIE with an extreme value — which merely coarsens range
    partitioning (ties route to one task), never reorders. String columns
    compare by dictionary code (dictionaries are sorted)."""
    d = col.data
    nan_mask = None
    if d.dtype == jnp.bool_:
        u = d.astype(jnp.uint32)
    elif jnp.issubdtype(d.dtype, jnp.floating):
        w = d.dtype.itemsize
        ut = {2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[w]
        b = d.view(ut)
        sign = jnp.asarray(1, ut) << (8 * w - 1)
        # IEEE radix trick: negatives flip all bits, positives flip sign
        u = jnp.where((b & sign) != 0, ~b, b ^ sign)
        nan_mask = jnp.isnan(d)
    elif jnp.issubdtype(d.dtype, jnp.signedinteger):
        w = d.dtype.itemsize
        ut = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[w]
        u = d.view(ut) ^ (jnp.asarray(1, ut) << (8 * w - 1))
    else:
        u = d
    if not ascending:
        u = ~u
    if nan_mask is not None:
        # the local sort kernel (argsort) and the host regroup both place
        # NaN LAST regardless of direction; route it the same way (after
        # the direction flip, before the null override)
        u = jnp.where(nan_mask, ~jnp.zeros((), u.dtype), u)
    if col.validity is not None:
        lo = jnp.zeros((), u.dtype)
        hi = ~jnp.zeros((), u.dtype)
        u = jnp.where(col.validity, u, lo if nulls_first else hi)
    return u


@scoped("exchange.range")
def range_shuffle_exchange(
    table: Table,
    keys,  # list[ops.sort.SortKey]
    axis: str,
    num_tasks: int,
    per_dest_capacity: int,
    samples_per_task: int = 64,
) -> tuple[Table, jnp.ndarray]:
    """Range-partition rows across the mesh axis by the composite sort key
    (classic distributed sample sort): after this exchange + a LOCAL sort,
    concatenating task outputs in axis order IS the global sort order — no
    device ever holds or re-sorts the full dataset, unlike the previous
    coalesce-then-sort plan whose every device sorted all T*C gathered
    rows. The splitters come from an all_gathered per-task sample (the
    small gather is the only global communication besides the row routing
    itself, which rides the same fused all_to_all as the hash shuffle).
    """
    cap = table.capacity
    live = table.row_mask()
    enc = [
        _order_encode(table.column(k.name), k.ascending, k.nulls_first)
        for k in keys
    ]

    # --- per-task sample: evenly spaced live rows -----------------------
    s = min(samples_per_task, cap)
    n = table.num_rows
    pos = (jnp.arange(s, dtype=jnp.int32) * jnp.maximum(n, 1)) // s
    pos = jnp.clip(pos, 0, cap - 1)
    samp_live = jnp.arange(s, dtype=jnp.int32) < n
    samp = [e[pos] for e in enc]

    # gather all tasks' samples: [T*s] per key lane, dead samples sort last
    g_live = _collective(jax.lax.all_gather, samp_live, axis).reshape(-1)
    g = [_collective(jax.lax.all_gather, e, axis).reshape(-1) for e in samp]
    order = jnp.argsort(~g_live, stable=True).astype(jnp.int32)
    for lane in reversed(g):
        # stable composition, least-significant first; dead-last applied
        # as the final (most significant) pass
        order = order[jnp.argsort(lane[order], stable=True)]
    order = order[jnp.argsort(~g_live[order], stable=True)]
    total_live = jnp.sum(g_live.astype(jnp.int32))

    # T-1 splitters at the live-sample quantiles
    ranks = (
        jnp.arange(1, num_tasks, dtype=jnp.int32) * total_live
    ) // num_tasks
    ranks = jnp.clip(ranks, 0, jnp.maximum(total_live - 1, 0))
    split_idx = order[ranks]  # [T-1] indices into gathered samples
    splitters = [lane[split_idx] for lane in g]  # per key: [T-1]

    # --- dest = number of splitters <= row (lexicographic) --------------
    dest = jnp.zeros(cap, dtype=jnp.int32)
    for j in range(num_tasks - 1):
        gt = jnp.zeros(cap, dtype=jnp.bool_)
        eq = jnp.ones(cap, dtype=jnp.bool_)
        for lane, spl in zip(enc, splitters):
            sj = spl[j]
            gt = gt | (eq & (lane > sj))
            eq = eq & (lane == sj)
        dest = dest + (gt | eq).astype(jnp.int32)
    dest = jnp.where(total_live > 0, dest, 0)
    dest = jnp.where(live, dest, num_tasks)  # dead rows go nowhere
    return _route_by_dest(table, dest, axis, num_tasks, per_dest_capacity)


@scoped("exchange.broadcast")
def broadcast_exchange(table: Table, axis: str, num_tasks: int) -> Table:
    """Replicate every task's rows to all tasks (build sides of broadcast
    joins — the reference's BroadcastExec + NetworkBroadcastExec pair)."""
    new_cols = []
    for col in table.columns:
        g = _collective(jax.lax.all_gather, col.data, axis)  # [T, C]
        data = g.reshape(-1)
        if col.validity is not None:
            validity = _collective(jax.lax.all_gather, col.validity,
                                   axis).reshape(-1)
        else:
            validity = None
        new_cols.append(Column(data, validity, col.dtype, col.dictionary))
    counts = _collective(jax.lax.all_gather, table.num_rows, axis)  # [T]
    cap = table.capacity
    local = jnp.arange(cap, dtype=jnp.int32)
    live_mask = (local[None, :] < counts[:, None]).reshape(-1)
    out = Table(table.names, tuple(new_cols), jnp.sum(counts))
    return _compact_with_mask(out, live_mask)


@scoped("exchange.coalesce")
def coalesce_exchange(table: Table, axis: str, num_tasks: int) -> Table:
    """N tasks -> one logical table (replicated on every task; the consumer
    stage usually runs at task count 1, others see identical data — SPMD).
    The reference's NetworkCoalesceExec concatenates producer task streams."""
    return broadcast_exchange(table, axis, num_tasks)


@scoped("exchange.coalesce")
def group_coalesce_exchange(
    table: Table, axis: str, num_tasks: int, num_consumers: int
) -> Table:
    """True N:M coalesce: consumer task j receives the CONTIGUOUS producer
    group [j*g, (j+1)*g), g = ceil(N/M) — the reference's
    `network_coalesce.rs:83-99` div_ceil group arithmetic; short groups
    contribute empty streams and tasks >= M end up empty.

    Implementation: g ppermute rounds (round r routes producer j*g+r ->
    consumer j — an injective permutation, so it rides ICI point-to-point
    links). Peak buffer is g*C per task instead of the all_gather's T*C, so
    memory no longer scales with total task count when M > 1.
    """
    g = -(-num_tasks // num_consumers)  # div_ceil
    if g == 1:
        return table  # M >= N: every producer is its own (only) group member
    me = jax.lax.axis_index(axis)
    cap = table.capacity

    recv_parts: list[Table] = []
    for r in range(g):
        # producer p = j*g + r sends to consumer j (skip out-of-range p)
        perm = []
        used_src = set()
        for j in range(num_consumers):
            src = j * g + r
            if src < num_tasks:
                perm.append((src, j))
                used_src.add(src)
        # ppermute requires nothing of unlisted tasks; their recv is zeros
        part_cols = []
        for col in table.columns:
            data = _collective(jax.lax.ppermute, col.data, axis, perm)
            validity = (
                _collective(jax.lax.ppermute, col.validity, axis, perm)
                if col.validity is not None else None
            )
            part_cols.append(Column(data, validity, col.dtype, col.dictionary))
        nrows = _collective(jax.lax.ppermute, table.num_rows, axis, perm)
        # tasks that received nothing this round hold zeroed buffers with
        # nrows == 0 (ppermute zero-fills unaddressed receivers)
        recv_parts.append(Table(table.names, tuple(part_cols), nrows))

    from datafusion_distributed_tpu.ops.table import concat_tables

    out = concat_tables(recv_parts, capacity=g * cap)
    # tasks >= num_consumers received no group: force empty
    is_consumer = me < num_consumers
    out = Table(
        out.names, out.columns,
        jnp.where(is_consumer, out.num_rows, 0).astype(jnp.int32),
    )
    return out


def _compact_with_mask(table: Table, keep: jnp.ndarray) -> Table:
    """Pack rows where keep==True to the front (keep already excludes
    padding)."""
    cap = table.capacity
    (idx,) = jnp.nonzero(keep, size=cap, fill_value=0)
    n = jnp.sum(keep, dtype=jnp.int32)
    cols = tuple(c.gather(idx) for c in table.columns)
    return Table(table.names, cols, n)


def partition_table(table: Table, num_parts: int) -> list[Table]:
    """Host-side: split a Table into row-range slices with equal padded
    capacity (the scale_up_leaf_node analogue for in-memory data)."""
    n = int(table.num_rows)
    per = (n + num_parts - 1) // num_parts if num_parts else 0
    from datafusion_distributed_tpu.ops.table import round_up_pow2

    cap = max(round_up_pow2(max(per, 1)), 8)
    out = []
    for i in range(num_parts):
        lo = min(i * per, n)
        hi = min(lo + per, n)
        cols = {}
        for name, col in zip(table.names, table.columns):
            data = jnp.zeros(cap, dtype=col.data.dtype)
            data = data.at[: hi - lo].set(col.data[lo:hi])
            validity = None
            if col.validity is not None:
                validity = jnp.zeros(cap, dtype=jnp.bool_)
                validity = validity.at[: hi - lo].set(col.validity[lo:hi])
            cols[name] = Column(data, validity, col.dtype, col.dictionary)
        out.append(Table(table.names, tuple(cols.values()), hi - lo))
    return out
