"""Hash join kernels (inner / left / semi / anti / mark), fully vectorized.

The reference uses DataFusion's `HashJoinExec` (CollectLeft or Partitioned,
chosen by the distributed planner's broadcast pass,
`/root/reference/src/distributed_planner/insert_broadcast.rs`). A TPU can't
chase per-row hash chains, so this kernel decomposes the join into dense
array passes:

1. BUILD: group build-side rows by key with the shared claim-loop hash table
   (ops/aggregate.build_group_table) -> every build row gets a group id; a
   CSR layout (counts + offsets + rows sorted by group) enumerates duplicates.
2. PROBE: a lookup-only probe loop resolves each probe row to its key's group
   id (or none) in O(max probe chain) vectorized rounds.
3. EXPAND: pair output positions come from an exclusive cumsum of per-probe
   match counts; each output row finds its probe row by searchsorted and its
   duplicate ordinal by subtraction — a static-capacity gather/gather, no
   dynamic shapes (SURVEY.md §7 hard part (f) analogue for join fan-out).

Semi/anti/mark avoid expansion entirely: they only need the per-probe match
count (optionally after a residual predicate pass over expanded pairs).
Output capacity is a static bound from the planner; overflow is reported as a
jit-safe flag like the aggregate kernel.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from datafusion_distributed_tpu import precision
from datafusion_distributed_tpu.ops.aggregate import GroupTable, build_group_table
from datafusion_distributed_tpu.ops.hash import fold_payload, hash_columns
from datafusion_distributed_tpu.ops.table import Column, Table, scoped
from datafusion_distributed_tpu.schema import DataType


def _fold_keys(cols, valids, lane_plan):
    """Payload folding with a FIXED lane layout shared by build and probe:
    ``lane_plan[i]`` == True adds a validity lane for key column i (required
    when EITHER side of the join is nullable, so the compare matrices always
    have matching shapes)."""
    lane = precision.LANE_INT
    lanes = []
    for c, v in zip(cols, valids):
        payload = fold_payload(c, lane)
        if v is not None:
            payload = jnp.where(v, payload, 0)
        lanes.append(payload)
    n = cols[0].shape[0]
    for v, want in zip(valids, lane_plan):
        if want:
            lanes.append(
                v.astype(lane) if v is not None
                else jnp.ones(n, dtype=lane)
            )
    return jnp.stack(lanes, axis=1)  # [N, lanes]


@scoped("join.probe")
def probe_group_table(
    gt_slot_keys_raw: jnp.ndarray,  # [H, lanes] LANE_INT (raw matrix)
    slot_used: jnp.ndarray,  # [H] bool
    probe_cols: Sequence[jnp.ndarray],
    probe_valids: Sequence[Optional[jnp.ndarray]],
    live: jnp.ndarray,
    lane_plan: Sequence[bool],
    max_rounds: int = 512,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Find each probe row's slot in a built table; -1 when absent.
    Returns (found, overflow): overflow=True when the probe loop exhausted
    max_rounds with rows still unresolved — matches must then be treated as
    unreliable, like the build side's overflow flag.

    SQL join semantics: a NULL key never matches, so rows with any null key
    column are resolved to -1 up front.
    """
    num_slots = slot_used.shape[0]
    mask = np.uint32(num_slots - 1)
    n = probe_cols[0].shape[0]
    keys_mat = _fold_keys(probe_cols, probe_valids, lane_plan)
    h0 = hash_columns(list(probe_cols), list(probe_valids))
    slot = (h0 & mask).astype(jnp.int32)

    has_null = jnp.zeros(n, dtype=jnp.bool_)
    for v in probe_valids:
        if v is not None:
            has_null = has_null | ~v
    active0 = live & ~has_null
    found0 = jnp.full(n, -1, dtype=jnp.int32)

    def cond(state):
        active, *_rest, rounds = state
        return jnp.any(active) & (rounds < max_rounds)

    def body(state):
        active, slot, found, rounds = state
        used = slot_used[slot]
        mine = gt_slot_keys_raw[slot]
        match = used & jnp.all(mine == keys_mat, axis=1)
        found = jnp.where(active & match, slot, found)
        # empty slot => key absent; stop. mismatch on used slot => next slot.
        still = active & used & ~match
        slot = jnp.where(
            still, ((slot + 1).astype(jnp.uint32) & mask).astype(jnp.int32), slot
        )
        return still, slot, found, rounds + 1

    still, _, found, _ = jax.lax.while_loop(
        cond, body, (active0, slot, found0, jnp.asarray(0, dtype=jnp.int32))
    )
    return found, jnp.any(still)


@dataclass
class BuildSide:
    """Build-side hash table + CSR duplicate layout, reusable across probes."""

    raw_slot_keys: jnp.ndarray  # [H, lanes]
    slot_used: jnp.ndarray  # [H]
    counts: jnp.ndarray  # [H] rows per group
    offsets: jnp.ndarray  # [H] exclusive start into rows_by_group
    rows_by_group: jnp.ndarray  # [M] build row indices sorted by group
    table: Table
    overflow: jnp.ndarray
    lane_plan: tuple  # per key col: validity lane present?
    has_null_key: jnp.ndarray  # scalar bool: any live build row had a null key


@scoped("join.build")
def build_join_table(
    build: Table,
    key_names: Sequence[str],
    num_slots: int,
    lane_plan: Optional[Sequence[bool]] = None,
) -> BuildSide:
    live = build.row_mask()
    cols = [build.column(k).data for k in key_names]
    valids = [build.column(k).validity for k in key_names]
    if lane_plan is None:
        lane_plan = [v is not None for v in valids]
    lane_plan = tuple(lane_plan)
    # SQL join: null keys on the build side can never match; treat as dead.
    # (NOT IN needs to know they existed: has_null_key.)
    has_null = jnp.zeros(build.capacity, dtype=jnp.bool_)
    for v in valids:
        if v is not None:
            has_null = has_null | ~v
    has_null_key = jnp.any(live & has_null)
    live = live & ~has_null
    gt = build_group_table(cols, valids, live, num_slots, lane_plan=lane_plan)
    m = build.capacity
    gid = jnp.where(live, gt.group_ids, num_slots)
    counts = (
        jnp.zeros(num_slots, dtype=jnp.int32)
        .at[gid]
        .add(jnp.ones(m, dtype=jnp.int32), mode="drop")
    )
    offsets = jnp.cumsum(counts) - counts  # exclusive
    rows_by_group = jnp.argsort(gid, stable=True).astype(jnp.int32)
    raw = _raw_slot_keys(gt, cols, lane_plan)
    return BuildSide(
        raw_slot_keys=raw,
        slot_used=gt.slot_used,
        counts=counts,
        offsets=offsets,
        rows_by_group=rows_by_group,
        table=build,
        overflow=gt.overflow,
        lane_plan=lane_plan,
        has_null_key=has_null_key,
    )


def _raw_slot_keys(gt: GroupTable, cols, lane_plan) -> jnp.ndarray:
    """Re-fold the group table's per-slot keys into the raw lane matrix the
    probe compares against (same lane layout as _fold_keys)."""
    lane = precision.LANE_INT
    lanes = []
    h = gt.slot_used.shape[0]
    for keys, kv in zip(gt.slot_keys, gt.slot_key_valid):
        payload = fold_payload(keys, lane)
        if kv is not None:
            payload = jnp.where(kv, payload, 0)
        lanes.append(payload)
    for kv, want in zip(gt.slot_key_valid, lane_plan):
        if want:
            lanes.append(
                kv.astype(lane) if kv is not None
                else jnp.ones(h, dtype=lane)
            )
    return jnp.stack(lanes, axis=1)


def hash_join(
    probe: Table,
    build_side: BuildSide,
    probe_keys: Sequence[str],
    join_type: str,  # inner | left | semi | anti | mark
    out_capacity: int,
    probe_prefix: str = "",
    build_prefix: str = "",
) -> tuple[Table, jnp.ndarray]:
    """Join probe against a built side. Returns (result, overflow flag).

    For inner/left the result concatenates probe columns then build columns
    (optionally name-prefixed). For semi/anti the result is probe rows
    filtered by match. For mark it is probe plus a BOOL `__mark` column.
    `left` marks unmatched probe rows' build columns invalid (SQL LEFT JOIN).
    """
    live = probe.row_mask()
    cols = [probe.column(k).data for k in probe_keys]
    valids = [probe.column(k).validity for k in probe_keys]
    g, probe_overflow = probe_group_table(
        build_side.raw_slot_keys, build_side.slot_used, cols, valids,
        live, build_side.lane_plan,
    )
    table_overflow = build_side.overflow | probe_overflow
    found = g >= 0
    g_safe = jnp.where(found, g, 0)
    match_count = jnp.where(found & live, build_side.counts[g_safe], 0)

    if join_type in ("semi", "anti", "mark"):
        has_match = match_count > 0
        if join_type == "semi":
            return probe.compact(has_match), table_overflow
        if join_type == "anti":
            return probe.compact(live & ~has_match), table_overflow
        mark = Column(has_match, None, DataType.BOOL)
        return probe.with_column("__mark", mark), table_overflow

    if join_type == "left":
        out_rows = jnp.where(live, jnp.maximum(match_count, 1), 0)
    elif join_type == "inner":
        out_rows = match_count
    else:
        raise NotImplementedError(f"join type {join_type}")
    with jax.named_scope("join.expand"):
        return _expand_matches(
            probe, build_side, join_type, out_capacity, probe_prefix,
            build_prefix, out_rows, match_count, g_safe, table_overflow,
        )


def _expand_matches(probe, build_side, join_type, out_capacity,
                    probe_prefix, build_prefix, out_rows, match_count,
                    g_safe, table_overflow):
    """`hash_join`'s CSR expansion: one output row a (probe row, matching
    build row) pair, gathered from both sides."""
    cum = jnp.cumsum(out_rows)
    total = cum[-1] if out_rows.shape[0] > 0 else jnp.asarray(0, jnp.int32)
    starts = cum - out_rows
    overflow = table_overflow | (total > out_capacity)

    j = jnp.arange(out_capacity, dtype=jnp.int32)
    # probe row for output j: first row whose cumulative end exceeds j
    l_idx = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
    l_idx = jnp.clip(l_idx, 0, probe.capacity - 1)
    k = j - starts[l_idx]  # duplicate ordinal within the match group
    lg = g_safe[l_idx]
    matched = (k < match_count[l_idx])
    pos = jnp.clip(
        build_side.offsets[lg] + k, 0, build_side.rows_by_group.shape[0] - 1
    )
    r_idx = build_side.rows_by_group[pos]
    r_idx = jnp.where(matched, r_idx, 0)

    out_cols: dict[str, Column] = {}
    for name, col in zip(probe.names, probe.columns):
        c = col.gather(l_idx)
        out_cols[probe_prefix + name] = c
    for name, col in zip(build_side.table.names, build_side.table.columns):
        c = col.gather(r_idx)
        if join_type == "left":
            v = c.valid_mask(out_capacity) & matched
            c = Column(c.data, v, c.dtype, c.dictionary)
        out_cols[build_prefix + name] = c
    result = Table(tuple(out_cols.keys()), tuple(out_cols.values()), total)
    return result, overflow
