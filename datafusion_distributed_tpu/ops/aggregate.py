"""Hash GROUP BY aggregation, fully vectorized for the TPU.

The reference relies on DataFusion's `AggregateExec` (Partial / Final /
PartialReduce modes — the PartialReduce shuffle-volume optimization is
`/root/reference/src/distributed_planner/partial_reduce_below_network_shuffles.rs`).
A row-wise hash table doesn't map to a SIMD machine, so this kernel builds the
group table with *vectorized claim rounds* instead of per-row probing:

  round := every unresolved row scatter-mins its row-id into its candidate
  slot ("claim"); winners write their keys; every row gathers its slot's keys
  and either resolves (match) or advances to the next probe slot (linear
  probing). Each round is O(N) scatter/gather on the VPU; the number of rounds
  is bounded by the longest probe chain, so for a table sized >= 2x NDV it
  converges in a handful of rounds (cf. "Global Hash Tables Strike Back!",
  PAPERS.md).

Aggregates then reduce by slot id (`_reduce_by_slot`). Into a table of
unknown keys that is a scatter-add / scatter-min / scatter-max: N updates
the TPU serializes (67.6 ms for 8Mi rows, whatever the width), deterministic,
so float results are identical run to run (the bit-parity requirement of
SURVEY.md §7 hard part (d)). Over a domain that is small and known when the
program is traced (below) it is a dense masked pass instead: for each slot
``s``, the sum / min / max of ``where(id == s, vals, identity)``, which XLA
fuses into one loop over the rows for all the aggregates of an operator and
reduces in a fixed order, as deterministic as the scatter.

Group keys may be any fixed-width device dtype (dict codes included); nulls
group together (SQL semantics), tracked via a folded-in validity lane.

Where every group key is dictionary-coded and the keys' whole domain fits
the planned table (`_dictionary_bases`: q1's 3 x 2 codes in 2048 slots; a key
column that holds a NULL has a validity array and one digit more, 4 x 3 were
both to; the planner makes such a table at least as wide as the domain,
`sql/planner.py _agg_slots`), no table is built at all: a row's group id is
the mixed-radix number of its codes (`direct_group_table`), one fused
elementwise pass in place of the claim loop. Up to `_DENSE_MAX_DOMAIN` slots
every reduction is then a dense pass over that domain, padded to the planned
``[num_slots]`` width, so the pack and the partial / final state schema keep
their shapes; beyond it (ClickBench q12's 2.1M search phrases), and for every
table the claim loop builds, they stay scatters, and a direct grouping
whose aggregates count each slot's live rows (COUNT(*)) reads its used
slots off that one count rather than scatter them a second time.
`global_aggregate` is the domain of one: plain masked reductions into slot
0, no scatter at all.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from datafusion_distributed_tpu import precision
from datafusion_distributed_tpu.ops.hash import fold_payload, hash_columns
from datafusion_distributed_tpu.ops.table import Column, Table, scoped
from datafusion_distributed_tpu.schema import DataType

_LANE = precision.LANE_INT
_ACC_INT = precision.ACC_INT

@dataclass(frozen=True)
class AggSpec:
    """One aggregate: func in {sum,count,count_star,min,max,avg}."""

    func: str
    input_name: Optional[str]  # None for count_star
    output_name: str


#: Aggregate functions whose partial states merge LOSSLESSLY through the
#: partial -> (exchange) -> final mode chain above: sum/count/min/max
#: merge as themselves, avg decomposes into a (sum, count) pair the
#: final stage recombines. This is the eligibility set the planner's
#: partial-aggregate push-down consults (planner/distributed.py
#: `_partial_agg_pushdown_pass`) — one source of truth next to the
#: kernel that implements the merges, so a new aggregate function only
#: becomes push-down-eligible when its merge modes actually exist here.
#: (The variance family also decomposes — see _VARIANCE_FUNCS — but is
#: kept out of the push-down set: the ISSUE scope is sum/count/min/max
#: + avg, and variance's (sum, sumsq, count) triple WIDENS the exchange
#: payload 3x, defeating the bytes-reduction goal at low NDV gains.)
PUSHDOWN_DECOMPOSABLE_FUNCS = frozenset(
    {"sum", "count", "count_star", "min", "max", "avg"}
)


@dataclass
class GroupTable:
    """Result of the claim loop: per-row group ids + per-slot key columns."""

    group_ids: jnp.ndarray  # [N] int32 slot index per row (garbage for dead rows)
    slot_used: jnp.ndarray  # [H] bool
    slot_keys: list[jnp.ndarray]  # per key column: [H] values
    slot_key_valid: list[Optional[jnp.ndarray]]  # per key column: [H] bool or None
    num_groups: jnp.ndarray  # scalar int32
    overflow: jnp.ndarray  # scalar bool: table too small, results invalid


def _fold_key_lanes(key_cols, key_valids, lane_plan, n):
    """Keys folded to fixed-width integer lanes (int32 in tpu precision
    mode, int64 in x64 mode). Nullability is an explicit extra lane in the
    compare matrix (not an in-band sentinel, which a real key value could
    collide with): column i with lane_plan[i] contributes lanes
    [payload-with-nulls-zeroed, is_valid].
    Returns (lanes list, per-key-column validity lane index or None)."""
    keys64 = []
    valid_lane_of: list[Optional[int]] = []  # per key col: its validity lane idx
    for c, v in zip(key_cols, key_valids):
        payload = fold_payload(c, _LANE)
        if v is not None:
            payload = jnp.where(v, payload, 0)
        keys64.append(payload)
        valid_lane_of.append(None)
    for i, (v, want) in enumerate(zip(key_valids, lane_plan)):
        if want:
            valid_lane_of[i] = len(keys64)
            keys64.append(
                v.astype(_LANE) if v is not None
                else jnp.ones(n, dtype=_LANE)
            )
    return keys64, valid_lane_of


def build_group_table(
    key_cols: Sequence[jnp.ndarray],
    key_valids: Sequence[Optional[jnp.ndarray]],
    live: jnp.ndarray,
    num_slots: int,
    max_rounds: int = 512,
    lane_plan: Optional[Sequence[bool]] = None,
) -> GroupTable:
    """Assign each live row a group id (a slot in a power-of-two table).

    ``lane_plan`` fixes which key columns carry a validity lane (True).
    Joins pass the union of build+probe nullability so both sides fold to
    identical compare-matrix shapes; by default it mirrors ``key_valids``.
    """
    assert num_slots & (num_slots - 1) == 0, "num_slots must be a power of two"
    n = key_cols[0].shape[0]
    k = len(key_cols)
    mask = np.uint32(num_slots - 1)
    if lane_plan is None:
        lane_plan = [v is not None for v in key_valids]

    keys64, valid_lane_of = _fold_key_lanes(key_cols, key_valids, lane_plan, n)

    h0 = hash_columns(list(key_cols), list(key_valids))
    slot0 = (h0 & mask).astype(jnp.int32)

    n_lanes = len(keys64)
    slot_keys0 = jnp.zeros((num_slots, n_lanes), dtype=_LANE)
    slot_used0 = jnp.zeros(num_slots, dtype=jnp.bool_)
    keys_mat = jnp.stack(keys64, axis=1)  # [N, k]

    # Dead rows are born resolved and never claim a slot.
    resolved0 = ~live
    gid0 = jnp.zeros(n, dtype=jnp.int32)

    def cond(state):
        resolved, *_ , rounds = state
        return (~jnp.all(resolved)) & (rounds < max_rounds)

    def body(state):
        resolved, slot, gid, slot_keys, slot_used, rounds = state
        # 1. unresolved rows claim their candidate slot (min row-id wins)
        claim_slot = jnp.where(resolved, num_slots, slot)  # drop resolved
        owner = jnp.full(num_slots, n, dtype=jnp.int32)
        owner = owner.at[claim_slot].min(
            jnp.arange(n, dtype=jnp.int32), mode="drop"
        )
        # Only claims on EMPTY slots count; occupied slots keep their keys.
        claimable = ~slot_used
        winner = (~resolved) & (owner[slot] == jnp.arange(n, dtype=jnp.int32)) & (
            claimable[slot]
        )
        # 2. winners write their keys and mark slots used
        wslot = jnp.where(winner, slot, num_slots)
        slot_keys = slot_keys.at[wslot].set(keys_mat, mode="drop")
        slot_used = slot_used.at[wslot].set(True, mode="drop")
        # 3. everyone gathers; match -> resolve, mismatch on used slot -> probe
        mine = slot_keys[slot]  # [N, k]
        used = slot_used[slot]
        match = used & jnp.all(mine == keys_mat, axis=1)
        newly = (~resolved) & match
        gid = jnp.where(newly, slot, gid)
        resolved = resolved | newly
        advance = (~resolved) & used & ~match
        slot = jnp.where(
            advance, ((slot + 1).astype(jnp.uint32) & mask).astype(jnp.int32), slot
        )
        return resolved, slot, gid, slot_keys, slot_used, rounds + 1

    state = (
        resolved0, slot0, gid0, slot_keys0, slot_used0,
        jnp.asarray(0, dtype=jnp.int32),
    )
    with jax.named_scope("agg.claim"):
        resolved, slot, gid, slot_keys, slot_used, _ = jax.lax.while_loop(
            cond, body, state
        )
    overflow = ~jnp.all(resolved)
    return _group_table_from_raw(
        gid, slot_keys, slot_used, overflow, key_cols, key_valids,
        valid_lane_of,
    )


def _group_table_from_raw(gid, slot_keys, slot_used, overflow, key_cols,
                          key_valids, valid_lane_of) -> GroupTable:
    """Unfold the raw [H, lanes] table back into per-key-column arrays."""
    out_keys = []
    out_valid = []
    for i, (c, v) in enumerate(zip(key_cols, key_valids)):
        payload = slot_keys[:, i]
        lane = valid_lane_of[i]
        if lane is not None:
            key_valid = slot_keys[:, lane] != 0
            out_valid.append(key_valid)
        else:
            out_valid.append(None)
        if c.dtype == jnp.float64:  # x64 mode only
            out_keys.append(payload.view(jnp.float64))
        elif c.dtype == jnp.float32:
            out_keys.append(payload.astype(jnp.int32).view(jnp.float32))
        else:
            out_keys.append(payload.astype(c.dtype))
    return GroupTable(
        group_ids=gid,
        slot_used=slot_used,
        slot_keys=out_keys,
        slot_key_valid=out_valid,
        num_groups=jnp.sum(slot_used, dtype=jnp.int32),
        overflow=overflow,
    )


def _dictionary_bases(key_columns: Sequence[Column],
                      num_slots: int) -> Optional[list[int]]:
    """The one rule for addressing groups directly: every key column is
    dictionary-coded and the keys' domain, the product of each column's
    ``len(dictionary)`` (one more where it has a validity array: NULL is
    a key of its own; a column without NULLs is registered with none,
    `io/parquet.py arrow_to_host_columns`, so TPC-H q1's domain is its
    dictionaries' own 3 x 2), is not empty and fits the ``num_slots`` the
    planner gave the aggregate. -> each column's base, or None for the
    claim loop. All of it is static at trace time (``Column.dictionary``
    and whether ``validity`` is None are pytree structure). It rests on a
    live, valid row's code lying in
    ``[0, len(dictionary))``, which every producer of a dictionary column
    keeps (tests/test_aggregate.py pins it)."""
    if any(c.dictionary is None for c in key_columns):
        return None
    bases = [len(c.dictionary) + (c.validity is not None)
             for c in key_columns]
    return bases if 0 < math.prod(bases) <= num_slots else None


#: Up to this domain a direct grouping touches no scatter: `_reduce_by_slot`
#: reduces by dense masked passes and `direct_group_table` finds the used
#: slots by comparing every row's id with each slot (N x D compares, fused
#: into one pass); beyond it each is a scatter, N serialized updates (the
#: presence none where a COUNT(*) scatters the same ids). On the
#: v5e (my chip run, PERF.md, PR 32), ms for 8Mi rows (2Mi in brackets), one
#: f32 sum and ten that share a pass, an int32 count and an f32 min reading
#: as the one sum does to 0.1 ms:
#:   domain      12     128    1024    2048    4096    8192
#:   dense 1    0.68    1.40    6.49   13.21   36.57  132.85
#:             (0.60    0.83    2.09    3.80    9.66   33.80)
#:   dense 10   2.29    9.90   61.99  121.97  241.36  481.22
#:             (1.50    3.52   16.65   31.61   61.50  121.36)
#:   scatter 1  67.3 up to 1024, 56.7 from 2048 on (17.4, 14.7)
#:   scatter 10 664.8 at 12, 559.9 at 2048 (167.5, 141.2)
#: One sum crosses its scatter near 5,000 slots, ten near 9,500, at either
#: height: 4096 is the largest power of two at which no width loses (at
#: 8192 one sum would cost 2.3 scatters). The presence compares (PR 30: 0.64
#: at 12, 1.5 at 128, 12.2 at 2048, 6 us a slot, against a scatter's 53.7)
#: cross near 9,000; between the two crossings they would save some 5 ms
#: beside reductions of 57 each, so the one cut serves both.
_DENSE_MAX_DOMAIN = 4096


def _radices(bases: Sequence[int]) -> list[int]:
    return [math.prod(bases[i + 1:]) for i in range(len(bases))]


@scoped("agg.direct")
def direct_group_ids(key_columns: Sequence[Column], bases: Sequence[int],
                     live: jnp.ndarray, num_slots: int) -> jnp.ndarray:
    """The group id of each row with no table built: ``sum(digit_i *
    radix_i)`` over its key columns, ``digit_i`` the dictionary code of a
    valid key and ``len(dictionary_i)`` of a NULL one (``bases`` from
    `_dictionary_bases`); a dead row's id is ``num_slots``, which names no
    slot."""
    gid = jnp.zeros(live.shape, dtype=jnp.int32)
    for col, radix in zip(key_columns, _radices(bases)):
        digit = col.data.astype(jnp.int32)
        if col.validity is not None:
            digit = jnp.where(col.validity, digit, len(col.dictionary))
        gid = gid + digit * np.int32(radix)
    return jnp.where(live, gid, num_slots)  # dead rows use no slot


@scoped("agg.direct")
def direct_group_table(key_columns: Sequence[Column], bases: Sequence[int],
                       gid: jnp.ndarray, num_slots: int,
                       slot_counts: Optional[jnp.ndarray] = None
                       ) -> GroupTable:
    """`build_group_table`'s result over the ids `direct_group_ids` gave.
    Slot ``s`` of the domain holds the keys ``(s // radix_i) % base_i``; a
    slot is used if some live row has its id: up to `_DENSE_MAX_DOMAIN`
    found by comparing every id with each slot; past it read off
    ``slot_counts`` (the live rows of each slot, which an aggregate that
    counts them has reduced already: `hash_aggregate`) where given, else a
    scatter of its own. The arrays keep the planned ``[num_slots]`` width,
    so what packs by slot sees the claim loop's shapes (the reductions read
    the domain off the same rule: `hash_aggregate`, `_reduce_by_slot`);
    groups come out in radix order."""
    domain = math.prod(bases)
    if domain <= _DENSE_MAX_DOMAIN:
        present = jnp.any(
            jnp.arange(domain, dtype=jnp.int32)[:, None] == gid[None, :],
            axis=1,
        )
        slot_used = jnp.pad(present, (0, num_slots - domain))
    elif slot_counts is not None:
        slot_used = slot_counts > 0
    else:
        slot_used = jnp.zeros(num_slots, dtype=jnp.bool_).at[gid].set(
            True, mode="drop"
        )
    slot_keys, slot_key_valid = _slot_keys(
        key_columns, bases, jnp.arange(num_slots, dtype=jnp.int32))
    return GroupTable(
        group_ids=gid,
        slot_used=slot_used,
        slot_keys=slot_keys,
        slot_key_valid=slot_key_valid,
        num_groups=jnp.sum(slot_used, dtype=jnp.int32),
        overflow=jnp.asarray(False),
    )


def _slot_keys(key_columns: Sequence[Column], bases: Sequence[int],
               slot: jnp.ndarray) -> tuple[list, list]:
    """The keys of each slot index in ``slot``, a direct grouping's: key
    ``i`` is the digit ``(slot // radix_i) % base_i``, its code, and the
    digit ``len(dictionary_i)`` a NULL -> (per key column the codes in its
    dtype, per key column its validity or None)."""
    keys, valids = [], []
    for col, base, radix in zip(key_columns, bases, _radices(bases)):
        digit = (slot // np.int32(radix)) % np.int32(base)
        if col.validity is not None:
            key_valid = digit < len(col.dictionary)
            digit = jnp.where(key_valid, digit, 0)
            valids.append(key_valid)
        else:
            valids.append(None)
        keys.append(digit.astype(col.data.dtype))
    return keys, valids


# op -> (the dense reduction, the scatter's method)
_SLOT_REDUCTIONS = {"sum": (jnp.sum, "add"), "min": (jnp.min, "min"),
                    "max": (jnp.max, "max")}


def _reduce_by_slot(op: str, ids, vals, num_slots: int,
                    dense_domain: Optional[int]):
    """``op`` ("sum" | "min" | "max") of ``vals`` over the rows of each slot
    id -> ``[num_slots]`` in ``vals``' dtype; a slot no row names holds the
    op's identity, and a row whose id is ``num_slots`` (dead, NULL) names
    none. ``dense_domain`` None: one scatter, N serialized updates on the
    TPU. Else every id lies in ``[0, dense_domain)`` (or is ``num_slots``)
    and each slot of the domain reduces ``where(id == slot, vals,
    identity)``: the compiler fuses the compare and the select into the
    reduction, and sibling reductions over the same ids into one pass, so
    no ``[domain, N]`` operand exists. A NaN stays in its own slot (it is
    masked, not multiplied), integers stay integers."""
    reduce, scatter = _SLOT_REDUCTIONS[op]
    identity = _identity(op, vals.dtype)
    if dense_domain is None:
        init = jnp.full(num_slots, identity, vals.dtype)
        return getattr(init.at[ids], scatter)(vals, mode="drop")
    slots = jnp.arange(dense_domain, dtype=jnp.int32)
    kept = jnp.where(ids[None, :] == slots[:, None], vals[None, :],
                     jnp.asarray(identity, vals.dtype))
    out = reduce(kept, axis=1).astype(vals.dtype)
    return jnp.pad(out, (0, num_slots - dense_domain),
                   constant_values=identity)


def _identity(op: str, dtype):
    """What ``op`` ("sum" | "min" | "max") leaves a slot no row names."""
    return 0 if op == "sum" else (
        _dtype_max if op == "min" else _dtype_min)(dtype)


def _counts_live_rows(spec: AggSpec, mode: str) -> bool:
    """Whether ``spec``'s value is the number of live rows of each slot:
    COUNT(*) over raw rows. In ``final`` and ``partial_reduce`` it sums
    partial counts instead."""
    return spec.func == "count_star" and mode in ("single", "partial")


def aggregate_slots(
    table: Table,
    group_names: Sequence[str],
    aggs: Sequence[AggSpec],
    num_slots: int,
    mode: str,
    prec_flags: Optional[list] = None,
    live: Optional[jnp.ndarray] = None,
    direct: Optional[list] = None,
    scatters: Optional[list] = None,
    presence_from_count: Optional[list] = None,
) -> tuple[GroupTable, dict[str, Column], Optional[list[int]]]:
    """`hash_aggregate` up to its pack (the arguments are its): -> (the
    group table, every output column at the ``[num_slots]`` width of the
    table, slot ``s`` holding the keys and the values of group ``s``, an
    unused slot each reduction's identity, and the keys' bases where the
    groups were addressed by their codes (`_dictionary_bases`), else
    None)."""
    if live is None:
        live = table.row_mask()
    key_columns = [table.column(g) for g in group_names]
    bases = _dictionary_bases(key_columns, num_slots)
    row_counts = None  # [num_slots]: the live rows of each slot, reduced once
    if bases is not None:
        domain = math.prod(bases)
        ids = direct_group_ids(key_columns, bases, live, num_slots)
        counter = next((s for s in aggs if _counts_live_rows(s, mode)), None)
        if domain > _DENSE_MAX_DOMAIN and counter is not None:
            row_counts = _eval_agg(counter, table, ids, live, num_slots, mode,
                                   prec_flags, None, scatters
                                   )[counter.output_name].data
        gt = direct_group_table(key_columns, bases, ids, num_slots,
                                row_counts)
        if direct is not None:
            direct.append(domain)
        if (scatters is not None and domain > _DENSE_MAX_DOMAIN
                and row_counts is None):
            scatters.append("presence")
        if presence_from_count is not None and row_counts is not None:
            presence_from_count.append(domain)
    else:
        gt = build_group_table(
            [c.data for c in key_columns],
            [c.validity for c in key_columns], live, num_slots,
        )
    gid = jnp.where(live, gt.group_ids, num_slots)  # dead rows drop out

    out_cols: dict[str, Column] = {}
    for g, keys, kv in zip(group_names, gt.slot_keys, gt.slot_key_valid):
        src = table.column(g)
        out_cols[g] = Column(keys, kv, src.dtype, src.dictionary)

    dense_domain = None
    if bases is not None and math.prod(bases) <= _DENSE_MAX_DOMAIN:
        dense_domain = math.prod(bases)
    for spec in aggs:
        if row_counts is not None and _counts_live_rows(spec, mode):
            out_cols[spec.output_name] = Column(row_counts, None,
                                                DataType.INT64)
            continue
        out_cols.update(
            _eval_agg(spec, table, gid, live, num_slots, mode, prec_flags,
                      dense_domain, scatters)
        )
    return gt, out_cols, bases


def hash_aggregate(
    table: Table,
    group_names: Sequence[str],
    aggs: Sequence[AggSpec],
    num_slots: int,
    mode: str = "single",  # "single" | "partial" | "final" | "partial_reduce"
    prec_flags: Optional[list] = None,
    out_capacity: Optional[int] = None,
    live: Optional[jnp.ndarray] = None,
    direct: Optional[list] = None,
    scatters: Optional[list] = None,
    presence_from_count: Optional[list] = None,
) -> tuple[Table, jnp.ndarray]:
    """GROUP BY aggregation. Returns (result table, overflow flag).

    ``live``, when given, is the ``[capacity] bool`` mask of the rows to
    aggregate, anywhere in the table (a filter's mask handed up unpacked:
    `ExecutionPlan.execute_masked`); None is the ``num_rows`` prefix.

    ``prec_flags``, when given, collects traced bools flagging integer SUM
    results that left int32's exact range (tpu precision mode only; the
    executor raises a non-retryable error for these).

    ``direct``, when given, collects the domain's size if the groups were
    addressed directly by their dictionary codes (`_dictionary_bases`) and
    no group table was built: the executor's ``direct_groupings``. A domain
    of at most `_DENSE_MAX_DOMAIN` also reduced by dense masked passes and
    not by scatters (`_reduce_by_slot`): its ``dense_aggregates``.

    ``scatters``, when given, collects one entry for each reduction by
    slot that lowered as a scatter, and one for a direct grouping's
    slot-presence pass over a domain past `_DENSE_MAX_DOMAIN` that
    scatters: the executor's ``scatter_reductions``.

    Past `_DENSE_MAX_DOMAIN` a direct grouping whose aggregates count the
    live rows of each slot (`_counts_live_rows`) reduces that count once,
    hands it to every such aggregate, and reads its used slots off it
    (``count > 0``) in place of a presence scatter; ``presence_from_count``,
    when given, collects the domain of each grouping that did: the
    executor's ``presence_from_count``.

    Modes mirror DataFusion's AggregateMode as used by the reference planner:
      partial        -> emits sum/count/min/max accumulator columns per agg
      final          -> consumes accumulator columns (re-groups, merges)
      single         -> full aggregation in one step
      partial_reduce -> consumes accumulator columns and emits MERGED
                        accumulator columns (AggregateMode::PartialReduce,
                        `partial_reduce_below_network_shuffles.rs` /
                        the progressive reduction-tree example): fewer
                        partial states cross each exchange hop
    The result table has capacity == min(out_capacity or num_slots,
    num_slots), groups packed to the front.
    """
    gt, out_cols, _ = aggregate_slots(
        table, group_names, aggs, num_slots, mode, prec_flags, live, direct,
        scatters, presence_from_count)

    # Pack used slots to the front — into a TIGHTER capacity when the
    # caller supplies one. The hash table stays wide for short probe
    # chains, but the OUTPUT (which downstream sorts/joins pay capacity-
    # proportional work for) only needs to hold the groups: group count is
    # bounded by live input rows, so a bound of pow2(input capacity) can
    # never overflow, and an NDV-derived bound folds into the overflow
    # flag (the session retry widens it like any other capacity).
    packed = Table.make(out_cols, gt.num_groups)
    keep = gt.slot_used
    out_cap = min(out_capacity or num_slots, num_slots)
    (idx,) = jnp.nonzero(keep, size=out_cap, fill_value=0)
    packed = packed.gather(idx, gt.num_groups)
    overflow = gt.overflow
    if out_cap < num_slots:
        overflow = overflow | (gt.num_groups > out_cap)
    return packed, overflow


@scoped("agg.global")
def global_aggregate(table: Table, aggs: Sequence[AggSpec], mode: str = "single",
                     prec_flags: Optional[list] = None,
                     live: Optional[jnp.ndarray] = None) -> Table:
    """Aggregation with no GROUP BY: one output row (capacity 8 keeps the
    result TPU-lane-friendly). Shares the per-aggregate evaluation with
    hash_aggregate, with every live row (``live`` as there) mapped to
    group 0."""
    if live is None:
        live = table.row_mask()
    cap = 8
    gid = jnp.zeros(table.capacity, dtype=jnp.int32)
    cols: dict[str, Column] = {}
    for spec in aggs:
        cols.update(_eval_agg(spec, table, gid, live, cap, mode, prec_flags,
                              dense_domain=1))
    return Table(tuple(cols.keys()), tuple(cols.values()),
                 jnp.asarray(1, dtype=jnp.int32))


def _mean_shifted_seg_sum(vals, valid, seg_sum, group_counts):
    """Per-group float sum as seg_sum(x - m) + m*n_g (f32 storage mode).

    A raw f32 scatter-add over millions of same-sign values drifts
    ~sqrt(N)*eps relative — enough that two task layouts of the SAME data
    disagree beyond 5e-4 (seen at TPC-H SF0.5, q1 avg_disc). The identity
    is algebraically exact for ANY scalar center m; centering residuals
    near zero makes the scatter-add cancel instead of accumulate (probe:
    3M rows, max rel err vs f64 truth 8e-8). m only needs to be a rough
    center, so a plain f32 mean is fine — but it must be FINITE: a
    non-finite m (any Inf/NaN in the data) would poison every group, so
    fall back to m=0 (the raw scatter-add, which confines Inf/NaN to the
    group containing it)."""
    m = jnp.sum(vals) / jnp.maximum(jnp.sum(valid), 1)
    m = jnp.where(jnp.isfinite(m), m, jnp.zeros_like(m))
    z = seg_sum(jnp.where(valid, vals - m, 0))
    # optimization_barrier: on this image's XLA:CPU, letting the compiler
    # fuse the `+ m*n_g` add with the two scatters corrupts the scatter's
    # contribution entirely (observed: result off by the full residual sum
    # plus more, ~1e-3 relative, vs ~1e-5 with the pieces computed
    # separately — reproduced with a python-constant m and bitwise-equal
    # inputs, so it is a fusion bug, not accumulation noise). The barrier
    # pins the scatter result before the elementwise add.
    z = jax.lax.optimization_barrier(z)
    return z + m * group_counts.astype(vals.dtype)


def _eval_agg(spec, table, gid, live, num_slots, mode, prec_flags,
              dense_domain, scatters=None):
    """Produce the output column(s) for one AggSpec in the given mode,
    under the scope ``agg.reduce.<func>``; every reduction by slot goes
    through `_reduce_by_slot` with ``dense_domain``, and ``scatters``
    (when given) gets an entry for each that lowers as a scatter."""
    def by_slot(op, ids, vals):
        if dense_domain is None and scatters is not None:
            scatters.append(op)
        return _reduce_by_slot(op, ids, vals, num_slots, dense_domain)

    def seg_sum(vals, dtype=None):
        return by_slot("sum", gid, vals.astype(dtype or vals.dtype))

    with jax.named_scope(f"agg.reduce.{spec.func}"):
        return _eval_agg_columns(spec, table, gid, live, num_slots, mode,
                                 seg_sum, by_slot, prec_flags)


def _eval_agg_columns(spec, table, gid, live, num_slots, mode, seg_sum,
                      by_slot, prec_flags):
    name = spec.output_name
    if spec.func == "count_star":
        if mode in ("final", "partial_reduce"):
            acc = table.column(f"{name}")
            vals = jnp.where(live, acc.data, 0)
            return {name: Column(seg_sum(vals), None, DataType.INT64)}
        cnt = seg_sum(jnp.where(live, 1, 0).astype(DataType.INT64.np_dtype))
        return {name: Column(cnt, None, DataType.INT64)}

    # sum/count/min/max: the merged accumulator IS the final value, so
    # partial_reduce and final share one merge (the output column stays a
    # valid partial state for a later final stage)
    if mode in ("final", "partial_reduce") and spec.func in (
        "sum", "count", "min", "max",
    ):
        # merge accumulator column produced by a partial stage
        acc = table.column(name)
        valid = acc.valid_mask() & live
        if spec.func in ("sum", "count"):
            vals = jnp.where(valid, acc.data, 0)
            merged = seg_sum(vals)
            if spec.func == "sum":
                _check_int32_sum_range(vals, seg_sum, prec_flags)
        else:
            merged = by_slot(
                spec.func, jnp.where(valid, gid, num_slots), acc.data)
        nonempty = seg_sum(jnp.where(valid, 1, 0).astype(_ACC_INT))
        if spec.func == "count":
            return {name: Column(merged, None, DataType.INT64)}
        out_valid = nonempty > 0
        return {name: Column(merged, out_valid, _col_dtype(acc), acc.dictionary)}

    if mode in ("final", "partial_reduce") and spec.func == "avg":
        s = table.column(f"{name}__sum")
        c = table.column(f"{name}__count")
        valid = live & s.valid_mask()
        ssum = seg_sum(jnp.where(valid, s.data, 0.0))
        scnt = seg_sum(jnp.where(live, c.data, 0))
        out_valid = scnt > 0
        if mode == "partial_reduce":  # keep the (sum, count) state form
            return {
                f"{name}__sum": Column(ssum, out_valid, DataType.FLOAT64),
                f"{name}__count": Column(scnt, None, DataType.INT64),
            }
        avg = ssum / jnp.where(scnt == 0, 1, scnt)
        return {name: Column(avg, out_valid, DataType.FLOAT64)}

    if spec.func in _VARIANCE_FUNCS and mode in ("final", "partial_reduce"):
        s = table.column(f"{name}__sum")
        sq = table.column(f"{name}__sumsq")
        c = table.column(f"{name}__count")
        valid = live & s.valid_mask()
        ssum = seg_sum(jnp.where(valid, s.data, 0.0))
        ssumsq = seg_sum(jnp.where(valid, sq.data, 0.0))
        scnt = seg_sum(jnp.where(live, c.data, 0))
        if mode == "partial_reduce":  # keep the (sum, sumsq, count) state
            nz = scnt > 0
            return {
                f"{name}__sum": Column(ssum, nz, DataType.FLOAT64),
                f"{name}__sumsq": Column(ssumsq, nz, DataType.FLOAT64),
                f"{name}__count": Column(scnt, None, DataType.INT64),
            }
        return {name: _variance_result(spec.func, ssum, ssumsq, scnt)}

    # partial/single over raw input
    col = table.column(spec.input_name)
    valid = col.valid_mask() & live
    vgid = jnp.where(valid, gid, num_slots)

    if spec.func in _VARIANCE_FUNCS:
        f = DataType.FLOAT64.np_dtype
        vals = jnp.where(valid, col.data, 0).astype(f)
        s = seg_sum(vals)
        sq = seg_sum(vals * vals)
        cnt = seg_sum(jnp.where(valid, 1, 0).astype(DataType.INT64.np_dtype))
        if mode == "partial":
            return {
                f"{name}__sum": Column(s, cnt > 0, DataType.FLOAT64),
                f"{name}__sumsq": Column(sq, cnt > 0, DataType.FLOAT64),
                f"{name}__count": Column(cnt, None, DataType.INT64),
            }
        return {name: _variance_result(spec.func, s, sq, cnt)}

    if spec.func == "count":
        cnt = seg_sum(jnp.where(valid, 1, 0).astype(DataType.INT64.np_dtype))
        return {name: Column(cnt, None, DataType.INT64)}

    if spec.func == "sum" or (spec.func == "avg" and mode == "partial"):
        acc_dtype = (
            DataType.FLOAT64.np_dtype if col.dtype.is_float
            else DataType.INT64.np_dtype
        )
        vals = jnp.where(valid, col.data, 0).astype(acc_dtype)
        nonempty = seg_sum(jnp.where(valid, 1, 0).astype(_ACC_INT))
        if col.dtype.is_float and jnp.dtype(acc_dtype) == jnp.float32:
            s = _mean_shifted_seg_sum(vals, valid, seg_sum, nonempty)
        else:
            s = seg_sum(vals)
            _check_int32_sum_range(vals, seg_sum, prec_flags)
        sum_dtype = DataType.FLOAT64 if col.dtype.is_float else DataType.INT64
        if spec.func == "sum":
            return {name: Column(s, nonempty > 0, sum_dtype)}
        # partial avg: emit sum + count pair
        return {
            f"{name}__sum": Column(
                s.astype(DataType.FLOAT64.np_dtype), nonempty > 0, DataType.FLOAT64
            ),
            f"{name}__count": Column(nonempty, None, DataType.INT64),
        }

    if spec.func == "avg":  # single
        vals = jnp.where(valid, col.data, 0).astype(DataType.FLOAT64.np_dtype)
        cnt = seg_sum(jnp.where(valid, 1, 0).astype(_ACC_INT))
        if jnp.dtype(vals.dtype) == jnp.float32:
            s = _mean_shifted_seg_sum(vals, valid, seg_sum, cnt)
        else:
            s = seg_sum(vals)
        avg = s / jnp.where(cnt == 0, 1, cnt)
        return {name: Column(avg, cnt > 0, DataType.FLOAT64)}

    if spec.func in ("min", "max"):
        red = by_slot(spec.func, vgid, col.data)
        nonempty = seg_sum(jnp.where(valid, 1, 0).astype(_ACC_INT))
        return {
            name: Column(red, nonempty > 0, col.dtype, col.dictionary)
        }

    raise NotImplementedError(f"aggregate function {spec.func}")


#: SQL variance family. Computed via the (sum, sumsq, count) decomposition —
#: mergeable across partial/final stages like avg's (sum, count). The naive
#: formula cancels catastrophically when stddev << mean; acceptable for the
#: benchmark domains (quantities/prices), exact-enough in x64 mode.
_VARIANCE_FUNCS = {"stddev", "stddev_samp", "stddev_pop", "var_samp",
                   "var_pop"}


#: Aggregate functions whose partial states merge slot by slot, each
#: state elementwise (`merge_slot_states`): a sum for every sum and count
#: (AVG's and the variance family's parts too), a min or a max for MIN and
#: MAX.
SLOT_MERGE_FUNCS = PUSHDOWN_DECOMPOSABLE_FUNCS | _VARIANCE_FUNCS


def merge_slot_states(columns: dict[str, Column], slot_used: jnp.ndarray,
                      group_names: Sequence[str], aggs: Sequence[AggSpec],
                      bases: Sequence[int], axis: str, num_tasks: int,
                      range_slots: int,
                      prec_flags: Optional[list] = None) -> Table:
    """A ``partial`` direct grouping's states (`aggregate_slots`: every
    column ``[num_slots]`` wide, slot ``s`` group ``s``) merged across the
    mesh axis by slot range. One program holds one dictionary a key
    column, so slot ``s`` is one group on every task: task ``i`` gets the
    slots ``[i * range_slots, (i + 1) * range_slots)`` of the domain, each
    state reduced over the tasks (`parallel/exchange.py
    slot_merge_exchange`), a sum for every sum and count, a min or a max
    for MIN and MAX. A SUM's, MIN's or MAX's validity is a lane of its own,
    summed; a slot is used where the merged COUNT(*) is above 0 or, with
    no COUNT(*), where a lane of the partials' used slots summed is; an
    int32 SUM is summed in float32 beside it, as a ``final`` merge checks
    it (`_check_int32_sum_range`). -> the range's used slots packed to the
    front, in the partial's schema, their keys the digits of the slot
    index (`_slot_keys`): no key crosses the axis."""
    from datafusion_distributed_tpu.parallel.exchange import (
        slot_merge_exchange,
    )

    domain = math.prod(bases)
    padded = num_tasks * range_slots
    lanes: list[tuple[str, jnp.ndarray]] = []

    def lane(op, x):
        lanes.append((op, jnp.pad(x[:domain], (0, padded - domain),
                                  constant_values=_identity(op, x.dtype))))
        return len(lanes) - 1

    funcs = {spec.output_name: spec.func for spec in aggs}
    states = [name for name in columns if name not in group_names]
    at = {}  # state -> its lanes: (data, validity or None, estimate or None)
    for name in states:
        col = columns[name]
        func = funcs.get(name)  # None: a part of AVG or of the variance family
        data = lane(func if func in ("min", "max") else "sum", col.data)
        valid = None
        if func in ("sum", "min", "max") and col.validity is not None:
            valid = lane("sum", col.validity.astype(jnp.int32))
        estimate = None
        if (func == "sum" and prec_flags is not None
                and jnp.issubdtype(col.data.dtype, jnp.integer)
                and np.dtype(col.data.dtype).itemsize == 4):
            estimate = lane("sum", col.data.astype(jnp.float32))
        at[name] = (data, valid, estimate)
    counter = next((s.output_name for s in aggs if s.func == "count_star"),
                   None)
    presence = (at[counter][0] if counter is not None
                else lane("sum", slot_used.astype(jnp.int32)))
    merged = slot_merge_exchange(lanes, axis, num_tasks)

    slot = (jax.lax.axis_index(axis) * np.int32(range_slots)
            + jnp.arange(range_slots, dtype=jnp.int32))
    used = merged[presence] > 0
    (idx,) = jnp.nonzero(used, size=range_slots, fill_value=0)
    key_columns = [columns[g] for g in group_names]
    keys, key_valids = _slot_keys(key_columns, bases, slot[idx])
    out = {g: Column(k, v, c.dtype, c.dictionary)
           for g, c, k, v in zip(group_names, key_columns, keys, key_valids)}
    for name in states:
        data, valid, estimate = at[name]
        col = columns[name]
        out[name] = Column(
            merged[data][idx],
            None if valid is None else merged[valid][idx] > 0,
            col.dtype, col.dictionary)
        if estimate is not None:
            prec_flags.append(jnp.any(
                jnp.abs(merged[estimate]) > np.float32(2.0**31 * 0.995)))
    return Table.make(out, jnp.sum(used, dtype=jnp.int32))


def finalize_slot_states(table: Table, group_names: Sequence[str],
                         aggs: Sequence[AggSpec]) -> Table:
    """``final`` mode over `merge_slot_states`' rows, one a group with its
    states merged already: each aggregate's final recipe
    (`_eval_agg_columns`) with nothing left to reduce, row by row, the
    rows where they are."""
    live = table.row_mask()

    def as_is(vals, dtype=None):
        return vals.astype(dtype or vals.dtype)

    out = {g: table.column(g) for g in group_names}
    for spec in aggs:
        with jax.named_scope(f"agg.reduce.{spec.func}"):
            out.update(_eval_agg_columns(
                spec, table, jnp.arange(table.capacity, dtype=jnp.int32),
                live, table.capacity, "final", as_is,
                lambda op, ids, vals: vals, None))
    return Table.make(out, table.num_rows)


def _variance_result(func: str, s, sq, cnt):
    """(sum, sumsq, count) -> variance/stddev Column with SQL null rules
    (samp needs n>=2, pop needs n>=1)."""
    f = DataType.FLOAT64.np_dtype
    pop = func.endswith("_pop")
    sqrt = func.startswith("stddev")
    n = cnt.astype(f)
    safe_n = jnp.maximum(n, 1.0)
    mean = s.astype(f) / safe_n
    m2 = sq.astype(f) - n * mean * mean  # sum((x-mean)^2), up to rounding
    m2 = jnp.maximum(m2, 0.0)
    denom = safe_n if pop else jnp.maximum(n - 1.0, 1.0)
    var = m2 / denom
    out = jnp.sqrt(var) if sqrt else var
    valid = cnt >= (1 if pop else 2)
    return Column(out, valid, DataType.FLOAT64)


def singleton_partial_states(table: Table, group_names, aggs) -> Table:
    """Per-row singleton partial-aggregation states: for each input row,
    the accumulator a partial aggregate would emit for a one-row group.
    Schema-identical to (and mergeable by the same final stage as)
    ``hash_aggregate(mode="partial")`` over the same input — the runtime
    bail-out (runtime/adaptivity.py) swaps a non-reducing pushed-down
    partial for this pure elementwise pass, which costs no hash table
    and no claim loop. Column recipes mirror the partial-mode arms of
    `_eval_agg` with group count == 1; padding rows past ``num_rows``
    carry garbage like every other elementwise operator."""
    i64 = DataType.INT64.np_dtype
    f64 = DataType.FLOAT64.np_dtype
    cols: dict = {}
    for g in group_names:
        cols[g] = table.column(g)
    for spec in aggs:
        name = spec.output_name
        if spec.func == "count_star":
            cols[name] = Column(
                jnp.ones(table.capacity, dtype=i64), None, DataType.INT64
            )
            continue
        col = table.column(spec.input_name)
        valid = col.valid_mask()
        one = jnp.where(valid, 1, 0).astype(i64)
        if spec.func == "count":
            cols[name] = Column(one, None, DataType.INT64)
        elif spec.func == "sum":
            acc_dtype = f64 if col.dtype.is_float else i64
            vals = jnp.where(valid, col.data, 0).astype(acc_dtype)
            sum_dtype = (DataType.FLOAT64 if col.dtype.is_float
                         else DataType.INT64)
            cols[name] = Column(vals, valid, sum_dtype)
        elif spec.func == "avg":
            vals = jnp.where(valid, col.data, 0).astype(f64)
            cols[f"{name}__sum"] = Column(vals, valid, DataType.FLOAT64)
            cols[f"{name}__count"] = Column(one, None, DataType.INT64)
        elif spec.func in _VARIANCE_FUNCS:
            vals = jnp.where(valid, col.data, 0).astype(f64)
            cols[f"{name}__sum"] = Column(vals, valid, DataType.FLOAT64)
            cols[f"{name}__sumsq"] = Column(
                vals * vals, valid, DataType.FLOAT64
            )
            cols[f"{name}__count"] = Column(one, None, DataType.INT64)
        elif spec.func in ("min", "max"):
            cols[name] = Column(col.data, valid, col.dtype, col.dictionary)
        else:
            raise NotImplementedError(
                f"no singleton partial state for {spec.func}"
            )
    return Table(tuple(cols.keys()), tuple(cols.values()), table.num_rows)


def _check_int32_sum_range(vals, seg_sum, prec_flags):
    """tpu precision mode: int32 scatter-add wraps silently past 2^31, so
    estimate each group's sum in float32 alongside and flag when any group's
    magnitude approaches the boundary (conservative 0.995 factor covers the
    ~1e-7 relative error of the f32 estimate). No-op in x64 mode."""
    if prec_flags is None:
        return
    if not (
        jnp.issubdtype(vals.dtype, jnp.integer)
        and np.dtype(vals.dtype).itemsize == 4
    ):
        return
    est = seg_sum(vals.astype(jnp.float32), dtype=jnp.float32)
    prec_flags.append(jnp.any(jnp.abs(est) > np.float32(2.0**31 * 0.995)))


def _col_dtype(col: Column) -> DataType:
    return col.dtype


def _dtype_max(dt):
    if jnp.issubdtype(dt, jnp.floating):
        return np.inf
    return np.iinfo(np.dtype(dt)).max


def _dtype_min(dt):
    if jnp.issubdtype(dt, jnp.floating):
        return -np.inf
    return np.iinfo(np.dtype(dt)).min
