"""Pallas claim-loop hash-table build (TPU kernel).

SURVEY.md §7 hard part (b): the XLA claim loop (ops/aggregate.py
build_group_table) runs O(probe-chain) ROUNDS, each a full HBM pass over all
rows plus scatters into the [H, lanes] table. This kernel is the
VMEM-resident alternative: sequential passes over the rows with the (sub-)
table held in VMEM, so each probe is an on-chip read instead of an HBM
round.

Production shape (round 5; the round-4 version staged everything as single
VMEM blocks and was gated to 2^16 slots / 2^18 rows):

- **Row blocking.** Rows stream through a grid dimension in blocks of
  2^15; the table lives in VMEM *scratch*, which persists across grid
  steps (TPU grids execute sequentially), so row count is unbounded.
- **Tables > VMEM: hash-partitioned multi-pass.** A table of H slots is
  split into P = H / 2^16 contiguous partitions; pass p holds only
  partition p in VMEM and processes only the rows whose initial probe slot
  falls in it (same hash => same partition, so a key's whole chain is
  confined to one partition). Cost: P sequential passes over the row
  stream — the classic partitioned hash build, trading row-stream reads
  (sequential HBM bandwidth) for table residency. **Collision strategy**:
  linear probing WITHIN the partition (slot = base + ((local0 + k) mod
  H/P)); a full partition raises the overflow flag (the session's
  capacity-retry loop widens the table, exactly as for the XLA path —
  hash uniformity keeps per-partition skew < a few % at the 2x load
  factor the planner sizes for).

Trade-off being measured (benchmarks/micro_bench.py hashbuild_* rows):
- XLA claim loop: massively parallel per round, ~rounds x N x lanes HBM
  traffic; great when chains are short (table >= 2x NDV).
- This kernel: ZERO HBM traffic per probe (sub-table in VMEM), but row
  processing is sequential on the scalar unit — throughput is bounded by
  probe-chain length x scalar-op latency, not bandwidth.

The engine uses the XLA path by default; DFTPU_PALLAS=1 switches
build_group_table's group-id assignment to this kernel where legal
(single-device, table <= _MAX_TABLE_SLOTS).

STATUS: interpret-only. All three kernels of this module
(pallas_build_group_ids, pallas_global_hash_aggregate,
pallas_multiway_probe) have only ever run in interpret mode on the CPU,
where the correctness tests run them, and the TPU v5e compiler REFUSES
each of them today with ``ValueError: Cannot store scalars to VMEM``
(tests/test_tpu_compile.py holds the three strict xfails). DFTPU_PALLAS=1
on a TPU therefore raises; there is no on-chip timing of them, and the
trade-off above is a design argument, not a measurement.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# One VMEM-resident table partition: [2^16, lanes] i32 + used flags is
# ~1.5 MiB at 4 lanes, comfortably inside the ~16 MiB/core budget next to
# a 2^15-row key block.
_MAX_VMEM_SLOTS = 1 << 16
_ROW_BLOCK = 1 << 15
# Beyond 16 partitions the P full row passes stop paying for residency;
# the XLA claim loop takes over (its rounds scale with chain length, not
# table size).
_MAX_PARTITIONS = 16
_MAX_TABLE_SLOTS = _MAX_VMEM_SLOTS * _MAX_PARTITIONS

# (the legacy _MAX_VMEM_ROWS row gate is gone: row blocking removed it)

# Probe chains longer than this are treated as table-too-small, matching
# ops/join.probe_group_table's max_rounds so the pallas and XLA probe paths
# report overflow on exactly the same inputs.
_PROBE_ROUNDS = 512


class PallasCapacityError(ValueError):
    """A requested table cannot be laid out within the kernel's VMEM
    partition budget. Typed (instead of a bare ValueError) so planners can
    degrade to the XLA path and so the session's capacity-retry loop — which
    keys on the word "overflow" — does NOT spin widening a table that can
    never fit. Surfaced statically as verifier diagnostic DFTPU025."""


def pallas_available() -> bool:
    try:
        from jax.experimental import pallas as pl  # noqa: F401

        return True
    except Exception:  # pragma: no cover
        return False


def use_pallas_hash() -> bool:
    return os.environ.get("DFTPU_PALLAS", "0") == "1" and pallas_available()


@partial(jax.jit, static_argnames=("num_slots", "interpret"))
def pallas_build_group_ids(
    keys_mat: jnp.ndarray,  # [N, L] int32 folded key lanes
    slot0: jnp.ndarray,  # [N] int32 initial probe slot (hash & mask)
    live: jnp.ndarray,  # [N] bool
    num_slots: int,
    interpret: bool = False,
):
    """-> (gid [N] i32, slot_keys [H, L] i32, slot_used [H] bool,
    overflow bool). Sequential insertion semantics: the first live row of a
    key claims a slot along its (partition-confined) probe chain. Grouping
    is consistent with the XLA claim loop but slot layout may differ (see
    module docstring)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, lanes = keys_mat.shape
    h = num_slots
    assert h & (h - 1) == 0
    if h > _MAX_TABLE_SLOTS:
        raise PallasCapacityError(
            f"{h} slots exceed {_MAX_PARTITIONS} VMEM partitions"
        )
    hp = min(h, _MAX_VMEM_SLOTS)
    num_parts = h // hp
    block = min(_ROW_BLOCK, max(
        8, 1 << max(int(np.ceil(np.log2(max(n, 1)))), 3)
    ))
    n_pad = -(-n // block) * block
    nb = n_pad // block

    keys_p = jnp.zeros((n_pad, lanes), jnp.int32).at[:n].set(
        keys_mat.astype(jnp.int32)
    )
    slot0_p = jnp.zeros((n_pad,), jnp.int32).at[:n].set(
        slot0.astype(jnp.int32)
    )
    live_p = jnp.zeros((n_pad,), jnp.int32).at[:n].set(live.astype(jnp.int32))

    def partition_pass(part: int):
        """One pallas_call per table partition: rows stream through the
        grid in blocks while the partition's sub-table persists in VMEM
        scratch (TPU grids run sequentially). A separate call per
        partition keeps each pass's state machine trivial — no
        cross-partition output aliasing semantics to get wrong."""

        def kernel(keys_ref, slot0_ref, live_ref, gid_ref,
                   tkeys_ref, used_ref, over_ref, tk_s, used_s, over_s):
            b = pl.program_id(0)

            @pl.when(b == 0)
            def _():
                tk_s[:, :] = jnp.zeros((hp, lanes), jnp.int32)
                used_s[:] = jnp.zeros((hp,), jnp.int32)
                over_s[0] = jnp.int32(0)

            def row(i, _):
                s0 = slot0_ref[i]
                in_part = (s0 // hp) == part
                is_live = (live_ref[i] != 0) & in_part
                local0 = s0 % hp

                # PURE probe: walk the chain reading the sub-table; all
                # mutation happens once, after the loop (stateful ops
                # inside while bodies do not discharge reliably into
                # pallas refs)
                def probe_body(state):
                    slot, done, steps = state
                    occupied = used_s[slot] != 0
                    match = jnp.bool_(True)
                    for lane in range(lanes):
                        match = match & (
                            tk_s[slot, lane] == keys_ref[i, lane]
                        )
                    resolved = (
                        jnp.logical_not(occupied) | (occupied & match)
                    )
                    nxt = jnp.where(
                        resolved, slot, (slot + 1) % jnp.int32(hp)
                    )
                    return nxt, resolved, steps + 1

                def probe_cond(state):
                    _, done, steps = state
                    return jnp.logical_not(done) & (steps < hp) & is_live

                slot, done, _ = jax.lax.while_loop(
                    probe_cond, probe_body,
                    (local0, jnp.logical_not(is_live), jnp.int32(0)),
                )
                claim = is_live & done & (used_s[slot] == 0)

                @pl.when(claim)
                def _():
                    for lane in range(lanes):
                        tk_s[slot, lane] = keys_ref[i, lane]
                    used_s[slot] = jnp.int32(1)

                @pl.when(is_live & done)
                def _():
                    gid_ref[i] = jnp.int32(part * hp) + slot

                @pl.when(is_live & jnp.logical_not(done))
                def _():
                    over_s[0] = jnp.int32(1)

                @pl.when(jnp.logical_not(is_live))
                def _():
                    gid_ref[i] = jnp.int32(0)  # full block write, no alias

                return _

            jax.lax.fori_loop(0, block, row, None)

            @pl.when(b == nb - 1)
            def _():
                tkeys_ref[:, :] = tk_s[:, :]
                used_ref[:] = used_s[:]

            over_ref[0] = over_s[0]

        return pl.pallas_call(
            kernel,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((block, lanes), lambda b: (b, 0)),
                pl.BlockSpec((block,), lambda b: (b,)),
                pl.BlockSpec((block,), lambda b: (b,)),
            ],
            out_specs=[
                pl.BlockSpec((block,), lambda b: (b,)),
                pl.BlockSpec((hp, lanes), lambda b: (0, 0)),
                pl.BlockSpec((hp,), lambda b: (0,)),
                pl.BlockSpec((1,), lambda b: (0,)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n_pad,), jnp.int32),
                jax.ShapeDtypeStruct((hp, lanes), jnp.int32),
                jax.ShapeDtypeStruct((hp,), jnp.int32),
                jax.ShapeDtypeStruct((1,), jnp.int32),
            ],
            scratch_shapes=[
                pltpu.VMEM((hp, lanes), jnp.int32),
                pltpu.VMEM((hp,), jnp.int32),
                pltpu.SMEM((1,), jnp.int32),
            ],
            interpret=interpret,
        )(keys_p, slot0_p, live_p)

    gid = jnp.zeros((n_pad,), jnp.int32)
    part_of_row = slot0_p // hp
    tkeys_parts = []
    used_parts = []
    over = jnp.asarray(False)
    for part in range(num_parts):
        gid_p, tk_p, used_p, over_p = partition_pass(part)
        gid = jnp.where(part_of_row == part, gid_p, gid)
        tkeys_parts.append(tk_p)
        used_parts.append(used_p)
        over = over | (over_p[0] != 0)
    tkeys = jnp.concatenate(tkeys_parts, axis=0)
    used = jnp.concatenate(used_parts, axis=0)
    return gid[:n], tkeys, used.astype(jnp.bool_), over


@partial(jax.jit, static_argnames=("table_slots", "interpret"))
def pallas_multiway_probe(
    keys_mat: jnp.ndarray,  # [N, K, Lmax] int32 per-table folded key lanes
    slot0_mat: jnp.ndarray,  # [N, K] int32 LOCAL initial slot per table
    active_mat: jnp.ndarray,  # [N, K] bool-ish: live row with non-null keys
    tkeys_packed: jnp.ndarray,  # [sum(H_k), Lmax] int32 tables, concatenated
    used_packed: jnp.ndarray,  # [sum(H_k)] int32 occupancy, concatenated
    table_slots: tuple,  # static per-table slot counts (pow2, <= one VMEM part)
    interpret: bool = False,
):
    """Cascaded multi-table probe: ONE grid pass where every row walks all
    K open-addressed tables back to back (the multiway-join formulation of
    *Efficient Multiway Hash Join on Reconfigurable Hardware* — the K
    tables play the role of the K pipelined CAM stages). All K tables are
    VMEM-resident simultaneously, so the cascade costs one row-stream read
    where K binary probes cost K.

    -> (found [N, K] i32 local slot or -1, over [K] bool). Semantics are
    exactly ops/join.probe_group_table per table: linear probing from
    slot0, stop at an empty slot (absent) or a full-lane match, overflow
    after _PROBE_ROUNDS unresolved steps. Lanes beyond a table's true lane
    count must be zero-padded on BOTH sides (zero == zero keeps the
    compare neutral).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, ntab, lanes = keys_mat.shape
    assert ntab == len(table_slots)
    offsets = []
    total = 0
    for hk in table_slots:
        assert hk & (hk - 1) == 0
        if hk > _MAX_VMEM_SLOTS:
            raise PallasCapacityError(
                f"multiway probe table of {hk} slots exceeds one VMEM "
                f"partition ({_MAX_VMEM_SLOTS})"
            )
        offsets.append(total)
        total += hk
    if total != tkeys_packed.shape[0]:
        raise ValueError(
            f"packed tables hold {tkeys_packed.shape[0]} slots, "
            f"table_slots sums to {total}"
        )

    block = min(_ROW_BLOCK, max(
        8, 1 << max(int(np.ceil(np.log2(max(n, 1)))), 3)
    ))
    n_pad = -(-n // block) * block
    nb = n_pad // block

    keys_p = jnp.zeros((n_pad, ntab, lanes), jnp.int32).at[:n].set(
        keys_mat.astype(jnp.int32)
    )
    slot0_p = jnp.zeros((n_pad, ntab), jnp.int32).at[:n].set(
        slot0_mat.astype(jnp.int32)
    )
    active_p = jnp.zeros((n_pad, ntab), jnp.int32).at[:n].set(
        active_mat.astype(jnp.int32)
    )

    def kernel(keys_ref, slot0_ref, active_ref, tkeys_ref, used_ref,
               found_ref, over_ref, over_s):
        b = pl.program_id(0)

        @pl.when(b == 0)
        def _():
            for k in range(ntab):
                over_s[k] = jnp.int32(0)

        def row(i, _):
            for k in range(ntab):  # static cascade across the K tables
                off = offsets[k]
                hk = table_slots[k]
                is_act = active_ref[i, k] != 0

                def probe_body(state, _off=off, _hk=hk, _k=k):
                    slot, done, found, steps = state
                    occupied = used_ref[_off + slot] != 0
                    match = occupied
                    for lane in range(lanes):
                        match = match & (
                            tkeys_ref[_off + slot, lane]
                            == keys_ref[i, _k, lane]
                        )
                    found = jnp.where(match, slot, found)
                    resolved = jnp.logical_not(occupied) | match
                    nxt = jnp.where(
                        resolved, slot, (slot + 1) % jnp.int32(_hk)
                    )
                    return nxt, resolved, found, steps + 1

                def probe_cond(state, _is_act=is_act):
                    _slot, done, _found, steps = state
                    return (jnp.logical_not(done)
                            & (steps < _PROBE_ROUNDS) & _is_act)

                _, done, found, _ = jax.lax.while_loop(
                    probe_cond, probe_body,
                    (slot0_ref[i, k], jnp.logical_not(is_act),
                     jnp.int32(-1), jnp.int32(0)),
                )

                @pl.when(is_act & jnp.logical_not(done))
                def _(_k=k):
                    over_s[_k] = jnp.int32(1)

                found_ref[i, k] = found
            return _

        jax.lax.fori_loop(0, block, row, None)

        for k in range(ntab):
            over_ref[k] = over_s[k]

    found, over = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block, ntab, lanes), lambda b: (b, 0, 0)),
            pl.BlockSpec((block, ntab), lambda b: (b, 0)),
            pl.BlockSpec((block, ntab), lambda b: (b, 0)),
            pl.BlockSpec((total, lanes), lambda b: (0, 0)),
            pl.BlockSpec((total,), lambda b: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block, ntab), lambda b: (b, 0)),
            pl.BlockSpec((ntab,), lambda b: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, ntab), jnp.int32),
            jax.ShapeDtypeStruct((ntab,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((ntab,), jnp.int32),
        ],
        interpret=interpret,
    )(keys_p, slot0_p, active_p,
      tkeys_packed.astype(jnp.int32), used_packed.astype(jnp.int32))
    return found[:n], over.astype(jnp.bool_)


def multiway_probe_reference(keys_mat, slot0_mat, active_mat,
                             tkeys_packed, used_packed, table_slots):
    """Pure-numpy oracle for pallas_multiway_probe (same per-table
    semantics as ops/join.probe_group_table)."""
    keys_mat = np.asarray(keys_mat)
    slot0_mat = np.asarray(slot0_mat)
    active_mat = np.asarray(active_mat).astype(bool)
    tkeys_packed = np.asarray(tkeys_packed)
    used_packed = np.asarray(used_packed).astype(bool)
    n, ntab, _lanes = keys_mat.shape
    offsets = np.concatenate([[0], np.cumsum(table_slots)])[:-1]
    found = np.full((n, ntab), -1, np.int32)
    over = np.zeros(ntab, bool)
    for i in range(n):
        for k in range(ntab):
            if not active_mat[i, k]:
                continue
            off, hk = int(offsets[k]), int(table_slots[k])
            slot = int(slot0_mat[i, k])
            for _ in range(_PROBE_ROUNDS):
                if not used_packed[off + slot]:
                    break
                if (tkeys_packed[off + slot] == keys_mat[i, k]).all():
                    found[i, k] = slot
                    break
                slot = (slot + 1) % hk
            else:
                over[k] = True
    return found, over


@partial(jax.jit, static_argnames=("num_slots", "ops", "interpret"))
def pallas_global_hash_aggregate(
    keys_mat: jnp.ndarray,  # [N, L] int32 folded group-key lanes
    slot0: jnp.ndarray,  # [N] int32 initial probe slot (hash & mask)
    live: jnp.ndarray,  # [N] bool
    values: jnp.ndarray,  # [N, A] int32, identity-mapped where invalid
    num_slots: int,
    ops: tuple,  # static, per accumulator column: 'sum' | 'min' | 'max'
    interpret: bool = False,
):
    """Global-hash-table aggregation (*Global Hash Tables Strike Back!*):
    ONE shared open-addressed table builds groups AND folds accumulators in
    the same VMEM-resident pass — no per-partition tables, no merge step.
    Same partition-pass machinery as pallas_build_group_ids (a table wider
    than one VMEM partition runs P sequential passes, a key's chain
    confined to its partition).

    Callers pre-map invalid rows' values to each op's identity (sum -> 0,
    min -> INT32_MAX, max -> INT32_MIN) so the kernel needs no validity
    lanes. Accumulation is int32: callers gate on value domains that fit.

    -> (gid [N] i32 slot per live row, rep [H] i32 claiming row index,
    used [H] bool, acc [H, A] i32, overflow bool). gid lets the caller
    run follow-up per-group scatters (e.g. the int32 sum-range guard)
    without a second build pass.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, lanes = keys_mat.shape
    _n2, na = values.shape
    assert na == len(ops)
    h = num_slots
    assert h & (h - 1) == 0
    if h > _MAX_TABLE_SLOTS:
        raise PallasCapacityError(
            f"{h} slots exceed {_MAX_PARTITIONS} VMEM partitions"
        )
    hp = min(h, _MAX_VMEM_SLOTS)
    num_parts = h // hp
    block = min(_ROW_BLOCK, max(
        8, 1 << max(int(np.ceil(np.log2(max(n, 1)))), 3)
    ))
    n_pad = -(-n // block) * block
    nb = n_pad // block

    _IDENT = {
        "sum": 0,
        "min": np.iinfo(np.int32).max,
        "max": np.iinfo(np.int32).min,
    }
    ident_list = [_IDENT[op] for op in ops]  # static: inlined in-kernel
    ident_row = jnp.asarray(ident_list, jnp.int32)

    keys_p = jnp.zeros((n_pad, lanes), jnp.int32).at[:n].set(
        keys_mat.astype(jnp.int32)
    )
    slot0_p = jnp.zeros((n_pad,), jnp.int32).at[:n].set(
        slot0.astype(jnp.int32)
    )
    live_p = jnp.zeros((n_pad,), jnp.int32).at[:n].set(live.astype(jnp.int32))
    vals_p = jnp.broadcast_to(ident_row, (n_pad, na)).at[:n].set(
        values.astype(jnp.int32)
    )

    def partition_pass(part: int):
        def kernel(keys_ref, slot0_ref, live_ref, vals_ref,
                   gid_ref, rep_ref, used_ref, acc_ref, over_ref,
                   tk_s, used_s, rep_s, acc_s, over_s):
            b = pl.program_id(0)

            @pl.when(b == 0)
            def _():
                tk_s[:, :] = jnp.zeros((hp, lanes), jnp.int32)
                used_s[:] = jnp.zeros((hp,), jnp.int32)
                rep_s[:] = jnp.zeros((hp,), jnp.int32)
                for a in range(na):  # scalar fills: no vector constant
                    acc_s[:, a] = jnp.full((hp,), ident_list[a], jnp.int32)
                over_s[0] = jnp.int32(0)

            def row(i, _):
                s0 = slot0_ref[i]
                in_part = (s0 // hp) == part
                is_live = (live_ref[i] != 0) & in_part
                local0 = s0 % hp

                def probe_body(state):
                    slot, done, steps = state
                    occupied = used_s[slot] != 0
                    match = jnp.bool_(True)
                    for lane in range(lanes):
                        match = match & (
                            tk_s[slot, lane] == keys_ref[i, lane]
                        )
                    resolved = (
                        jnp.logical_not(occupied) | (occupied & match)
                    )
                    nxt = jnp.where(
                        resolved, slot, (slot + 1) % jnp.int32(hp)
                    )
                    return nxt, resolved, steps + 1

                def probe_cond(state):
                    _, done, steps = state
                    return jnp.logical_not(done) & (steps < hp) & is_live

                slot, done, _ = jax.lax.while_loop(
                    probe_cond, probe_body,
                    (local0, jnp.logical_not(is_live), jnp.int32(0)),
                )
                claim = is_live & done & (used_s[slot] == 0)

                @pl.when(claim)
                def _():
                    for lane in range(lanes):
                        tk_s[slot, lane] = keys_ref[i, lane]
                    used_s[slot] = jnp.int32(1)
                    rep_s[slot] = jnp.int32(b * block) + i

                @pl.when(is_live & done)
                def _():
                    gid_ref[i] = jnp.int32(part * hp) + slot
                    for a in range(na):  # static accumulator plan
                        if ops[a] == "sum":
                            acc_s[slot, a] = acc_s[slot, a] + vals_ref[i, a]
                        elif ops[a] == "min":
                            acc_s[slot, a] = jnp.minimum(
                                acc_s[slot, a], vals_ref[i, a]
                            )
                        else:
                            acc_s[slot, a] = jnp.maximum(
                                acc_s[slot, a], vals_ref[i, a]
                            )

                @pl.when(is_live & jnp.logical_not(done))
                def _():
                    over_s[0] = jnp.int32(1)

                @pl.when(jnp.logical_not(is_live))
                def _():
                    gid_ref[i] = jnp.int32(0)  # full block write, no alias

                return _

            jax.lax.fori_loop(0, block, row, None)

            @pl.when(b == nb - 1)
            def _():
                rep_ref[:] = rep_s[:]
                used_ref[:] = used_s[:]
                acc_ref[:, :] = acc_s[:, :]

            over_ref[0] = over_s[0]

        return pl.pallas_call(
            kernel,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((block, lanes), lambda b: (b, 0)),
                pl.BlockSpec((block,), lambda b: (b,)),
                pl.BlockSpec((block,), lambda b: (b,)),
                pl.BlockSpec((block, na), lambda b: (b, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block,), lambda b: (b,)),
                pl.BlockSpec((hp,), lambda b: (0,)),
                pl.BlockSpec((hp,), lambda b: (0,)),
                pl.BlockSpec((hp, na), lambda b: (0, 0)),
                pl.BlockSpec((1,), lambda b: (0,)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n_pad,), jnp.int32),
                jax.ShapeDtypeStruct((hp,), jnp.int32),
                jax.ShapeDtypeStruct((hp,), jnp.int32),
                jax.ShapeDtypeStruct((hp, na), jnp.int32),
                jax.ShapeDtypeStruct((1,), jnp.int32),
            ],
            scratch_shapes=[
                pltpu.VMEM((hp, lanes), jnp.int32),
                pltpu.VMEM((hp,), jnp.int32),
                pltpu.VMEM((hp,), jnp.int32),
                pltpu.VMEM((hp, na), jnp.int32),
                pltpu.SMEM((1,), jnp.int32),
            ],
            interpret=interpret,
        )(keys_p, slot0_p, live_p, vals_p)

    gid = jnp.zeros((n_pad,), jnp.int32)
    part_of_row = slot0_p // hp
    rep_parts, used_parts, acc_parts = [], [], []
    over = jnp.asarray(False)
    for part in range(num_parts):
        gid_p, rep_p, used_p, acc_p, over_p = partition_pass(part)
        gid = jnp.where(part_of_row == part, gid_p, gid)
        rep_parts.append(rep_p)
        used_parts.append(used_p)
        acc_parts.append(acc_p)
        over = over | (over_p[0] != 0)
    rep = jnp.concatenate(rep_parts, axis=0)
    used = jnp.concatenate(used_parts, axis=0)
    acc = jnp.concatenate(acc_parts, axis=0)
    return gid[:n], rep, used.astype(jnp.bool_), acc, over


def global_hash_aggregate_reference(keys_mat, slot0, live, values,
                                    num_slots, ops):
    """Pure-numpy oracle for pallas_global_hash_aggregate (same
    partition-confined sequential-insert semantics as
    build_group_ids_reference, plus the accumulator fold)."""
    gid, _tkeys, used, overflow = build_group_ids_reference(
        keys_mat, slot0, live, num_slots
    )
    values = np.asarray(values)
    live = np.asarray(live).astype(bool)
    n, na = values.shape
    _IDENT = {
        "sum": 0,
        "min": np.iinfo(np.int32).max,
        "max": np.iinfo(np.int32).min,
    }
    acc = np.tile(
        np.asarray([_IDENT[op] for op in ops], np.int32), (num_slots, 1)
    )
    rep = np.zeros(num_slots, np.int32)
    seen = np.zeros(num_slots, bool)
    for i in range(n):
        if not live[i]:
            continue
        s = int(gid[i])
        if not seen[s]:
            rep[s] = i
            seen[s] = True
        for a, op in enumerate(ops):
            if op == "sum":
                acc[s, a] = np.int32(acc[s, a] + values[i, a])
            elif op == "min":
                acc[s, a] = min(acc[s, a], values[i, a])
            else:
                acc[s, a] = max(acc[s, a], values[i, a])
    return gid, rep, used, acc, overflow


def build_group_ids_reference(keys_mat, slot0, live, num_slots):
    """Pure-numpy oracle for the kernel's sequential-insert semantics
    (partition-confined linear probing, partition width = _MAX_VMEM_SLOTS)."""
    keys_mat = np.asarray(keys_mat)
    slot0 = np.asarray(slot0)
    live = np.asarray(live)
    n, lanes = keys_mat.shape
    hp = min(num_slots, _MAX_VMEM_SLOTS)
    tkeys = np.zeros((num_slots, lanes), np.int32)
    used = np.zeros(num_slots, bool)
    gid = np.zeros(n, np.int32)
    overflow = False
    for i in range(n):
        if not live[i]:
            continue
        base = (int(slot0[i]) // hp) * hp
        local = int(slot0[i]) % hp
        for _ in range(hp):
            slot = base + local
            if not used[slot]:
                tkeys[slot] = keys_mat[i]
                used[slot] = True
                gid[i] = slot
                break
            if (tkeys[slot] == keys_mat[i]).all():
                gid[i] = slot
                break
            local = (local + 1) % hp
        else:
            overflow = True
    return gid, tkeys, used, overflow
