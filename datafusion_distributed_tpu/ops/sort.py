"""Sorting kernels: multi-key lexicographic sort, limit, top-k.

The reference uses DataFusion's `SortExec`/`SortPreservingMergeExec`
(SURVEY.md L0; the distributed planner treats a sort above a stage as a
coalesce point, `inject_network_boundaries.rs` sort/coalesce case). XLA has a
high-quality parallel sort, so the TPU design is: stable argsort per key from
least- to most-significant (radix-style composition), with dead/padding rows
forced to the tail so `num_rows` semantics survive.

String keys sort by dictionary code (dictionaries are sorted => code order is
lexicographic). Nulls order via a separate flag pass (no in-band sentinel).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp

from datafusion_distributed_tpu.ops.table import Table, _round_up, scoped


@dataclass(frozen=True)
class SortKey:
    name: str
    ascending: bool = True
    nulls_first: bool = False


@scoped("sort.permutation")
def sort_permutation(table: Table, keys: list[SortKey]) -> jnp.ndarray:
    """[capacity] permutation: live rows in key order first, dead rows last.

    ONE `lax.sort` call with a lexicographic operand list — most-significant
    first: [dead-row flag, key1 null flag, key1 values, key2 ...] — instead
    of composing per-key stable argsorts. The composed form paid up to
    2 sorts per key + a dead-row pass over the FULL padded capacity (a
    2-key sort over a 1M-capacity aggregate output ran 5 million-row
    argsorts: ~2.5 s of TPC-H q3's 2.8 s wall on the CPU tier); the fused
    form pays exactly one."""
    import jax

    cap = table.capacity
    operands: list[jnp.ndarray] = [~table.row_mask()]  # live rows first
    for key in keys:
        col = table.column(key.name)
        if col.validity is not None:
            # null placement dominates this key's value order
            flag = col.validity if key.nulls_first else ~col.validity
            operands.append(flag)  # False sorts first
        vals = col.data
        if vals.dtype == jnp.bool_:
            vals = vals.astype(jnp.int32)
        if not key.ascending:
            if jnp.issubdtype(vals.dtype, jnp.floating):
                vals = -vals
            else:
                # avoid signed overflow on INT_MIN: flip via complement
                vals = ~vals if jnp.issubdtype(vals.dtype, jnp.integer) else -vals
        operands.append(vals)
    perm0 = jnp.arange(cap, dtype=jnp.int32)
    out = jax.lax.sort(
        tuple(operands) + (perm0,), num_keys=len(operands), is_stable=True
    )
    return out[-1]


def fetch_capacity(fetch: Optional[int], capacity: int) -> int:
    """The capacity of a sort's output under a static ``fetch``: the fetch
    rounded up to the sublane (8 at least), or ``capacity`` where that is
    not smaller or there is no fetch."""
    if fetch is None:
        return capacity
    return min(capacity, max(_round_up(fetch), 8))


def sort_table(table: Table, keys: list[SortKey],
               fetch: Optional[int] = None) -> Table:
    """The table in key order, dead rows last; under a static ``fetch``
    the first ``fetch`` rows of it. The permutation is always the whole
    input's; where `fetch_capacity` is below the input's capacity only its
    first entries are gathered, so a top-k over a wide table moves k rows
    and not the table."""
    perm = sort_permutation(table, keys)
    k = fetch_capacity(fetch, table.capacity)
    if k < table.capacity:
        perm = perm[:k]
    out = table.gather(perm, table.num_rows)
    return out if fetch is None else out.head(fetch)


def limit_table(table: Table, fetch, skip=0) -> Table:
    """LIMIT fetch OFFSET skip over an ordered table (jit-safe)."""
    cap = table.capacity
    skip = jnp.asarray(skip, dtype=jnp.int32)
    fetch = jnp.asarray(fetch, dtype=jnp.int32)
    remaining = jnp.maximum(table.num_rows - skip, 0)
    n = jnp.minimum(remaining, fetch)
    idx = jnp.clip(jnp.arange(cap, dtype=jnp.int32) + skip, 0, cap - 1)
    return table.gather(idx, n)
