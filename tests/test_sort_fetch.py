"""A sort under a static ``fetch`` hands on only the fetch, rounded up
(`ops/sort.py fetch_capacity`, `plan/physical.py SortExec`): its output's
capacity is ``min(input capacity, max(round_up(fetch), 8))``, the
permutation it cuts is the whole input's, so the live rows are the full
sort's first rows bit for bit, and ``fetch_bounded_sorts`` counts the
sorts whose output the cut made smaller."""

import numpy as np
import pyarrow as pa
import pytest

from datafusion_distributed_tpu.io.parquet import arrow_to_table
from datafusion_distributed_tpu.ops.sort import SortKey
from datafusion_distributed_tpu.plan.physical import (
    DistributedTaskContext,
    ExecContext,
    LimitExec,
    MemoryScanExec,
    SortExec,
    execute_plan,
)

ROWS = 40
CAPACITY = 64


def _input():
    """40 live rows in 64 slots: ``a`` small ints with ties and NULLs,
    ``b`` floats with NaN and NULLs, ``s`` a dictionary-coded string with
    ties, ``x`` the row's position (what tells ties apart)."""
    rng = np.random.default_rng(44)
    a = rng.integers(0, 5, ROWS)
    b = rng.normal(size=ROWS)
    b[[3, 17, 29]] = np.nan
    s = rng.choice(["delta", "alpha", "echo", "bravo", "charlie"], ROWS)
    arrow = pa.table({
        "a": pa.array(a, mask=rng.random(ROWS) < 0.15),
        "b": pa.array(b, mask=rng.random(ROWS) < 0.1),
        "s": pa.array(s),
        "x": pa.array(np.arange(ROWS)),
    })
    t = arrow_to_table(arrow, capacity=CAPACITY)
    return MemoryScanExec([t], t.schema())


def _run(plan, scan):
    ctx = ExecContext(task=DistributedTaskContext(),
                      inputs={scan.node_id: scan.tasks[0]})
    return plan.execute(ctx), ctx.counters


def _rows(table, lo, hi):
    """Each column's data and validity over rows [lo, hi), as raw bytes."""
    return {
        name: (np.asarray(c.data)[lo:hi].tobytes(),
               None if c.validity is None
               else np.asarray(c.validity)[lo:hi].tobytes())
        for name, c in zip(table.names, table.columns)
    }


A_ASC = [SortKey("a", True, nulls_first=False)]


@pytest.mark.parametrize("keys,fetch,skip", [
    (A_ASC, 10, None),
    ([SortKey("a", False, nulls_first=True)], 10, None),
    ([SortKey("a", True, nulls_first=True)], 5, None),
    ([SortKey("a", False, nulls_first=False)], 5, None),
    ([SortKey("a", True), SortKey("s", False)], 12, None),
    ([SortKey("b", True)], 7, None),
    ([SortKey("b", False, nulls_first=True)], 9, None),
    ([SortKey("s", True), SortKey("x", False)], 3, None),
    (A_ASC, 0, None),
    (A_ASC, 45, None),
    (A_ASC, CAPACITY, None),
    (A_ASC, 100, None),
    # LIMIT 3 OFFSET 5: the sort's fetch is 8, the limit skips 5 of it
    (A_ASC, 8, 5),
], ids=["asc", "desc", "nulls_first", "desc_nulls_last", "two_keys_ties",
        "float_nan", "float_nan_desc", "string", "fetch_0", "fetch_ge_live",
        "fetch_eq_capacity", "fetch_ge_capacity", "limit_offset"])
def test_a_fetch_cuts_the_output_to_the_full_sorts_first_rows(keys, fetch,
                                                             skip):
    scan = _input()
    k = max(-(-fetch // 8) * 8, 8)
    cut = k < CAPACITY
    sort = SortExec(keys, scan, fetch=fetch)
    plan = sort if skip is None else LimitExec(sort, fetch - skip, skip)
    got, counters = _run(plan, scan)
    full, full_counters = _run(SortExec(keys, scan), scan)

    assert sort.output_capacity() == min(CAPACITY, k)
    assert plan.output_capacity() == got.capacity == min(CAPACITY, k)
    assert counters["fetch_bounded_sorts"] == int(cut)
    assert full_counters["fetch_bounded_sorts"] == 0
    assert full.capacity == CAPACITY
    lo = skip or 0
    live = min(ROWS, fetch) - lo
    assert int(got.num_rows) == live
    assert _rows(got, 0, live) == _rows(full, lo, lo + live)
    for mine, theirs in zip(got.columns, full.columns):
        assert mine.dictionary is theirs.dictionary
    # the compiled program gives the same rows
    compiled = execute_plan(plan)
    assert compiled.capacity == got.capacity
    assert _rows(compiled, 0, live) == _rows(got, 0, live)
