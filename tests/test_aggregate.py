"""Hash aggregate kernel golden tests vs pandas groupby."""

import jax
import jax.numpy as jnp
import numpy as np

from datafusion_distributed_tpu import precision as _precision

# f32 compute in tpu precision mode: summation-order differences are ~eps
FLOAT_RTOL = _precision.test_rtol()

import pandas as pd
import pyarrow as pa
import pytest

from datafusion_distributed_tpu.io.parquet import arrow_to_table
from datafusion_distributed_tpu.ops.aggregate import AggSpec, hash_aggregate


def _run(table, groups, aggs, slots=64, mode="single"):
    out, overflow = jax.jit(
        lambda t: hash_aggregate(t, groups, aggs, slots, mode),
        static_argnames=(),
    )(table)
    assert not bool(overflow)
    return out.to_pandas()


def test_groupby_sum_count():
    rng = np.random.default_rng(0)
    k = rng.integers(0, 10, 1000)
    v = rng.normal(size=1000)
    t = arrow_to_table(pa.table({"k": k, "v": v}))
    got = _run(
        t, ["k"],
        [AggSpec("sum", "v", "sv"), AggSpec("count_star", None, "n")],
    ).sort_values("k").reset_index(drop=True)
    exp = (
        pd.DataFrame({"k": k, "v": v})
        .groupby("k")
        .agg(sv=("v", "sum"), n=("v", "size"))
        .reset_index()
    )
    np.testing.assert_array_equal(got["k"], exp["k"])
    np.testing.assert_allclose(got["sv"], exp["sv"], rtol=FLOAT_RTOL)
    np.testing.assert_array_equal(got["n"], exp["n"])


def test_groupby_min_max_avg():
    rng = np.random.default_rng(1)
    k = rng.integers(0, 7, 500)
    v = rng.integers(-1000, 1000, 500)
    t = arrow_to_table(pa.table({"k": k, "v": v}))
    got = _run(
        t, ["k"],
        [AggSpec("min", "v", "mn"), AggSpec("max", "v", "mx"),
         AggSpec("avg", "v", "av")],
    ).sort_values("k").reset_index(drop=True)
    exp = (
        pd.DataFrame({"k": k, "v": v})
        .groupby("k")
        .agg(mn=("v", "min"), mx=("v", "max"), av=("v", "mean"))
        .reset_index()
    )
    np.testing.assert_array_equal(got["mn"], exp["mn"])
    np.testing.assert_array_equal(got["mx"], exp["mx"])
    np.testing.assert_allclose(got["av"], exp["av"], rtol=FLOAT_RTOL)


def test_multi_key_with_strings_and_nulls():
    t = arrow_to_table(
        pa.table(
            {
                "a": pa.array(["x", "y", "x", None, "y", None]),
                "b": pa.array([1, 1, 1, 2, None, 2], type=pa.int64()),
                "v": pa.array([10.0, 20.0, 30.0, 40.0, 50.0, 60.0]),
            }
        )
    )
    got = _run(
        t, ["a", "b"],
        [AggSpec("sum", "v", "sv"), AggSpec("count", "v", "cv")],
        slots=16,
    )
    got = got.sort_values(["a", "b"], na_position="last").reset_index(drop=True)
    # groups: (x,1)->40, (y,1)->20, (y,null)->50, (null,2)->100
    assert len(got) == 4
    gx1 = got[(got["a"] == "x") & (got["b"] == 1)]
    assert float(gx1["sv"].iloc[0]) == 40.0 and int(gx1["cv"].iloc[0]) == 2
    gnull2 = got[got["a"].isna()]
    assert float(gnull2["sv"].iloc[0]) == 100.0


def test_partial_then_final_equals_single():
    """The distributed contract: partial on shards + final == single-node."""
    rng = np.random.default_rng(2)
    k = rng.integers(0, 20, 2000)
    v = rng.normal(size=2000)
    full = arrow_to_table(pa.table({"k": k, "v": v}))
    aggs = [
        AggSpec("sum", "v", "sv"),
        AggSpec("count", "v", "cv"),
        AggSpec("min", "v", "mn"),
        AggSpec("max", "v", "mx"),
        AggSpec("avg", "v", "av"),
    ]
    single = _run(full, ["k"], aggs, slots=128).sort_values("k").reset_index(drop=True)

    # shard into two halves, partial-aggregate each, concat, final-aggregate
    from datafusion_distributed_tpu.ops.table import concat_tables

    h1 = arrow_to_table(pa.table({"k": k[:1000], "v": v[:1000]}), capacity=2048)
    h2 = arrow_to_table(pa.table({"k": k[1000:], "v": v[1000:]}), capacity=2048)
    p1, o1 = hash_aggregate(h1, ["k"], aggs, 128, "partial")
    p2, o2 = hash_aggregate(h2, ["k"], aggs, 128, "partial")
    assert not bool(o1) and not bool(o2)
    merged = concat_tables([p1, p2], capacity=256)
    fin, o3 = hash_aggregate(merged, ["k"], aggs, 128, "final")
    assert not bool(o3)
    fin = fin.to_pandas().sort_values("k").reset_index(drop=True)
    np.testing.assert_array_equal(fin["k"], single["k"])
    np.testing.assert_allclose(fin["sv"], single["sv"], rtol=FLOAT_RTOL)
    np.testing.assert_array_equal(fin["cv"], single["cv"])
    np.testing.assert_array_equal(fin["mn"], single["mn"])
    np.testing.assert_array_equal(fin["mx"], single["mx"])
    np.testing.assert_allclose(fin["av"], single["av"], rtol=FLOAT_RTOL)


def test_overflow_flag():
    k = np.arange(100)  # 100 distinct groups
    t = arrow_to_table(pa.table({"k": k, "v": k * 1.0}))
    _, overflow = hash_aggregate(
        t, ["k"], [AggSpec("sum", "v", "s")], num_slots=32
    )
    assert bool(overflow)


def test_high_collision_pressure():
    """num_slots barely above NDV: linear probing must still resolve."""
    rng = np.random.default_rng(3)
    k = rng.integers(0, 120, 4000)
    t = arrow_to_table(pa.table({"k": k, "v": np.ones(4000)}))
    out, overflow = hash_aggregate(
        t, ["k"], [AggSpec("count_star", None, "n")], num_slots=128,
        mode="single",
    )
    assert not bool(overflow)
    got = out.to_pandas().sort_values("k").reset_index(drop=True)
    exp = pd.Series(k).value_counts().sort_index()
    np.testing.assert_array_equal(got["k"], exp.index)
    np.testing.assert_array_equal(got["n"], exp.values)


def test_partial_reduce_tree_equals_single():
    """4 shards -> partial, pairwise partial_reduce merges, then final ==
    single (the progressive reduction tree of AggregateMode::PartialReduce,
    examples/custom_partial_reduction_tree.py)."""
    from datafusion_distributed_tpu.ops.table import concat_tables

    rng = np.random.default_rng(9)
    k = rng.integers(0, 15, 4000)
    v = rng.normal(size=4000)
    full = arrow_to_table(pa.table({"k": k, "v": v}))
    aggs = [
        AggSpec("sum", "v", "sv"),
        AggSpec("count", "v", "cv"),
        AggSpec("min", "v", "mn"),
        AggSpec("max", "v", "mx"),
        AggSpec("avg", "v", "av"),
        AggSpec("var_samp", "v", "vr"),
        AggSpec("count_star", None, "n"),
    ]
    single = _run(full, ["k"], aggs, slots=128).sort_values("k").reset_index(
        drop=True
    )

    shards = [
        arrow_to_table(
            pa.table({"k": k[i::4], "v": v[i::4]}), capacity=2048
        )
        for i in range(4)
    ]
    partials = [hash_aggregate(s, ["k"], aggs, 128, "partial")[0]
                for s in shards]
    # level 1: merge states pairwise, OUTPUT STAYS IN STATE FORM
    l1 = []
    for a, b in ((0, 1), (2, 3)):
        m = concat_tables([partials[a], partials[b]], capacity=256)
        r, ov = hash_aggregate(m, ["k"], aggs, 128, "partial_reduce")
        assert not bool(ov)
        l1.append(r)
    # level 2: final over the merged states
    m = concat_tables(l1, capacity=256)
    fin, ov = hash_aggregate(m, ["k"], aggs, 128, "final")
    assert not bool(ov)
    fin = fin.to_pandas().sort_values("k").reset_index(drop=True)
    np.testing.assert_array_equal(fin["k"], single["k"])
    # atol: group sums of zero-mean data land near 0, where an rtol-only
    # comparison of two equally-f32-accurate layouts (mean-shifted
    # accumulation centers differ per chunk) is meaningless
    np.testing.assert_allclose(fin["sv"], single["sv"], rtol=FLOAT_RTOL,
                               atol=2e-6)
    np.testing.assert_array_equal(fin["cv"], single["cv"])
    np.testing.assert_array_equal(fin["mn"], single["mn"])
    np.testing.assert_array_equal(fin["mx"], single["mx"])
    np.testing.assert_allclose(fin["av"], single["av"], rtol=FLOAT_RTOL,
                               atol=2e-6)
    np.testing.assert_allclose(fin["vr"], single["vr"], rtol=FLOAT_RTOL * 10)
    np.testing.assert_array_equal(fin["n"], single["n"])


# ---------------------------------------------------------------------------
# direct addressing: dictionary-coded keys group by arithmetic on their codes
# ---------------------------------------------------------------------------

_DIRECT_AGGS = [
    AggSpec("sum", "v", "sv"),
    AggSpec("sum", "i", "si"),
    AggSpec("count", "v", "cv"),
    AggSpec("count_star", None, "n"),
    AggSpec("min", "i", "mn"),
    AggSpec("max", "i", "mx"),
    AggSpec("avg", "v", "av"),
]


def _direct_case(case):
    """-> (table, group names, num_slots, live(table) -> mask or None, the
    domain if the direct path must engage else None)."""
    from datafusion_distributed_tpu.ops import aggregate
    from datafusion_distributed_tpu.ops.table import Dictionary, Table

    rng = np.random.default_rng(11)
    n = 600
    a = rng.choice(["x", "y", "z"], n).astype(object)
    b = rng.choice(["p", "q"], n).astype(object)
    v = pa.array(rng.integers(-40, 40, n) / 4.0,  # quarters: sums are exact
                 mask=rng.random(n) < 0.1)
    columns = {"a": a, "b": b, "i": rng.integers(-1000, 1000, n), "v": v}
    groups, slots, live, domain = ["a", "b"], 64, (lambda t: None), 3 * 2
    nullable, dictionaries = (), None
    if case == "nullable_key":  # NULL is a group of its own
        a[rng.random(n) < 0.2] = None
        nullable, domain = ("a",), (3 + 1) * 2
    elif case == "code_never_occurs":  # no output row for "w"
        dictionaries = {"a": Dictionary.from_strings(["w", "x", "y", "z"])}
        domain = 4 * 2
    elif case == "no_live_row":
        live = lambda t: jnp.zeros(t.capacity, dtype=jnp.bool_)  # noqa: E731
    elif case == "live_with_holes":
        live = lambda t: t.row_mask() & jnp.asarray(  # noqa: E731
            np.random.default_rng(5).random(t.capacity) < 0.5)
    elif case == "domain_equals_slots":
        columns["a"] = rng.choice(["w", "x", "y", "z"], n).astype(object)
        slots, domain = 8, 4 * 2
    elif case == "domain_over_slots":  # 8 > 4: the claim loop, 4 groups
        columns["a"] = rng.choice(["w", "x", "y", "z"], n).astype(object)
        columns["b"] = np.where(np.isin(columns["a"], ["w", "y"]), "p", "q")
        slots, domain = 4, None
    elif case == "wide_domain":  # past the compares: presence by scatter
        columns["a"] = rng.integers(0, 100, n).astype(str).astype(object)
        columns["b"] = rng.integers(0, 100, n).astype(str).astype(object)
        vocabulary = Dictionary.from_strings(sorted(map(str, range(100))))
        dictionaries = {"a": vocabulary, "b": vocabulary}
        slots, domain = 16384, 100 * 100
        assert domain > aggregate._DENSE_MAX_DOMAIN
    elif case == "key_without_dictionary":
        groups, domain = ["a", "k"], None
        columns["k"] = rng.integers(0, 3, n)
    else:
        assert case == "two_keys_3x2"
    t = arrow_to_table(pa.table(columns), dictionaries=dictionaries)
    # arrow ingestion gives every column a validity array; a key that is
    # not nullable carries none after a projection or an aggregate
    cols = tuple(c if name in nullable or name not in groups
                 else c.with_validity(None)
                 for name, c in zip(t.names, t.columns))
    return Table(t.names, cols, t.num_rows), groups, slots, live, domain


def _without_dictionaries(table, groups):
    """The same table, its key columns plain integers: `hash_aggregate`'s
    reference path, the claim loop."""
    from datafusion_distributed_tpu.ops.table import Column, Table

    cols = tuple(Column(c.data, c.validity, c.dtype, None) if name in groups
                 else c for name, c in zip(table.names, table.columns))
    return Table(table.names, cols, table.num_rows)


def _group_rows(out, groups) -> dict:
    """key tuple (None for a NULL key) -> the row's other values."""
    n = int(out.num_rows)
    cols = [(np.asarray(c.data)[:n],
             None if c.validity is None else np.asarray(c.validity)[:n])
            for c in out.columns]
    rows = {}
    for r in range(n):
        vals = tuple(None if valid is not None and not valid[r]
                     else data[r].item() for data, valid in cols)
        rows[vals[:len(groups)]] = vals[len(groups):]
    assert len(rows) == n  # no group twice
    return rows


@pytest.mark.parametrize("mode", ["single", "partial", "final",
                                  "partial_reduce"])
@pytest.mark.parametrize("case", [
    "two_keys_3x2", "nullable_key", "code_never_occurs", "no_live_row",
    "live_with_holes", "domain_equals_slots", "domain_over_slots",
    "wide_domain", "key_without_dictionary",
])
def test_direct_grouping_equals_the_claim_loop(case, mode, monkeypatch):
    """Dictionary-coded keys whose domain fits the planned table group by
    the mixed-radix number of their codes and build no table; the result
    is the claim loop's (`build_group_table`, reached by the same call
    over the same keys without their dictionaries), group for group:
    integers and counts exactly, floats at the oracle's tolerance. A
    domain over the planned width, or a key with no dictionary, falls
    back to the claim loop."""
    from datafusion_distributed_tpu.ops import aggregate
    from datafusion_distributed_tpu.ops.table import concat_tables

    claims = []
    build = aggregate.build_group_table
    monkeypatch.setattr(
        aggregate, "build_group_table",
        lambda *a, **k: claims.append(1) or build(*a, **k))
    table, groups, slots, live, domain = _direct_case(case)
    if mode in ("final", "partial_reduce"):
        # two partial states a group, from the halves of the rows
        halves = [table.row_mask() & (jnp.arange(table.capacity) % 2 == h)
                  for h in (0, 1)]
        states = [hash_aggregate(table, groups, _DIRECT_AGGS, slots,
                                 "partial", live=half)[0] for half in halves]
        table = concat_tables(states, capacity=2 * states[0].capacity)
        if case == "nullable_key":
            assert table.column("a").validity is not None
        claims.clear()

    def run(t, direct):
        out, overflow = jax.jit(
            lambda t: hash_aggregate(t, groups, _DIRECT_AGGS, slots, mode,
                                     live=live(t), direct=direct))(t)
        assert not bool(overflow)
        return out

    direct: list = []
    got = run(table, direct)
    assert direct == ([] if domain is None else [domain])
    assert len(claims) == (1 if domain is None else 0)
    unused: list = []
    want = run(_without_dictionaries(table, groups), unused)
    assert unused == [] and len(claims) == (2 if domain is None else 1)

    assert got.names == want.names and got.capacity == want.capacity
    got_rows, want_rows = _group_rows(got, groups), _group_rows(want, groups)
    assert set(got_rows) == set(want_rows)
    if case == "no_live_row":
        assert got_rows == {}
    elif case == "nullable_key":
        assert any(key[0] is None for key in got_rows)
    elif case == "code_never_occurs":
        assert all(key[0] != 0 for key in got_rows) and len(got_rows) == 6
    for key, want_vals in want_rows.items():
        for name, g, w in zip(got.names[len(groups):], got_rows[key],
                              want_vals):
            if isinstance(w, float):
                # the benchmark's oracle: 5e-4 relative, 1e-4 absolute
                np.testing.assert_allclose(g, w, rtol=5e-4, atol=1e-4,
                                           err_msg=f"{key} {name}")
            else:
                assert g == w and type(g) is type(w), (key, name)


# ---------------------------------------------------------------------------
# past the dense cut: the used slots are read off a COUNT(*) already reduced
# ---------------------------------------------------------------------------

_WIDE_LIVE = {
    "all_rows": lambda t: t.row_mask(),
    "no_live_row": lambda t: jnp.zeros(t.capacity, dtype=jnp.bool_),
    "live_with_holes": lambda t: t.row_mask() & jnp.asarray(
        np.random.default_rng(5).random(t.capacity) < 0.5),
}
_WIDE_AGGS = {
    "count_star": [AggSpec("count_star", None, "n")],
    "count_star_and_sum": [AggSpec("count_star", None, "n"),
                           AggSpec("sum", "v", "sv")],
    "sum_only": [AggSpec("sum", "v", "sv")],
}


def _run_wide(table, groups, aggs, slots, mode, live):
    """-> (result, direct, scatters, presence_from_count) of one jitted
    `hash_aggregate`."""
    direct, scatters, from_count = [], [], []
    out, overflow = jax.jit(
        lambda t: hash_aggregate(t, groups, aggs, slots, mode, live=live(t),
                                 direct=direct, scatters=scatters,
                                 presence_from_count=from_count))(table)
    assert not bool(overflow)
    return out, direct, scatters, from_count


def _assert_claim_loop_groups(got, table, groups, aggs, slots, mode, live):
    """The same groups, values and group count as the claim loop's."""
    want, *_ = _run_wide(_without_dictionaries(table, groups), groups, aggs,
                         slots, mode, live)
    assert got.names == want.names and got.capacity == want.capacity
    assert int(got.num_rows) == int(want.num_rows)
    got_rows, want_rows = _group_rows(got, groups), _group_rows(want, groups)
    assert got_rows.keys() == want_rows.keys()
    for key, want_vals in want_rows.items():
        _assert_same_values(got.names[len(groups):], got_rows[key],
                            want_vals, key)


@pytest.mark.parametrize("mode", ["single", "partial"])
@pytest.mark.parametrize("aggs", sorted(_WIDE_AGGS))
@pytest.mark.parametrize("live", sorted(_WIDE_LIVE))
def test_a_wide_direct_grouping_reads_its_presence_off_the_count(
        live, aggs, mode, monkeypatch):
    """A direct grouping past `_DENSE_MAX_DOMAIN` (10,000 codes) whose
    aggregates hold COUNT(*) over raw rows scatters that count once and
    reads its used slots off it: the result is the claim loop's, group for
    group, and bit for bit the presence scatter's (the same call with no
    aggregate taken for a row count), groups in the same packed order. A
    SUM alone keeps the presence scatter."""
    from datafusion_distributed_tpu.ops import aggregate

    table, groups, slots, _, domain = _direct_case("wide_domain")
    specs = _WIDE_AGGS[aggs]
    engaged = aggs != "sum_only"
    got, direct, scatters, from_count = _run_wide(
        table, groups, specs, slots, mode, _WIDE_LIVE[live])
    assert direct == [domain]
    assert from_count == ([domain] if engaged else [])
    assert ("presence" in scatters) is not engaged
    _assert_claim_loop_groups(got, table, groups, specs, slots, mode,
                              _WIDE_LIVE[live])

    monkeypatch.setattr(aggregate, "_counts_live_rows", lambda *a: False)
    by_scatter, _, scatters_before, none = _run_wide(
        table, groups, specs, slots, mode, _WIDE_LIVE[live])
    assert "presence" in scatters_before and none == []
    assert len(scatters_before) == len(scatters) + engaged
    assert _same_bits(got, by_scatter)
    if live == "no_live_row":
        assert int(got.num_rows) == 0


@pytest.mark.parametrize("mode", ["final", "partial_reduce"])
def test_a_wide_merge_of_partial_counts_keeps_its_presence_scatter(
        mode, monkeypatch):
    """Merging partial states, COUNT(*) sums partial counts, not rows: a
    wide direct grouping there keeps its presence scatter, and its result
    is the claim loop's."""
    from datafusion_distributed_tpu.ops.table import concat_tables

    table, groups, slots, _, domain = _direct_case("wide_domain")
    halves = [table.row_mask() & (jnp.arange(table.capacity) % 2 == h)
              for h in (0, 1)]
    states = [hash_aggregate(table, groups, _DIRECT_AGGS, slots, "partial",
                             live=half)[0] for half in halves]
    merged = concat_tables(states, capacity=2 * states[0].capacity)
    live = _WIDE_LIVE["all_rows"]
    got, direct, scatters, from_count = _run_wide(
        merged, groups, _DIRECT_AGGS, slots, mode, live)
    assert direct == [domain] and from_count == []
    assert "presence" in scatters
    _assert_claim_loop_groups(got, merged, groups, _DIRECT_AGGS, slots, mode,
                              live)


# ---------------------------------------------------------------------------
# dense reductions: a small known domain reduces by masked passes, not scatters
# ---------------------------------------------------------------------------

_DENSE_AGGS = _DIRECT_AGGS + [AggSpec("var_samp", "v", "vr")]


def _reduction_spy(monkeypatch):
    """-> the list that collects ``dense_domain`` of every
    `_reduce_by_slot` call traced from here on."""
    from datafusion_distributed_tpu.ops import aggregate

    seen: list = []
    reduce_by_slot = aggregate._reduce_by_slot

    def spy(op, ids, vals, num_slots, dense_domain):
        seen.append(dense_domain)
        return reduce_by_slot(op, ids, vals, num_slots, dense_domain)

    monkeypatch.setattr(aggregate, "_reduce_by_slot", spy)
    return seen


def _dense_case(case):
    """-> (table, group names, num_slots, live(table), domain, the cut to
    run under or None for the module's)."""
    base = {"nulls_and_holes": "live_with_holes",
            "no_live_row": "no_live_row",
            "unused_slot": "code_never_occurs"}.get(case, "two_keys_3x2")
    table, groups, slots, live, domain = _direct_case(base)
    # the rows of one group: the first code of either key
    first = (np.asarray(table.column("a").data) == 0) & (
        np.asarray(table.column("b").data) == 0)
    cut = None
    if case == "nulls_and_holes":
        # every value of one group NULL: its sum, avg, min, max are NULL
        for name in ("v", "i"):
            col = table.column(name)
            table = table.with_column(name, col.with_validity(
                col.valid_mask() & ~jnp.asarray(first)))
    elif case == "nan_and_inf":
        rows = np.flatnonzero(first)[:2]
        col = table.column("v")
        table = table.with_column("v", type(col)(
            col.data.at[rows].set(jnp.asarray([np.nan, np.inf],
                                              col.data.dtype)),
            col.valid_mask().at[rows].set(True), col.dtype, col.dictionary))
    elif case == "domain_at_cut":
        cut = domain
    elif case == "domain_over_cut":
        cut = domain - 1
    return table, groups, slots, live, domain, cut


def _assert_same_values(names, got, want, where):
    for name, g, w in zip(names, got, want):
        if isinstance(w, float):
            # the benchmark's oracle: 5e-4 relative, 1e-4 absolute
            np.testing.assert_allclose(g, w, rtol=5e-4, atol=1e-4,
                                       err_msg=f"{where} {name}")
        else:
            assert g == w and type(g) is type(w), (where, name)


def _same_bits(a, b) -> bool:
    leaves = zip(jax.tree.leaves(a), jax.tree.leaves(b))
    return all(np.asarray(x).tobytes() == np.asarray(y).tobytes()
               for x, y in leaves)


@pytest.mark.parametrize("mode", ["single", "partial", "final",
                                  "partial_reduce"])
@pytest.mark.parametrize("case", [
    "nulls_and_holes", "no_live_row", "unused_slot", "nan_and_inf",
    "domain_at_cut", "domain_over_cut",
])
def test_dense_reduction_equals_the_scatter(case, mode, monkeypatch):
    """A domain up to `_DENSE_MAX_DOMAIN` reduces every aggregate by
    dense masked passes (`_reduce_by_slot`); the result is the scatters' (the
    same call with the cut at 0), group for group: integers and counts
    exactly, floats at the oracle's tolerance, a NaN and an Inf confined to
    their own group, and two runs bit for bit the same. A domain one over
    the cut keeps the scatters."""
    from datafusion_distributed_tpu.ops import aggregate
    from datafusion_distributed_tpu.ops.table import concat_tables

    table, groups, slots, live, domain, cut = _dense_case(case)
    if mode in ("final", "partial_reduce"):
        halves = [table.row_mask() & (jnp.arange(table.capacity) % 2 == h)
                  for h in (0, 1)]
        states = [hash_aggregate(table, groups, _DENSE_AGGS, slots,
                                 "partial", live=half)[0] for half in halves]
        table = concat_tables(states, capacity=2 * states[0].capacity)
    seen = _reduction_spy(monkeypatch)

    def run(cut):
        if cut is not None:
            monkeypatch.setattr(aggregate, "_DENSE_MAX_DOMAIN", cut)
        direct: list = []
        out, overflow = jax.jit(
            lambda t: hash_aggregate(t, groups, _DENSE_AGGS, slots, mode,
                                     live=live(t), direct=direct))(table)
        assert not bool(overflow) and direct == [domain]
        return out

    got = run(cut)
    over = case == "domain_over_cut"
    assert seen and set(seen) == {None if over else domain}
    assert _same_bits(got, run(cut))
    del seen[:]
    want = run(0)
    assert seen and set(seen) == {None}

    assert got.names == want.names and got.capacity == want.capacity
    got_rows, want_rows = _group_rows(got, groups), _group_rows(want, groups)
    assert set(got_rows) == set(want_rows)
    assert len(got_rows) == (0 if case == "no_live_row" else 6)
    names = got.names[len(groups):]
    for key, want_vals in want_rows.items():
        _assert_same_values(names, got_rows[key], want_vals, key)
    sums = {key: dict(zip(names, vals)) for key, vals in got_rows.items()}
    if case == "nan_and_inf" and mode in ("single", "final"):
        bad = [key for key, row in sums.items()
               if row["sv"] is not None and not np.isfinite(row["sv"])]
        assert len(bad) == 1 and np.isnan(sums[bad[0]]["av"])
        assert all(np.isfinite(row["sv"]) and np.isfinite(row["av"])
                   for key, row in sums.items() if key != bad[0])
    if case == "nulls_and_holes" and mode in ("single", "final"):
        (empty,) = [row for row in sums.values() if row["cv"] == 0]
        assert (empty["sv"], empty["si"], empty["mn"], empty["mx"],
                empty["av"]) == (None,) * 5 and empty["n"] > 0


@pytest.mark.parametrize("mode", ["single", "partial", "final",
                                  "partial_reduce"])
@pytest.mark.parametrize("case", ["nulls_and_holes", "no_live_row",
                                  "nan_and_inf"])
def test_global_aggregate_reduces_densely(case, mode, monkeypatch):
    """No GROUP BY is a domain of one: plain masked reductions into slot 0
    of the capacity-8 result, no scatter in the program at all. The result
    is a grouped aggregate's over one constant key, reduced by scatters."""
    from datafusion_distributed_tpu.ops import aggregate
    from datafusion_distributed_tpu.ops.aggregate import global_aggregate
    from datafusion_distributed_tpu.ops.table import (
        Column,
        Dictionary,
        concat_tables,
    )
    from datafusion_distributed_tpu.schema import DataType

    table, _, _, live, _, _ = _dense_case(case)
    if mode in ("final", "partial_reduce"):
        halves = [table.row_mask() & (jnp.arange(table.capacity) % 2 == h)
                  for h in (0, 1)]
        states = [global_aggregate(table, _DENSE_AGGS, "partial",
                                   live=half & (True if live(table) is None
                                                else live(table)))
                  for half in halves]
        table = concat_tables(states, capacity=2 * states[0].capacity)
        live = lambda t: None  # noqa: E731
    seen = _reduction_spy(monkeypatch)
    run = jax.jit(lambda t: global_aggregate(t, _DENSE_AGGS, mode,
                                             live=live(t)))
    got = run(table)
    assert seen and set(seen) == {1}
    assert "scatter" not in run.lower(table).as_text()
    assert _same_bits(got, run(table))
    assert got.capacity == 8 and int(got.num_rows) == 1

    del seen[:]
    constant = Dictionary.from_strings(["c"])
    keyed = table.with_column("k", Column(
        jnp.zeros(table.capacity, jnp.int32), None, DataType.STRING,
        constant))
    monkeypatch.setattr(aggregate, "_DENSE_MAX_DOMAIN", 0)
    want, _ = jax.jit(lambda t: hash_aggregate(
        t, ["k"], _DENSE_AGGS, 8, mode, live=live(t)))(keyed)
    assert seen and set(seen) == {None}
    (got_row,) = _group_rows(got, []).values()
    want_rows = _group_rows(want, ["k"])
    names = got.names
    assert names == want.names[1:]
    row = dict(zip(names, got_row))
    if case == "no_live_row" and mode in ("single", "final"):
        assert row == {"sv": None, "si": None, "cv": 0, "n": 0, "mn": None,
                       "mx": None, "av": None, "vr": None}
    if case == "no_live_row" and mode in ("single", "partial"):
        assert want_rows == {}  # over a key no row makes no group
        return
    (want_row,) = want_rows.values()
    _assert_same_values(names, got_row, want_row, case)
    if case == "nan_and_inf" and mode in ("single", "final"):
        assert np.isnan(row["sv"]) and row["si"] is not None


def _string_columns():
    """(name, Column) of every way a dictionary-coded column is made."""
    from datafusion_distributed_tpu.ops.table import (
        Dictionary,
        unify_dictionaries,
    )
    from datafusion_distributed_tpu.plan import expressions as ex
    from datafusion_distributed_tpu.schema import DataType

    strings = ["pear", None, "apple", "fig", None, "apple", "kiwi"]
    ingested = {
        "plain": pa.array(strings),
        "large": pa.array(strings, type=pa.large_string()),
        "all_null": pa.array([None, None, None], type=pa.string()),
        "empty": pa.array([], type=pa.string()),
        # the wire's shape: sorted dictionary adopted with its codes
        "wire": pa.DictionaryArray.from_arrays(
            pa.array([2, None, 0, 1, None], type=pa.int32()),
            pa.array(["a", "b", "c"])),
        # unsorted, with a duplicate: decoded and encoded again
        "unsorted": pa.DictionaryArray.from_arrays(
            pa.array([0, 1, None, 2, 1], type=pa.int32()),
            pa.array(["b", "a", "b"])),
    }
    for name, array in ingested.items():
        yield name, arrow_to_table(pa.table({"s": array})).column("s")
    for name, vocabulary in (("provided", ["apple", "fig"]),  # others NULL
                             ("provided_unsorted", ["pear", "apple"]),
                             ("provided_empty", [])):
        t = arrow_to_table(
            pa.table({"s": pa.array(strings)}),
            dictionaries={"s": Dictionary.from_strings(vocabulary)})
        yield name, t.column("s")

    t = arrow_to_table(pa.table({
        "s": pa.array(strings),
        "u": pa.array(["zeta", "apple", None, None, "beta", "fig", None]),
    }))
    # codes re-encoded into the sorted union of two vocabularies
    union, luts = unify_dictionaries(
        [t.column("s").dictionary, None, t.column("u").dictionary,
         Dictionary.from_strings([])])
    for name, lut in (("s", luts[0]), ("u", luts[2])):
        src = t.column(name)
        yield f"unified_{name}", type(src)(
            ex._remap_codes(src.data, lut), src.validity, src.dtype, union)
    s, u = ex.Col("s"), ex.Col("u")
    derived = {
        "substring": ex.Substring(s, 1, 1),
        "upper": ex.StringCase(s, True),
        "regexp_replace": ex.RegexpReplace(s, "[aeiou]", "_"),
        "concat": ex.ConcatStrings((s, ex.Literal("-", DataType.STRING), u)),
        "coalesce": ex.Coalesce((s, u)),
        "coalesce_literal": ex.Coalesce((s, ex.Literal("none",
                                                       DataType.STRING))),
        "literal": ex.Literal("only", DataType.STRING),
    }
    for name, expr in derived.items():
        yield name, ex.expr_to_column(expr.evaluate(t))


@pytest.mark.parametrize("producer", [
    "plain", "large", "all_null", "empty", "wire", "unsorted", "provided",
    "provided_unsorted", "provided_empty", "unified_s", "unified_u",
    "substring", "upper", "regexp_replace", "concat", "coalesce",
    "coalesce_literal", "literal",
])
def test_valid_dictionary_codes_lie_inside_the_dictionary(producer):
    """What direct addressing rests on (`_dictionary_bases`): wherever a
    column carries a dictionary, a valid row's code is an index into it,
    NULL rows included in the column (their code is anything, their
    validity False)."""
    column = dict(_string_columns())[producer]
    assert column.dictionary is not None
    codes = np.asarray(column.data)
    valid = (np.ones(len(codes), dtype=bool) if column.validity is None
             else np.asarray(column.validity))
    assert codes.dtype == np.int32
    assert ((codes[valid] >= 0) & (codes[valid] < len(column.dictionary))).all()
    if producer in ("all_null", "provided_empty"):
        assert len(column.dictionary) == 0 and not valid.any()
