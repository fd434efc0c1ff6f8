"""Physical plan IR: single-task execution, operator composition."""

import os

import numpy as np

from datafusion_distributed_tpu import precision as _precision

# f32 compute in tpu precision mode: summation-order differences are ~eps
FLOAT_RTOL = _precision.test_rtol()

import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from datafusion_distributed_tpu.io.parquet import arrow_to_table
from datafusion_distributed_tpu.ops.aggregate import AggSpec
from datafusion_distributed_tpu.ops.sort import SortKey
from datafusion_distributed_tpu.plan.expressions import (
    BinaryOp,
    Col,
    Literal,
)
from datafusion_distributed_tpu.plan import physical as phys
from datafusion_distributed_tpu.plan.physical import (
    DistributedTaskContext,
    ExecutionPlan,
    FilterExec,
    HashAggregateExec,
    LimitExec,
    MemoryScanExec,
    ParquetScanExec,
    PartialPassthroughExec,
    ProjectionExec,
    SortExec,
    execute_plan,
)
from datafusion_distributed_tpu.schema import DataType


def sample_table(n=200, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table(
        {
            "k": rng.integers(0, 8, n),
            "v": rng.normal(size=n),
            "w": rng.integers(-50, 50, n),
        }
    )


def test_scan_filter_project_aggregate_sort_limit():
    arrow = sample_table()
    t = arrow_to_table(arrow)
    scan = MemoryScanExec([t], t.schema())
    filt = FilterExec(
        BinaryOp(">", Col("w"), Literal(0, DataType.INT64)), scan
    )
    proj = ProjectionExec(
        [(Col("k"), "k"),
         (BinaryOp("*", Col("v"), Literal(2.0, DataType.FLOAT64)), "v2")],
        filt,
    )
    agg = HashAggregateExec(
        "single", ["k"], [AggSpec("sum", "v2", "s"), AggSpec("count_star", None, "n")],
        proj, num_slots=32,
    )
    sort = SortExec([SortKey("s", ascending=False)], agg)
    lim = LimitExec(sort, fetch=3)
    out = execute_plan(lim).to_pandas()

    df = arrow.to_pandas()
    df = df[df.w > 0]
    df["v2"] = df.v * 2
    exp = (
        df.groupby("k").agg(s=("v2", "sum"), n=("v2", "size")).reset_index()
        .sort_values("s", ascending=False).head(3).reset_index(drop=True)
    )
    np.testing.assert_array_equal(out["k"], exp["k"])
    np.testing.assert_allclose(out["s"], exp["s"], rtol=FLOAT_RTOL)
    np.testing.assert_array_equal(out["n"], exp["n"])


def test_global_aggregate_no_groups():
    arrow = sample_table()
    t = arrow_to_table(arrow)
    scan = MemoryScanExec([t], t.schema())
    agg = HashAggregateExec(
        "single", [],
        [AggSpec("sum", "w", "sw"), AggSpec("count_star", None, "n"),
         AggSpec("min", "w", "mn"), AggSpec("avg", "v", "av")],
        scan,
    )
    out = execute_plan(agg).to_pandas()
    df = arrow.to_pandas()
    assert len(out) == 1
    assert int(out["sw"][0]) == int(df.w.sum())
    assert int(out["n"][0]) == len(df)
    assert int(out["mn"][0]) == int(df.w.min())
    np.testing.assert_allclose(out["av"][0], df.v.mean(), rtol=FLOAT_RTOL)


def test_sort_multi_key_with_nulls():
    arrow = pa.table(
        {
            "a": pa.array([2, 1, 2, None, 1], type=pa.int64()),
            "b": pa.array([1.0, 5.0, 0.5, 9.9, None]),
        }
    )
    t = arrow_to_table(arrow)
    scan = MemoryScanExec([t], t.schema())
    sort = SortExec([SortKey("a", True, nulls_first=False),
                     SortKey("b", False, nulls_first=False)], scan)
    out = execute_plan(sort).to_pandas()
    # expect a asc (nulls last), b desc (nulls last) within groups
    exp = (
        arrow.to_pandas()
        .sort_values(["a", "b"], ascending=[True, False],
                     na_position="last", kind="stable")
        # pandas sorts nulls-last per column but sorts 'a' nulls after;
        .reset_index(drop=True)
    )
    # row order: a=1:(b=5.0, b=null), a=2:(b=1.0, 0.5), a=null
    assert list(out["a"].fillna(-1)) == [1, 1, 2, 2, -1]
    assert out["b"][0] == 5.0 and pd.isna(out["b"][1])
    assert out["b"][2] == 1.0 and out["b"][3] == 0.5


def test_limit_offset():
    arrow = pa.table({"x": list(range(10))})
    t = arrow_to_table(arrow)
    plan = LimitExec(MemoryScanExec([t], t.schema()), fetch=3, skip=4)
    out = execute_plan(plan).to_pandas()
    assert list(out["x"]) == [4, 5, 6]


def test_parquet_scan_multi_task(tmp_path):
    files = []
    for i in range(3):
        p = tmp_path / f"f{i}.parquet"
        pq.write_table(pa.table({"x": [i * 10 + j for j in range(5)]}), p)
        files.append(str(p))
    from datafusion_distributed_tpu.io.parquet import schema_from_arrow

    schema = schema_from_arrow(pq.read_schema(files[0]))
    scan = ParquetScanExec(
        file_groups=[[files[0], files[1]], [files[2]]],
        schema=schema,
        capacity=16,
    )
    t0 = execute_plan(scan, DistributedTaskContext(0, 2)).to_pandas()
    t1 = execute_plan(scan, DistributedTaskContext(1, 2)).to_pandas()
    assert list(t0["x"]) == [0, 1, 2, 3, 4, 10, 11, 12, 13, 14]
    assert list(t1["x"]) == [20, 21, 22, 23, 24]


def test_overflow_raises_at_executor():
    rng = np.random.default_rng(5)
    arrow = pa.table({"k": rng.integers(0, 1000, 2000), "v": np.ones(2000)})
    t = arrow_to_table(arrow)
    agg = HashAggregateExec(
        "single", ["k"], [AggSpec("count_star", None, "n")],
        MemoryScanExec([t], t.schema()), num_slots=64,
    )
    with pytest.raises(RuntimeError, match="overflow"):
        execute_plan(agg)


def test_display_tree():
    arrow = sample_table(10)
    t = arrow_to_table(arrow)
    plan = LimitExec(
        FilterExec(BinaryOp(">", Col("w"), Literal(0, DataType.INT64)),
                   MemoryScanExec([t], t.schema())),
        fetch=5,
    )
    s = plan.display_tree()
    assert "Limit" in s and "Filter" in s and "MemoryScan" in s


def test_final_mode_schema_after_partial():
    arrow = sample_table(50)
    t = arrow_to_table(arrow)
    scan = MemoryScanExec([t], t.schema())
    partial = HashAggregateExec(
        "partial", ["k"],
        [AggSpec("sum", "v", "sv"), AggSpec("avg", "v", "av"),
         AggSpec("min", "w", "mn")],
        scan, num_slots=32,
    )
    fin = HashAggregateExec(
        "final", ["k"],
        [AggSpec("sum", "v", "sv"), AggSpec("avg", "v", "av"),
         AggSpec("min", "w", "mn")],
        partial, num_slots=32,
    )
    s = fin.schema()  # must not KeyError on raw input names
    assert s.names == ["k", "sv", "av", "mn"]
    out = execute_plan(fin).to_pandas().sort_values("k").reset_index(drop=True)
    df = arrow.to_pandas().groupby("k").agg(
        sv=("v", "sum"), av=("v", "mean"), mn=("w", "min")).reset_index()
    np.testing.assert_allclose(out["sv"], df["sv"], rtol=FLOAT_RTOL)
    np.testing.assert_allclose(out["av"], df["av"], rtol=FLOAT_RTOL)
    np.testing.assert_array_equal(out["mn"], df["mn"])


# ---------------------------------------------------------------------------
# the masked path: an aggregate pulls (table, live) and filters and
# projections underneath hand their mask up without compacting
# ---------------------------------------------------------------------------

class _PackedExec(ExecutionPlan):
    """Pass-through with the base class's `execute_masked`: whatever pulls
    through it gets its child's PACKED rows, as every consumer did before
    the masked path."""

    def __init__(self, child):
        super().__init__()
        self.child = child

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return _PackedExec(children[0])

    def schema(self):
        return self.child.schema()

    def output_capacity(self):
        return self.child.output_capacity()

    def _execute(self, ctx):
        return self.child.execute(ctx)


def _masked_case_scan(n=200, seed=5, dictionary_key=False):
    """k: group key (``dictionary_key``: and d, the same key as a
    dictionary-coded string); w: nullable predicate column; v: nullable
    aggregate input. Floats are quarters, so every sum is exact and no
    order of adding them can show: equal answers are then equal bit for bit.
    200 rows in a capacity of 256: padding rows past ``num_rows``."""
    rng = np.random.default_rng(seed)
    w = rng.integers(-50, 50, n).astype(object)
    w[rng.random(n) < 0.2] = None
    v = (rng.integers(-400, 400, n) / 4.0).astype(object)
    v[rng.random(n) < 0.2] = None
    columns = {
        "k": rng.integers(0, 8, n),
        "w": pa.array(list(w), type=pa.int64()),
        "v": pa.array(list(v), type=pa.float64()),
    }
    if dictionary_key:
        columns["d"] = np.array(list("abcdefgh"), dtype=object)[columns["k"]]
    t = arrow_to_table(pa.table(columns))
    assert t.capacity > n
    assert t.column("w").validity is not None
    assert t.column("v").validity is not None
    return MemoryScanExec([t], t.schema())


_MASKED_PREDICATES = {
    # nullable column: a null predicate keeps no row
    "some": BinaryOp(">", Col("w"), Literal(0, DataType.INT64)),
    "none": BinaryOp("<", Col("k"), Literal(0, DataType.INT64)),
    "all": BinaryOp(">=", Col("k"), Literal(0, DataType.INT64)),
}
_MASKED_AGGS = [
    AggSpec("sum", "v2", "s"), AggSpec("avg", "v", "a"),
    AggSpec("min", "w", "mn"), AggSpec("max", "v", "mx"),
    AggSpec("count", "v", "c"), AggSpec("count_star", None, "n"),
]


def _masked_case_input(shape, predicate):
    """The aggregate's child, and the group names of the aggregate."""
    by_dictionary = shape == "agg_dictionary_key"
    scan = _masked_case_scan(dictionary_key=by_dictionary)
    proj = [(Col("k"), "k"), (Col("w"), "w"), (Col("v"), "v"),
            (BinaryOp("*", Col("v"), Literal(2.0, DataType.FLOAT64)), "v2")]
    if by_dictionary:
        proj.append((Col("d"), "d"))
    if shape == "agg_filter_filter":
        # the projection sits under the filters, which stack
        inner = FilterExec(
            BinaryOp(">", Col("w"), Literal(-40, DataType.INT64)),
            ProjectionExec(proj, scan),
        )
        return FilterExec(predicate, inner), ["k"]
    child = ProjectionExec(proj, FilterExec(predicate, scan))
    return child, {"global": [], "agg_dictionary_key": ["d"]}.get(shape,
                                                                 ["k"])


def _aggregate_over(child, groups, mode):
    if mode == "single":
        return HashAggregateExec("single", groups, _MASKED_AGGS, child, 32)
    partial = HashAggregateExec("partial", groups, _MASKED_AGGS, child, 32)
    return HashAggregateExec("final", groups, _MASKED_AGGS, partial, 32)


def _assert_bit_equal(got, want, dense=False):
    """The two frames bit for bit. ``dense``: but for the float columns,
    which agree to a few units in the last place. A dense reduction
    (`ops/aggregate.py _reduce_by_slot`, since PR 32) adds in an order the
    rows' positions fix, and the centred residuals of
    `_mean_shifted_seg_sum` are not quarters, so the same rows packed and
    in place round differently; a scatter adds them in row order either
    way."""
    exact = [name for name in want.columns
             if not (dense and want[name].dtype.kind == "f")]
    pd.testing.assert_frame_equal(got[exact], want[exact], check_exact=True)
    for name in exact:
        assert (got[name].to_numpy().tobytes()
                == want[name].to_numpy().tobytes()), name
    pd.testing.assert_frame_equal(got, want, check_exact=False, rtol=2e-6,
                                  atol=0)


def _lowered(plan) -> str:
    """The plan's lowered program, its ops' scopes in the locations."""
    from datafusion_distributed_tpu.spans import NULL_TRACER

    prog = phys._prepare_program(
        plan, DistributedTaskContext(), None, False, None, None, NULL_TRACER
    )
    text = prog.fn.lower(prog.inputs, prog.params).as_text(debug_info=True)
    assert "HashAggregateExec" in text or "PartialPassthroughExec" in text
    return text


def _compacting_filters(plan) -> dict:
    """`FilterExec.<position>` -> the number of op locations of the plan's
    lowered program under that filter's ``table.compact`` scope (the
    ``nonzero`` and the gathers): the filters that pack their rows."""
    import collections
    import re

    text = _lowered(plan)
    return dict(collections.Counter(re.findall(
        r'loc\("[^"]*/(FilterExec\.\d+)/table\.compact[/"]', text
    )))


@pytest.mark.parametrize("predicate", ["some", "none", "all"])
@pytest.mark.parametrize("mode", ["single", "partial_final"])
@pytest.mark.parametrize(
    "shape", ["agg_proj_filter", "agg_filter_filter", "global",
              "agg_dictionary_key"]
)
def test_masked_aggregate_equals_packed(shape, mode, predicate):
    """An aggregate over filters and projections reduces under their mask;
    the same aggregate over the same child's packed rows gives the same
    frame bit for bit where the reductions scatter (`k` is an integer with
    no dictionary), and but for a float sum's last places where they are
    dense (`global`, and `d`'s domain of 9: `_assert_bit_equal`)."""
    child, groups = _masked_case_input(shape, _MASKED_PREDICATES[predicate])
    masked = _aggregate_over(child, groups, mode)
    packed = _aggregate_over(_PackedExec(child), groups, mode)
    filters = len(masked.collect(lambda n: isinstance(n, FilterExec)))
    assert filters == (2 if shape == "agg_filter_filter" else 1)
    assert _compacting_filters(masked) == {}
    assert len(_compacting_filters(packed)) == filters
    got = execute_plan(masked).to_pandas()
    want = execute_plan(packed).to_pandas()
    dense = groups != ["k"]
    reducing = [name for name in _scatter_scopes(_lowered(masked))
                if "/agg.reduce." in name or "/agg.global" in name]
    assert bool(reducing) != dense
    _assert_bit_equal(got, want, dense)
    if groups:
        assert len(want) == {"some": 8, "none": 0, "all": 8}[predicate]
    else:
        assert len(want) == 1
        kept = {"some": None, "none": 0, "all": 200}[predicate]
        if kept is not None:
            assert int(want["n"][0]) == kept


def _filter_under(consumer):
    scan = _masked_case_scan()
    filt = FilterExec(_MASKED_PREDICATES["some"], scan)
    aggs = [AggSpec("sum", "v", "s"), AggSpec("count_star", None, "n")]
    return {
        "aggregate": lambda: HashAggregateExec("single", ["k"], aggs, filt,
                                               32),
        "partial_aggregate": lambda: HashAggregateExec("partial", ["k"],
                                                       aggs, filt, 32),
        "partial_passthrough": lambda: PartialPassthroughExec(["k"], aggs,
                                                              filt),
        "sort": lambda: HashAggregateExec(
            "single", ["k"], aggs, SortExec([SortKey("w")], filt), 32),
        "limit": lambda: HashAggregateExec(
            "single", ["k"], aggs, LimitExec(filt, fetch=50), 32),
    }[consumer]()


@pytest.mark.parametrize("consumer,compact_ops", [
    ("aggregate", {}),
    ("partial_aggregate", {}),
    # the counts of the program at the parent commit, from before the
    # masked path (there the two aggregates read 24 as well)
    ("partial_passthrough", {"FilterExec.1": 24}),
    ("sort", {"FilterExec.2": 24}),
    ("limit", {"FilterExec.2": 24}),
])
def test_filter_compacts_for_every_consumer_but_an_aggregate(consumer,
                                                             compact_ops):
    """Who pulls decides: an aggregate takes the mask; the bail-out form of
    a partial aggregate emits a state a row and a sort or limit needs a
    prefix, so under them the filter packs exactly as it did."""
    assert _compacting_filters(_filter_under(consumer)) == compact_ops


def _tpch_session():
    from datafusion_distributed_tpu.data.tpchgen import gen_tpch
    from datafusion_distributed_tpu.sql.context import SessionContext

    ctx = SessionContext()
    for name, arrow in gen_tpch(sf=0.002, seed=7).items():
        ctx.register_arrow(name, arrow)
    return ctx


@pytest.fixture(scope="module")
def tpch_ctx():
    return _tpch_session()


@pytest.fixture(scope="module")
def tpch_ctx_masked():
    """`tpch_ctx`'s tables with an all-true validity array on every
    column: the form every registered column had until PR 37."""
    from mask_forms import force_all_true_masks

    ctx = _tpch_session()
    force_all_true_masks(ctx)
    return ctx


def _tpch_plan(ctx, query):
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "queries", "tpch")
    with open(os.path.join(root, f"{query}.sql")) as f:
        return ctx.sql(f.read()).physical_plan()


@pytest.mark.parametrize("query,filters,compact_ops", [
    # Sort/Projection/Aggregate/Projection/Filter/Projection/scan
    ("q1", 1, {}),
    # Projection/Aggregate/Projection/Filter x4/Projection/scan
    ("q6", 4, {}),
    # every filter feeds a join: the program at the parent commit, less
    # the two gathers of the mask each scan's columns no longer carry
    # (PR 37: 24 with them)
    ("q3", 3, {"FilterExec.7": 22, "FilterExec.10": 22,
               "FilterExec.13": 22}),
])
def test_tpch_programs_compact_only_under_joins(tpch_ctx, query, filters,
                                                compact_ops):
    """No op under ``table.compact`` below q1's and q6's aggregates; q3's
    three filters pack their rows for the joins exactly as before."""
    plan = _tpch_plan(tpch_ctx, query)
    assert len(plan.collect(lambda n: isinstance(n, FilterExec))) == filters
    assert _compacting_filters(plan) == compact_ops


def _scatter_scopes(text: str) -> list:
    """The scope path (`jit(run)/<node>/<scope>/...`) of every
    `stablehlo.scatter` of a program lowered with debug info: the name
    that the op's own location, after its region, is an alias of."""
    import re

    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    own = re.findall(
        r'"stablehlo\.scatter".*?^\s*\}\) : [^\n]* loc\((#loc\d+)\)$', text,
        re.M | re.S)
    assert len(own) == text.count('"stablehlo.scatter"')
    return [names[loc] for loc in own]


# sha256 of q3's and q18's lowered text (no debug info) over `tpch_ctx`'s
# tables: their aggregates keep the claim loop and the scatters. Their
# `Sort fetch=10` / `fetch=100` gathers only the first 16 / 104 entries of
# the whole input's permutation (`ops/sort.py fetch_capacity`), so the
# sort's and the LIMIT's gathers and the program's outputs are 16 / 104
# rows wide; the rest of the text is what it was before that cut.
# "masked": every column with an all-true validity array, as registration
# made them while a column without NULLs still carried one. "registered":
# the columns as registration makes them now (no NULL, no mask).
_PARENT_LOWERED_SHA256 = {
    ("q3", "masked"):
        "d6c6dce8a7fe133c70312ab20ddb98f7349dd11bdd00f4c1abfe2dd70bfdc853",
    ("q18", "masked"):
        "2b3d0803606743d87617bc80eaf8ca9d644b2a2ad715fe213d4f23ce64cee183",
    ("q3", "registered"):
        "f80f0270b72413140bd55ab15c5f2ab1608dacc2fd3f1a125423d94fb62732d8",
    ("q18", "registered"):
        "8489195947abefc49a86e655b857eaf6e9710043b9fd49c03480643c492f428f",
}


def _plain_lowered_sha256(plan) -> str:
    """sha256 of the plan's lowered text, no debug info."""
    import hashlib

    from datafusion_distributed_tpu.spans import NULL_TRACER

    prog = phys._prepare_program(
        plan, DistributedTaskContext(), None, False, None, None, NULL_TRACER)
    plain = prog.fn.lower(prog.inputs, prog.params).as_text()
    return hashlib.sha256(plain.encode()).hexdigest()


@pytest.mark.parametrize("query", ["q3", "q18"])
def test_masked_inputs_lower_to_the_parents_text(tpch_ctx_masked, query):
    """Columns that DO carry a mask (all true here, as every column did
    until PR 37) reach the operators they reached: q3's and q18's lowered
    text is the pinned one, byte for byte."""
    assert _plain_lowered_sha256(_tpch_plan(tpch_ctx_masked, query)) == (
        _PARENT_LOWERED_SHA256[query, "masked"])


@pytest.mark.parametrize("query,grouping", [
    # l_returnflag x l_linestatus: dictionary codes, 3 x 2 <= 2048
    ("q1", "agg.direct"),
    # integer and date keys: the claim loop, as at the parent commit
    ("q3", "agg.claim"),
    ("q18", "agg.claim"),
    # no GROUP BY: neither
    ("q6", None),
])
def test_tpch_programs_claim_only_without_dictionary_keys(tpch_ctx, query,
                                                          grouping):
    """q1's group ids are arithmetic on its keys' dictionary codes: no op
    under ``agg.claim`` and no `while` in its lowered program. Keys
    without a dictionary still build the group table by claim rounds.
    The reductions follow: over q1's domain of 6 and q6's of one they are
    dense passes, no `stablehlo.scatter` under ``agg.reduce.*`` or
    ``agg.global``; q3 and q18 scatter into their 2Mi-slot tables, their
    whole lowered text the pinned one."""
    plan = _tpch_plan(tpch_ctx, query)
    text = _lowered(plan)
    for scope in ("agg.direct", "agg.claim"):
        assert (f"/{scope}" in text) == (scope == grouping), scope
    if grouping != "agg.claim":
        assert "stablehlo.while" not in text
    reducing = [name for name in _scatter_scopes(text)
                if "/agg.reduce." in name or "/agg.global" in name]
    if grouping == "agg.claim":
        assert reducing
        assert _plain_lowered_sha256(plan) == (
            _PARENT_LOWERED_SHA256[query, "registered"])
    else:
        assert reducing == []


def test_masked_filter_reports_the_kept_rows():
    """`output_rows` of a filter and a projection on the masked path is
    the mask's popcount: what the packed nodes report."""
    from datafusion_distributed_tpu.runtime.metrics import (
        MetricsStore,
        explain_analyze,
    )

    child, groups = _masked_case_input("agg_filter_filter",
                                       _MASKED_PREDICATES["some"])
    rows = {}
    for name, over in (("masked", child), ("packed", _PackedExec(child))):
        plan = _aggregate_over(over, groups, "single")
        store = MetricsStore()
        execute_plan(plan, metrics_store=store, task_label="task0")
        by_node = store.aggregated()
        rows[name] = [
            (type(n).__name__, by_node[n.node_id]["output_rows"])
            for n in plan.collect(lambda n: not isinstance(n, _PackedExec))
        ]
        text = explain_analyze(plan, store)
        for _, count in rows[name]:
            assert f"output_rows={count}" in text
    assert rows["masked"] == rows["packed"]
    df = child.child.child.child.tasks[0].to_pandas()
    outer = int(((df.w > -40) & (df.w > 0)).sum())
    inner = int((df.w > -40).sum())
    assert [c for _, c in rows["masked"]] == [8, outer, inner, 200, 200]


# the fingerprints of `_aggregate_over(*_masked_case_input("agg_proj_filter",
# some), "single")`: as they are, and as the program cache keys them
# (literals hoisted). Taken at commit f86743d (the tree before PR 37) with
# the all-true mask of the scan's key ``k`` taken off by hand: a
# fingerprint covers the scan's schema, and since PR 37 a column without
# NULLs has no mask and is not nullable there (d97adc7c137ba9b5a77aede26c5f7c7d
# and c181cd94e922ee1b19c9e7c723166ae1 with the mask, at ff446a1).
_MASKED_CASE_FINGERPRINT = "7b83aba7f5c3a0328cefbc808d62a32e"
_MASKED_CASE_HOISTED_FINGERPRINT = "a418541c6e040de6bdf625fd8aea811b"


def test_masked_path_adds_nothing_to_fingerprint_or_codec():
    """The plan tree is the one it was: the fingerprint of
    Aggregate/Projection/Filter/scan is the stored one (taken before the
    masked path existed), and the codec carries the same keys a node and
    round-trips to the same fingerprint."""
    from datafusion_distributed_tpu.plan.fingerprint import (
        plan_fingerprint,
        prepare_plan,
    )
    from datafusion_distributed_tpu.runtime.codec import (
        TableStore,
        decode_plan,
        encode_plan,
    )

    child, groups = _masked_case_input("agg_proj_filter",
                                       _MASKED_PREDICATES["some"])
    plan = _aggregate_over(child, groups, "single")
    assert plan_fingerprint(plan) == _MASKED_CASE_FINGERPRINT
    # the program cache's key: the literal-hoisted plan's
    fp = prepare_plan(plan).fingerprint
    assert fp == _MASKED_CASE_HOISTED_FINGERPRINT
    store = TableStore()
    wire = encode_plan(plan, store)
    assert set(wire) == {"t", "mode", "groups", "aggs", "slots", "c", "_fp"}
    assert set(wire["c"]) == {"t", "exprs", "c"}
    assert set(wire["c"]["c"]) == {"t", "pred", "c"}
    assert wire["_fp"] == fp
    decoded = decode_plan(wire, store)
    assert prepare_plan(decoded).fingerprint == fp
    assert plan_fingerprint(decoded) == _MASKED_CASE_FINGERPRINT
    assert "masked" not in plan.display_tree().lower()
