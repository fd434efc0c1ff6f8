"""A column with no NULLs is resident without a validity array (PR 37).

`io/parquet.py arrow_to_host_columns` decides from the data (an Arrow
column's ``null_count``): no NULL, no mask; one NULL, the mask as it always
was. Everywhere else ``Column.validity is None`` reads as "all true". So the
same rows must give the same answers in three forms: as registration makes
them (no mask), with an all-true mask forced on (what every column carried
until PR 37), and with a real NULL; on every tier: one program, the
coordinator over four workers, one SPMD program over four host devices.
"""

import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from datafusion_distributed_tpu.data.tpchgen import gen_tpch
from datafusion_distributed_tpu.io.parquet import (
    arrow_to_host_columns,
    arrow_to_table,
)
from datafusion_distributed_tpu.ops.aggregate import _dictionary_bases
from datafusion_distributed_tpu.ops.table import concat_tables, host_view
from datafusion_distributed_tpu.sql.context import SessionContext

from mask_forms import force_all_true_masks, with_all_true_masks
from tpch_oracle import ORACLES, compare_results, load_pandas

SF = 0.002
SEED = 7
QUERIES_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "queries", "tpch")
FORMS = ("no_mask", "all_true_mask", "one_null")
TIERS = ("direct", "coord4", "mesh4")

# a third of TPC-H's customers have no order: the join pads their rows, and
# the padded side's columns come out with a mask whatever they went in with
OUTER_JOIN = """
select c_mktsegment, count(o_orderkey) as orders, count(*) as n,
       sum(o_totalprice) as total
from customer left join orders on c_custkey = o_custkey
group by c_mktsegment order by c_mktsegment
"""


def _outer_join_oracle(T):
    j = T["customer"].merge(T["orders"], how="left", left_on="c_custkey",
                            right_on="o_custkey")
    g = j.groupby("c_mktsegment").agg(
        orders=("o_orderkey", "count"), n=("c_custkey", "size"),
        total=("o_totalprice", "sum")).reset_index()
    return g.sort_values("c_mktsegment").reset_index(drop=True)


QUERIES = {
    "q1": ORACLES["q1"], "q6": ORACLES["q6"], "q3": ORACLES["q3"],
    "outer_join": _outer_join_oracle,
}


def _query_text(query: str) -> str:
    if query == "outer_join":
        return OUTER_JOIN
    with open(os.path.join(QUERIES_DIR, f"{query}.sql")) as f:
        text = f.read()
    # q3 whole, not its first ten rows: the oracle gives every group
    return text.rsplit("limit 10", 1)[0] if query == "q3" else text


def _with_null(arrow: pa.Table, column: str, rows) -> pa.Table:
    values = arrow.column(column).to_pylist()
    for row in rows:
        values[row] = None
    field = arrow.schema.field(column)
    return arrow.set_column(arrow.schema.get_field_index(column), field,
                            pa.array(values, type=field.type))


# the columns the "one_null" form puts its NULLs in: an aggregate input of
# q1, q6 and q3 (row 0, and a row q6's filters keep), and the outer join's
NULLED = {"lineitem": "l_extendedprice", "orders": "o_totalprice"}


def _one_null_tables(tables: dict) -> dict:
    li = load_pandas({"lineitem": tables["lineitem"]})["lineitem"]
    kept_by_q6 = np.flatnonzero(
        (li.l_shipdate >= 8766) & (li.l_shipdate < 9131)
        & (li.l_discount >= 0.05) & (li.l_discount <= 0.07)
        & (li.l_quantity < 24))
    out = dict(tables)
    out["lineitem"] = _with_null(tables["lineitem"], "l_extendedprice",
                                 [0, int(kept_by_q6[0])])
    out["orders"] = _with_null(tables["orders"], "o_totalprice", [0])
    return out


class _Forms:
    """One session a form, made when first asked for, and every answer
    once (form, tier, query)."""

    def __init__(self):
        self.arrow = gen_tpch(sf=SF, seed=SEED)
        self._sessions: dict = {}
        self._answers: dict = {}
        self._cluster = None

    def session(self, form: str):
        if form not in self._sessions:
            arrow = (_one_null_tables(self.arrow) if form == "one_null"
                     else self.arrow)
            ctx = SessionContext()
            # forced heavy distribution at a tiny scale, as
            # tests/test_tpch_distributed.py does
            ctx.config.distributed_options["bytes_per_task"] = 1
            for name, table in arrow.items():
                ctx.register_arrow(name, table)
            if form == "all_true_mask":
                force_all_true_masks(ctx)
            self._sessions[form] = (ctx, load_pandas(arrow))
        return self._sessions[form]

    def answer(self, form: str, tier: str, query: str) -> pd.DataFrame:
        key = (form, tier, query)
        if key not in self._answers:
            ctx, _ = self.session(form)
            df = ctx.sql(_query_text(query))
            if tier == "direct":
                table = df.collect_table()
            elif tier == "mesh4":
                from datafusion_distributed_tpu.runtime.mesh_executor import (
                    make_mesh,
                )

                table = df.collect_distributed_table(mesh=make_mesh(4))
            else:
                from datafusion_distributed_tpu.runtime.coordinator import (
                    Coordinator,
                    InMemoryCluster,
                )

                if self._cluster is None:
                    self._cluster = InMemoryCluster(4)
                coord = Coordinator(resolver=self._cluster,
                                    channels=self._cluster)
                table = df.collect_coordinated_table(coordinator=coord,
                                                     num_tasks=4)
            self._answers[key] = df._strip_quals(table).to_pandas()
        return self._answers[key]


@pytest.fixture(scope="module")
def forms():
    return _Forms()


@pytest.mark.parametrize("form", FORMS)
def test_registration_masks_only_the_columns_that_hold_a_null(forms, form):
    """`register_arrow` of null-free data leaves ``validity`` None on every
    column (TPC-H's base tables hold no NULL: 61 columns, no mask); a
    column with one NULL has its mask, true but for that row, and its
    neighbours have none. The session counts them a table."""
    ctx, _ = forms.session(form)
    for name, table in ctx.catalog.tables.items():
        rows = int(table.num_rows)
        for column_name, column in zip(table.names, table.columns):
            if form == "all_true_mask":
                assert column.validity is not None
                continue
            nulled = form == "one_null" and NULLED.get(name) == column_name
            assert (column.validity is not None) == nulled, (
                name, column_name)
            if nulled:
                valid = np.asarray(column.validity)
                holes = forms.session(form)[1][name][column_name].isna()
                assert (valid[:rows] == ~holes.to_numpy()).all()
                assert 1 <= holes.sum() <= 2 and not valid[rows:].any()
        want = {"no_mask": 0, "all_true_mask": len(table.columns),
                "one_null": int(name in NULLED)}[form]
        assert ctx.table_masks(name) == table.validity_masks == want


@pytest.mark.parametrize("query", sorted(QUERIES))
@pytest.mark.parametrize("tier", TIERS)
@pytest.mark.parametrize("form", FORMS)
def test_answers_are_the_same_in_every_form_on_every_tier(forms, form, tier,
                                                          query):
    """q1, q6, q3 and an outer join against the pandas oracle over the
    form's own data (the NULLs leave their sums, averages and counts as
    SQL says); and, mask or no mask, the same frame: the forced all-true
    mask changes no cell of the answer the maskless tables give."""
    got = forms.answer(form, tier, query)
    compare_results(got, QUERIES[query](forms.session(form)[1]))
    if form == "all_true_mask":
        plain = forms.answer("no_mask", tier, query)
        pd.testing.assert_frame_equal(got, plain, check_exact=False,
                                      rtol=2e-6, atol=0)
    if form == "one_null" and query in ("q1", "q6", "outer_join"):
        # the NULLs sit in rows these queries read: the answer moved
        plain = forms.answer("no_mask", tier, query)
        assert not got.equals(plain)


def _q1_program(ctx):
    import datafusion_distributed_tpu.plan.physical as phys
    from datafusion_distributed_tpu.plan.physical import (
        DistributedTaskContext,
    )
    from datafusion_distributed_tpu.spans import NULL_TRACER

    plan = ctx.sql(_query_text("q1")).physical_plan()
    return phys._prepare_program(plan, DistributedTaskContext(), None, False,
                                 None, None, NULL_TRACER)


@pytest.mark.parametrize("form,bases,count_passes", [
    # the dictionaries' own domain, and ONE count for the eight aggregates
    ("no_mask", [3, 2], 1),
    # a NULL digit a key, and a count an aggregate input: `count(*)` and
    # one each for l_quantity, l_extendedprice, the two products, l_discount
    ("all_true_mask", [4, 3], 6),
])
def test_q1_lowers_to_one_count_pass_over_six_slots(forms, form, bases,
                                                    count_passes):
    """q1's lowering over maskless columns: `_dictionary_bases` gives the
    dictionaries' own sizes (no NULL digit: a domain of 6, not 12), and the
    compiled program holds one per-slot count reduction, because every
    aggregate's ``valid`` is the filter's mask itself; with a mask a column
    (all true or not, the compiler cannot know) it holds one an input."""
    import re

    ctx, _ = forms.session(form)
    lineitem = ctx.catalog.tables["lineitem"]
    keys = [lineitem.column("l_returnflag"), lineitem.column("l_linestatus")]
    assert _dictionary_bases(keys, 2048) == bases
    domain = bases[0] * bases[1]
    prog = _q1_program(ctx)
    text = prog.fn.lower(prog.inputs, prog.params).compile().as_text()
    reduced = re.findall(r"= (\w+\[\d+\])\{[^}]*\} reduce\(", text)
    assert reduced.count(f"s32[{domain}]") == count_passes, reduced
    assert reduced.count(f"f32[{domain}]") == 5  # the mean-shifted sums
    other = 12 if domain == 6 else 6
    assert not [r for r in reduced if r.endswith(f"[{other}]")]


def test_a_null_key_keeps_its_mask_and_its_digit():
    """A dictionary-coded key that holds a NULL has its mask, and NULL is
    a group of its own: one more digit in `_dictionary_bases`, and the
    rows pandas groups under NaN."""
    flags = ["A", "N", None, "R", "N", "A", None, "R", "N"]
    ctx = SessionContext()
    ctx.register_arrow("t", pa.table({
        "flag": pa.array(flags, pa.string()),
        "status": pa.array(["F", "O", "F", "O", "F", "O", "F", "O", "F"]),
        "v": np.arange(9, dtype=np.float64)}))
    t = ctx.catalog.tables["t"]
    assert t.column("flag").validity is not None
    assert t.column("status").validity is None and ctx.table_masks("t") == 1
    assert _dictionary_bases([t.column("flag"), t.column("status")],
                             64) == [4, 2]
    got = ctx.sql("select flag, status, sum(v) as s, count(*) as n from t "
                  "group by flag, status").to_pandas()
    want = (pd.DataFrame({"flag": flags, "status": list("FOFOFOFOF"),
                          "v": np.arange(9.0)})
            .groupby(["flag", "status"], dropna=False)
            .agg(s=("v", "sum"), n=("v", "size")).reset_index())
    key = ["flag", "status"]
    got = got.sort_values(key, na_position="last").reset_index(drop=True)
    want = want.sort_values(key, na_position="last").reset_index(drop=True)
    assert got["flag"].isna().tolist() == want["flag"].isna().tolist()
    assert got.fillna("-").values.tolist() == want.fillna("-").values.tolist()


def test_a_table_that_comes_to_hold_a_null_is_planned_anew():
    """A second `register_arrow` of a name, its column now holding a NULL:
    the column gets its mask, the catalog's generation moves, and the same
    text is planned and traced anew over it (no answer of the old plan)."""
    ctx = SessionContext()
    text = "select count(v) as c, count(*) as n, sum(v) as s from t"
    ctx.register_arrow("t", pa.table({"v": [1.0, 2.0, 4.0]}))
    assert ctx.table_masks("t") == 0
    generation = ctx.catalog.generation
    first = ctx.sql(text).to_pandas()
    assert first.values.tolist() == [[3, 3, 7.0]]
    ctx.register_arrow("t", pa.table({"v": [1.0, None, 4.0]}))
    assert ctx.table_masks("t") == 1
    assert ctx.catalog.generation > generation
    assert ctx.sql(text).to_pandas().values.tolist() == [[2, 3, 5.0]]
    ctx.register_arrow("t", pa.table({"v": [1.0, 2.0, 8.0]}))
    assert ctx.table_masks("t") == 0
    assert ctx.sql(text).to_pandas().values.tolist() == [[3, 3, 11.0]]


def _mixed_arrow(rows: int = 5) -> pa.Table:
    return pa.table({
        "i": pa.array(range(rows), pa.int64()),
        "f": pa.array([None if r == 1 else r / 4 for r in range(rows)],
                      pa.float64()),
        "s": pa.array(["pear", "apple", "fig", "apple", "kiwi"][:rows]),
        "u": pa.array([None, "x", "y", "x", None][:rows], pa.string()),
        "d": pa.array([8000 + r for r in range(rows)], pa.int32()).cast(
            pa.date32()),
        "b": pa.array([r % 2 == 0 for r in range(rows)]),
    })


def test_the_rule_is_taken_from_the_data_or_from_the_schema():
    """`arrow_to_host_columns`: by default what a column holds decides
    (its ``null_count``; no rows: the mask stays); a caller whose tasks
    must agree in tree structure asks for the schema's word instead, and
    a nullable field keeps an all-true mask. A column with a NULL has the
    same mask under either rule."""
    arrow = _mixed_arrow()
    _, validity, _, schema = arrow_to_host_columns(arrow)
    assert sorted(validity) == ["f", "u"]
    assert validity["f"].tolist() == [True, False, True, True, True]
    assert validity["u"].tolist() == [False, True, True, True, False]
    _, by_schema, _, _ = arrow_to_host_columns(arrow,
                                               mask_nullable_fields=True)
    assert sorted(by_schema) == sorted(arrow.column_names)
    for name in arrow.column_names:
        want = validity.get(name, np.ones(5, dtype=bool))
        assert by_schema[name].tolist() == want.tolist()
    # a field that says it holds no NULL needs no mask under either rule
    strict = pa.Table.from_arrays(
        [arrow.column("i"), arrow.column("s")],
        schema=pa.schema([pa.field("i", pa.int64(), nullable=False),
                          pa.field("s", pa.string(), nullable=False)]))
    assert arrow_to_host_columns(strict, mask_nullable_fields=True)[1] == {}
    # no rows: the mask stays, so that a padding slot's code 0 is not a
    # valid index into what may be an empty dictionary
    _, empty, dicts, _ = arrow_to_host_columns(arrow.slice(0, 0))
    assert sorted(empty) == sorted(arrow.column_names)
    assert len(dicts["s"]) == 0


def test_a_value_the_provided_dictionary_lacks_is_a_null():
    """Encoded against a provided dictionary, a column without NULLs needs
    a mask exactly when some value is missing from the dictionary."""
    from datafusion_distributed_tpu.ops.table import Dictionary

    arrow = pa.table({"s": ["pear", "apple", "fig"]})
    whole = Dictionary.from_strings(["apple", "fig", "pear"])
    part = Dictionary.from_strings(["apple", "pear"])
    data, validity, _, _ = arrow_to_host_columns(arrow, {"s": whole})
    assert validity == {} and data["s"].tolist() == [2, 0, 1]
    data, validity, _, _ = arrow_to_host_columns(arrow, {"s": part})
    assert validity["s"].tolist() == [True, True, False]
    assert data["s"].tolist() == [1, 0, 0]


@pytest.mark.parametrize("adaptive", [False, True],
                         ids=["one_blob", "a_blob_a_column"])
def test_the_wire_keeps_which_columns_carry_a_mask(adaptive):
    """`encode_table` -> `decode_table` (and the per-column frames) of a
    table with maskless columns, masked columns and an all-true mask: each
    comes back in the form it left in, the rows equal, whatever the rows of
    the slice hold (an empty slice of a maskless column stays maskless:
    every slice of one table decodes to one tree structure)."""
    from datafusion_distributed_tpu.runtime import codec

    table = arrow_to_table(_mixed_arrow())
    assert [c.validity is not None for c in table.columns] == [
        False, True, False, True, False, False]
    forced = with_all_true_masks(table.select(["i", "s"])).rename(
        {"i": "i_masked", "s": "s_masked"})
    for name, column in zip(forced.names, forced.columns):
        table = table.with_column(name, column)
    for piece in (table, table.head(0)):
        if adaptive:
            blobs, _ = codec.encode_table_adaptive(piece, ("none",))
            back = codec.decode_table_adaptive(blobs, len(piece.names),
                                               capacity=8)
        else:
            back = codec.decode_table(codec.encode_table(piece), capacity=8)
        assert back.names == piece.names
        assert [c.validity is not None for c in back.columns] == [
            c.validity is not None for c in piece.columns]
        pd.testing.assert_frame_equal(back.to_pandas(), piece.to_pandas())


@pytest.mark.parametrize("backing", ["device", "host"])
def test_concat_of_a_masked_and_a_maskless_side(backing):
    """`concat_tables` over a side with a mask and a side without (a
    `UNION ALL`'s arms, a shuffle's producers): the result carries a mask,
    true over the maskless side's rows; two maskless sides stay maskless."""
    left = arrow_to_table(pa.table({"k": [1, 2, 3], "s": ["a", "b", "a"]}))
    right = arrow_to_table(pa.table({
        "k": pa.array([4, None], pa.int64()), "s": ["c", None]}))
    assert left.validity_masks == 0 and right.validity_masks == 2
    if backing == "host":
        left, right = host_view(left), host_view(right)
    for sides in ((left, right), (right, left)):
        both = concat_tables(sides, capacity=8)
        assert both.validity_masks == 2
        want = pd.concat([t.to_pandas() for t in sides], ignore_index=True)
        pd.testing.assert_frame_equal(both.to_pandas(), want)
        assert not np.asarray(both.column("k").validity)[5:].any()
    assert concat_tables((left, left), capacity=8).validity_masks == 0


def test_union_all_of_a_masked_and_a_maskless_table():
    ctx = SessionContext()
    ctx.register_arrow("a", pa.table({"k": [1, 2, 3], "v": [1.0, 2.0, 3.0]}))
    ctx.register_arrow("b", pa.table({
        "k": [4, 5], "v": pa.array([None, 5.0], pa.float64())}))
    assert (ctx.table_masks("a"), ctx.table_masks("b")) == (0, 1)
    for text in ("select k, v from a union all select k, v from b",
                 "select k, v from b union all select k, v from a"):
        got = ctx.sql(text).to_pandas().sort_values("k").reset_index(
            drop=True)
        assert got["k"].tolist() == [1, 2, 3, 4, 5]
        assert got["v"].isna().tolist() == [False] * 3 + [True, False]
        assert got["v"].dropna().tolist() == [1.0, 2.0, 3.0, 5.0]
    got = ctx.sql("select count(v) as c, count(*) as n from "
                  "(select v from a union all select v from b) as u"
                  ).to_pandas()
    assert got.values.tolist() == [[4, 5]]


def test_shuffle_regroup_of_a_masked_and_a_maskless_producer():
    """The coordinator's host regroup over producers that disagree (one
    side with a mask, one without), on the view path and the copying one:
    every row lands where its key hashes to, the same place either way."""
    from datafusion_distributed_tpu.runtime.coordinator import (
        _shuffle_regroup,
    )

    plain = arrow_to_table(pa.table({"k": np.arange(40),
                                     "v": np.arange(40) / 2}))
    masked = with_all_true_masks(arrow_to_table(pa.table({
        "k": np.arange(40, 80),
        "v": pa.array([None if r % 7 == 0 else r / 2 for r in range(40)],
                      pa.float64())})))
    assert plain.validity_masks == 0 and masked.validity_masks == 2
    frames = {}
    for zero_copy in (True, False):
        slices = _shuffle_regroup([plain, masked], ["k"], 4, 64,
                                  zero_copy=zero_copy)
        frames[zero_copy] = [s.to_pandas() for s in slices]
        assert all(s.validity_masks == 2 for s in slices)
    for a, b in zip(frames[True], frames[False]):
        pd.testing.assert_frame_equal(a, b)
    everything = pd.concat(frames[True]).sort_values("k").reset_index(
        drop=True)
    assert everything["k"].tolist() == list(range(80))
    assert int(everything["v"].isna().sum()) == 6
    # a key's destination does not depend on whether its column is masked
    again = _shuffle_regroup([with_all_true_masks(plain)], ["k"], 4, 64)
    for got, want in zip(again, _shuffle_regroup([plain], ["k"], 4, 64)):
        assert got.to_pandas()["k"].tolist() == want.to_pandas()["k"].tolist()
