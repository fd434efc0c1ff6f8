"""`data/tpchgen.py`: the data every TPC-H test and every benchmark cell runs
on. The text columns are built by Arrow's string kernels since PR 34 (a
Python loop over rows cost 29 s a SF unit); everything here holds them, and
every other column, to what the loop made: the accepted cells run on the
data they ran on."""

import hashlib
import sys
import threading
import time

import numpy as np
import pytest

from datafusion_distributed_tpu.data import tpchgen
from datafusion_distributed_tpu.data.tpchgen import (
    gen_tpch,
    tpch_cardinalities,
)

# sha256 (16 hex digits) of each column's arrow type and values, made by
# `column_digest` on the PARENT of PR 34 (commit 368d2a8) at SF0.01: the
# numeric columns, and the text columns too, since the draws and the words
# are the loop's
PARENT_DIGESTS = {
    7: {
        "r_regionkey": "60c939fb42b3b6a8", "r_name": "fdba52e188947256",
        "r_comment": "7728135a8e100d5c", "n_nationkey": "8d86474b586d73ab",
        "n_name": "72f954b592681f23", "n_regionkey": "0dc7a35ef2e1454c",
        "n_comment": "7a055de39d3b818c", "s_suppkey": "1343345adeea4123",
        "s_name": "81c6e85ff9441327", "s_address": "ee2a3e83d946f1f3",
        "s_nationkey": "2ffec31146d1da28", "s_phone": "724de95c3e4aabea",
        "s_acctbal": "e03476db67e491f1", "s_comment": "5aa94e715bc5a27c",
        "c_custkey": "d82a32e481bfb96b", "c_name": "7f7c53fa9eaef3a3",
        "c_address": "cf884b7f0bd45f86", "c_nationkey": "bf339f4462528d7b",
        "c_phone": "81309fef382dab71", "c_acctbal": "977f83cf6f147deb",
        "c_mktsegment": "dbc7081f8654a220", "c_comment": "e6e0e24465278844",
        "p_partkey": "794eb550574c420d", "p_name": "535d0c056f3e8ac4",
        "p_mfgr": "3e150798f0ea2fda", "p_brand": "0d917ad8e6263625",
        "p_type": "dfeced17cbe64134", "p_size": "d5ad1d8e0d557984",
        "p_container": "fd90abb605ee5d1f",
        "p_retailprice": "702e3f2596a0ffaf", "p_comment": "a4c0bb42815d333d",
        "ps_partkey": "79d14034d15edec5", "ps_suppkey": "e097ded7df4cbfa4",
        "ps_availqty": "40021657e6af4480",
        "ps_supplycost": "e2d3de763859fdd5", "ps_comment": "527888d680de204b",
        "o_orderkey": "680209a1e4e4b527", "o_custkey": "ce079cf7794d34fa",
        "o_orderstatus": "008b0c26ad97e6df",
        "o_totalprice": "bbc92dc8a67b004b", "o_orderdate": "cb9e51243f2130bf",
        "o_orderpriority": "b286f817d12e531b", "o_clerk": "09bec7e1085cf577",
        "o_shippriority": "f99b090ae95fec05", "o_comment": "2e876a45b464f8eb",
        "l_orderkey": "af78b4d7c707fa80", "l_partkey": "62a34640e910c1f4",
        "l_suppkey": "1396c1d82adb53e1", "l_linenumber": "993d2ae55946be52",
        "l_quantity": "cce3ff115980dd40",
        "l_extendedprice": "fbe4fa4f955d908f",
        "l_discount": "1e397c2a23a1c70a", "l_tax": "d5d340665fabea1d",
        "l_returnflag": "a6712d23830469c4",
        "l_linestatus": "83333c94a5512239", "l_shipdate": "1408b9c9b961758d",
        "l_commitdate": "00fd39e4c6228a84",
        "l_receiptdate": "bbb59a978cc1c95e",
        "l_shipinstruct": "aad4cd92e9d721d7",
        "l_shipmode": "a05490e9c1bca839", "l_comment": "02b1dc0b5ea3d8e7",
    },
    2147483649: {
        "r_regionkey": "60c939fb42b3b6a8", "r_name": "fdba52e188947256",
        "r_comment": "e8814f928c7d1c0b", "n_nationkey": "8d86474b586d73ab",
        "n_name": "72f954b592681f23", "n_regionkey": "0dc7a35ef2e1454c",
        "n_comment": "15808957ba7ef78a", "s_suppkey": "1343345adeea4123",
        "s_name": "81c6e85ff9441327", "s_address": "a4a2a620649709ac",
        "s_nationkey": "bda1e8e4a307ee50", "s_phone": "1847a13e1de14a20",
        "s_acctbal": "003b71167a637d3f", "s_comment": "15efa2c11cecef52",
        "c_custkey": "d82a32e481bfb96b", "c_name": "7f7c53fa9eaef3a3",
        "c_address": "0a199de43b87fade", "c_nationkey": "585304d883f9818e",
        "c_phone": "dca596092b80e75c", "c_acctbal": "cbec86cf55bded48",
        "c_mktsegment": "fbf0565e07424428", "c_comment": "b09adfa985a9005d",
        "p_partkey": "794eb550574c420d", "p_name": "88936e9e4512a61c",
        "p_mfgr": "c0032211629f5e97", "p_brand": "76f84174b31c6775",
        "p_type": "73355eead10f61a1", "p_size": "b5a0485b405654cb",
        "p_container": "5753deb6cb54a36a",
        "p_retailprice": "702e3f2596a0ffaf", "p_comment": "d590946d01bc62ad",
        "ps_partkey": "79d14034d15edec5", "ps_suppkey": "e097ded7df4cbfa4",
        "ps_availqty": "98155d8cf3c8d551",
        "ps_supplycost": "15434495748cd1d3", "ps_comment": "7eb0e637a2dc9534",
        "o_orderkey": "680209a1e4e4b527", "o_custkey": "dcfcae52b01a7445",
        "o_orderstatus": "0711a0f1f5442172",
        "o_totalprice": "e9cd2765178e6b34", "o_orderdate": "c0a010eb87a0a2cd",
        "o_orderpriority": "647052b094750f66", "o_clerk": "e13064e7d9c8ca5e",
        "o_shippriority": "f99b090ae95fec05", "o_comment": "cd073883d4d68ff3",
        "l_orderkey": "8f72bcee9cab7286", "l_partkey": "492f23e3535b159f",
        "l_suppkey": "2f66637b9f48b959", "l_linenumber": "c6c86c3f10851965",
        "l_quantity": "da5c780cbd845ac0",
        "l_extendedprice": "74129c01a6819393",
        "l_discount": "9e648e8c236ddfb8", "l_tax": "3c33bcd6e2c33eb3",
        "l_returnflag": "efd1b741b668e224",
        "l_linestatus": "da4ddffd1637bd83", "l_shipdate": "8ac672460b2ef9a8",
        "l_commitdate": "e60c2688bb72766a",
        "l_receiptdate": "2cca663bc62bdd03",
        "l_shipinstruct": "878ac70d7a768781",
        "l_shipmode": "270781bcbfa6e0d3", "l_comment": "4d8cfc2116d2b2a7",
    },
}
TABLES = ("region", "nation", "supplier", "customer", "part", "partsupp",
          "orders", "lineitem")


def column_digest(column) -> str:
    h = hashlib.sha256(f"{column.type}|".encode())
    h.update(repr(column.to_pylist()).encode())
    return h.hexdigest()[:16]


@pytest.fixture(scope="module")
def generated():
    return {seed: gen_tpch(0.01, seed) for seed in PARENT_DIGESTS}


@pytest.mark.parametrize("table", TABLES)
@pytest.mark.parametrize("seed", PARENT_DIGESTS)
def test_every_column_holds_the_values_the_parent_made(generated, seed,
                                                       table):
    arrow = generated[seed][table]
    assert arrow.num_columns > 0
    for field in arrow.schema:
        assert column_digest(arrow.column(field.name)) == (
            PARENT_DIGESTS[seed][field.name]), field.name
    # and no column the parent did not make
    assert set(arrow.column_names) <= set(PARENT_DIGESTS[seed])


def test_the_pins_cover_every_column(generated):
    for seed, tables in generated.items():
        assert sorted(PARENT_DIGESTS[seed]) == sorted(
            name for t in tables.values() for name in t.column_names)


@pytest.mark.parametrize("sf", [0.01, 1.0, 10.0])
def test_cardinalities_are_the_specs_by_formula(sf):
    """Clause 4.2.5, without generating: SF10 is 15M orders."""
    rows = tpch_cardinalities(sf)
    assert (rows["region"], rows["nation"]) == (5, 25)
    assert rows["supplier"] == round(10_000 * sf)
    assert rows["customer"] == round(150_000 * sf)
    assert rows["part"] == round(200_000 * sf)
    assert rows["partsupp"] == round(800_000 * sf)
    assert rows["orders"] == round(1_500_000 * sf)
    assert "lineitem" not in rows  # drawn: one to seven lines an order


def test_generated_tables_have_the_formulas_row_counts(generated):
    for tables in generated.values():
        rows = tpch_cardinalities(0.01)
        assert {n: t.num_rows for n, t in tables.items()
                if n != "lineitem"} == rows
        lines = tables["lineitem"].num_rows
        assert rows["orders"] <= lines <= 7 * rows["orders"]
        assert abs(lines / rows["orders"] - 4.0) < 0.1
        # the fact table's keys are the dimensions' keys
        keys = tables["lineitem"].column("l_orderkey").to_numpy()
        assert keys.min() == 1 and keys.max() == rows["orders"]


def test_the_patterns_q13_and_q16_look_for_are_injected(generated):
    for tables in generated.values():
        o_comment = tables["orders"].column("o_comment").to_pylist()
        assert o_comment[0::17] == [
            "blithely special foxes requests nag"] * len(o_comment[0::17])
        s_comment = tables["supplier"].column("s_comment").to_pylist()
        assert s_comment[0::19] == [
            "wake Customer slyly Complaints haggle"] * len(s_comment[0::19])
        assert "Customer" not in " ".join(s_comment[1:19])


def test_part_names_are_five_colour_words(generated):
    colours = set(tpchgen._COLOR_WORDS)
    for tables in generated.values():
        names = tables["part"].column("p_name").to_pylist()
        assert all(len(n.split(" ")) == 5 and set(n.split(" ")) <= colours
                   for n in names)
        # q9 and q20 filter on them: neither may come back empty
        assert any("green" in n for n in names)
        assert any(n.startswith("forest") for n in names)


def test_text_columns_are_arrow_strings_of_the_spec_shapes(generated):
    tables = generated[7]
    import pyarrow as pa

    for table in tables.values():
        for field in table.schema:
            if field.name.split("_", 1)[1] in (
                    "comment", "name", "address", "phone", "clerk", "brand",
                    "mfgr", "type", "container", "mktsegment", "shipmode",
                    "shipinstruct", "returnflag", "linestatus",
                    "orderstatus", "orderpriority"):
                assert field.type == pa.string(), field.name
    assert tables["supplier"].column("s_name")[6].as_py() == (
        "Supplier#000000007")
    assert tables["customer"].column("c_name")[-1].as_py() == (
        f"Customer#{tables['customer'].num_rows:09d}")
    phone = tables["customer"].column("c_phone")[0].as_py().split("-")
    assert [len(p) for p in phone] == [2, 3, 3, 4]
    assert int(phone[0]) == 10 + tables["customer"].column(
        "c_nationkey")[0].as_py()
    assert set(tables["lineitem"].column("l_returnflag").to_pylist()) == set(
        "RAN")
    assert set(tables["orders"].column("o_orderstatus").to_pylist()) == set(
        "OPF")
    assert all(c.startswith("Clerk#0000000") for c in
               tables["orders"].column("o_clerk").to_pylist()[:50])


def test_a_text_column_is_not_made_by_a_loop_over_rows():
    """2M comments: the interpreter makes far fewer calls than there are
    rows (a loop over rows makes one `str.join` a row at the least), on
    this thread and on the chunks' threads, and inside a time limit that a
    loop over rows on a loaded host would not hold."""
    rows = 2_000_000
    calls = [0]

    def count(frame, event, arg):
        calls[0] += 1

    rng = np.random.default_rng(1)
    threading.setprofile(count)
    sys.setprofile(count)
    start = time.perf_counter()
    try:
        column = tpchgen._comments(rng, rows, 4, every=17, instead="x y")
    finally:
        sys.setprofile(None)
        threading.setprofile(None)
    seconds = time.perf_counter() - start
    assert len(column) == rows
    assert calls[0] < rows // 20
    assert seconds < 30
    # what the loop made: 2 to 4 of the comment words, joined by spaces
    head = column.slice(0, 1000).to_pylist()
    assert head[0] == "x y" and head[17] == "x y"
    words = set(tpchgen._COMMENT_WORDS)
    assert all(2 <= len(c.split(" ")) <= 4 and set(c.split(" ")) <= words
               for i, c in enumerate(head) if i % 17)


def test_two_seeds_differ_and_one_seed_repeats():
    a, b = gen_tpch(0.01, 11), gen_tpch(0.01, 12)
    assert not a["lineitem"].equals(b["lineitem"])
    assert gen_tpch(0.01, 11)["lineitem"].equals(a["lineitem"])
