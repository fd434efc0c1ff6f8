"""Deterministic TPC-H data generator (numpy for the numbers, Arrow's string
kernels for the text: no Python loop over rows).

The reference generates benchmark data with external tools
(`/root/reference/benchmarks/gen-tpch.sh` uses tpchgen-rs); data files are
not vendored (testdata is LFS). This generator produces schema-correct,
distribution-plausible TPC-H tables at any scale factor — deterministic by
seed so correctness tests are reproducible. It follows the TPC-H spec's
cardinalities and value domains (spec is public); it is NOT a byte-exact
dbgen clone, which is fine because correctness tests compare our engine
against a trusted oracle (pandas/duck-style reference executor) on the SAME
generated data, and benchmarks measure relative engine speed.

Cardinalities at SF=1: region 5, nation 25, supplier 10k, customer 150k,
part 200k, partsupp 800k, orders 1.5M, lineitem ~6M.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
_NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
_SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
_INSTRUCTIONS = [
    "COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN",
]
_TYPES_P1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
_TYPES_P2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
_TYPES_P3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
_CONTAINERS_P1 = ["SM", "MED", "JUMBO", "WRAP", "LG"]
_CONTAINERS_P2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
_COMMENT_WORDS = (
    "the of and regular deposits carefully quickly furiously final special "
    "express ironic pending bold slyly blithely even silent unusual requests "
    "accounts packages theodolites foxes ideas dependencies instructions "
    "platelets pinto beans sleep haggle nag use wake cajole detect integrate"
).split()

# P_NAME is a concatenation of color words in the TPC-H spec; queries
# FILTER on them (q9 `like '%green%'`, q20 `like 'forest%'`), so a name
# pool without colors makes those queries vacuously return 0 rows — a
# parity check that can never fail. Subset of the spec's color list.
_COLOR_WORDS = (
    "almond antique aquamarine azure beige bisque black blanched blue "
    "blush brown burlywood burnished chartreuse chiffon chocolate coral "
    "cornflower cornsilk cream cyan dark deep dim dodger drab firebrick "
    "floral forest frosted gainsboro ghost goldenrod green grey honeydew "
    "hot indian ivory khaki lace lavender lawn lemon light lime linen "
    "magenta maroon medium metallic midnight mint misty moccasin navajo "
    "navy olive orange orchid pale papaya peach peru pink plum powder "
    "puff purple red rose rosy royal saddle salmon sandy seashell sienna "
    "sky slate smoke snow spring steel tan thistle tomato turquoise "
    "violet wheat white yellow"
).split()

_EPOCH_1992 = 8035  # days 1970-01-01 -> 1992-01-01
_EPOCH_1998_AUG2 = 10440  # last possible o_orderdate (1998-08-02)


def _dates(rng, n, lo=_EPOCH_1992, hi=_EPOCH_1998_AUG2):
    return rng.integers(lo, hi + 1, n).astype(np.int32)


# Text columns are built by Arrow's string kernels, a chunk of rows at a
# time on a few threads (the kernels release the interpreter's lock): at SF10
# `l_comment` alone is 60M strings, and a Python loop over rows cost 29 s a
# SF unit. The chunks come back in order, so the result does not depend on
# the threads.
_TEXT_CHUNK = 1 << 18
_TEXT_THREADS = min(8, os.cpu_count() or 1)


def _text_column(n, make):
    """-> pyarrow string column of ``n`` rows: ``make(lo, hi)`` -> the rows
    ``[lo, hi)`` as a pyarrow string array, for each chunk."""
    import pyarrow as pa

    bounds = [(lo, min(lo + _TEXT_CHUNK, n)) for lo in range(0, n, _TEXT_CHUNK)]
    if len(bounds) == 1:
        return make(*bounds[0])
    with ThreadPoolExecutor(_TEXT_THREADS) as pool:
        chunks = list(pool.map(lambda b: make(*b), bounds))
    return pa.chunked_array(chunks, pa.string())


def _take_strings(vocab, codes):
    """``vocab[codes]`` as a string column: one entry of a short word list
    a row."""
    import pyarrow as pa

    words = pa.array(list(vocab), pa.string())
    codes = np.asarray(codes)
    return _text_column(
        len(codes),
        lambda lo, hi: words.take(pa.array(codes[lo:hi].astype(np.int32))))


def _join_words(vocab, idx, counts=None, every=None, instead=None):
    """Row i -> the words ``vocab[idx[i, :counts[i]]]`` joined by spaces
    (the whole row of ``idx`` where ``counts`` is None; every count is at
    least 2). Rows ``0, every, 2 * every, ...`` hold ``instead``: the
    patterns q13 and q16 look for."""
    import pyarrow as pa
    import pyarrow.compute as pc

    words = pa.array(list(vocab), pa.string())
    absent = pa.scalar(None, pa.string())

    def make(lo, hi):
        cols = []
        for j in range(idx.shape[1]):
            col = words.take(pa.array(np.ascontiguousarray(idx[lo:hi, j])))
            if counts is not None and j >= 2:
                col = pc.if_else(pa.array(counts[lo:hi] > j), col, absent)
            cols.append(col)
        out = pc.binary_join_element_wise(*cols, " ", null_handling="skip")
        if every is not None:
            out = pc.if_else(pa.array(np.arange(lo, hi) % every == 0),
                             pa.scalar(instead, pa.string()), out)
        return out

    return _text_column(idx.shape[0], make)


def _comments(rng, n, max_words=8, every=None, instead=None):
    # the draws are the 64-bit ones the row loop made; they are kept as
    # bytes, a quarter of a gigabyte for SF10's 60M line items and not two
    k = rng.integers(2, max_words + 1, n).astype(np.uint8)
    idx = rng.integers(0, len(_COMMENT_WORDS), (n, max_words)).astype(np.uint8)
    return _join_words(_COMMENT_WORDS, idx, k, every, instead)


def _numbered(prefix, numbers, width=9):
    """``f"{prefix}{i:0{width}d}"`` for every i of ``numbers``."""
    import pyarrow as pa
    import pyarrow.compute as pc

    digits = pc.utf8_lpad(pc.cast(pa.array(numbers), pa.string()), width, "0")
    return pc.binary_join_element_wise(pa.scalar(prefix), digits, "")


def _phones(rng, n, nation_keys):
    import pyarrow as pa
    import pyarrow.compute as pc

    a = nation_keys.astype(np.int64) + 10
    b = rng.integers(100, 1000, n)
    c = rng.integers(100, 1000, n)
    d = rng.integers(1000, 10000, n)
    return pc.binary_join_element_wise(
        *(pc.cast(pa.array(part), pa.string()) for part in (a, b, c, d)), "-")


def tpch_cardinalities(sf: float) -> dict:
    """-> {table: rows} at scale factor ``sf``, the spec's clause 4.2.5 (with
    a floor, so that a tiny scale still joins); `lineitem` is drawn, one to
    seven lines an order, and is not in it."""
    n_part = max(int(200_000 * sf), 40)
    return {
        "region": 5,
        "nation": 25,
        "supplier": max(int(10_000 * sf), 10),
        "customer": max(int(150_000 * sf), 30),
        "part": n_part,
        "partsupp": n_part * 4,
        "orders": max(int(1_500_000 * sf), 150),
    }


def gen_tpch(sf: float = 0.01, seed: int = 0) -> dict:
    """-> {table_name: pyarrow.Table} for all 8 TPC-H tables."""
    import pyarrow as pa

    rng = np.random.default_rng(seed)

    rows = tpch_cardinalities(sf)
    n_supp, n_cust, n_part = rows["supplier"], rows["customer"], rows["part"]
    n_psupp, n_ord = rows["partsupp"], rows["orders"]
    n_clerks = max(n_supp // 10, 2)

    region = pa.table(
        {
            "r_regionkey": np.arange(5, dtype=np.int64),
            "r_name": pa.array(_REGIONS, pa.string()),
            "r_comment": _comments(rng, 5),
        }
    )

    n_nationkey = np.arange(25, dtype=np.int64)
    nation = pa.table(
        {
            "n_nationkey": n_nationkey,
            "n_name": pa.array([n for n, _ in _NATIONS], pa.string()),
            "n_regionkey": np.array([r for _, r in _NATIONS], dtype=np.int64),
            "n_comment": _comments(rng, 25),
        }
    )

    s_nation = rng.integers(0, 25, n_supp)
    supplier = pa.table(
        {
            "s_suppkey": np.arange(1, n_supp + 1, dtype=np.int64),
            "s_name": _numbered("Supplier#", np.arange(1, n_supp + 1)),
            "s_address": _comments(rng, n_supp, 3),
            "s_nationkey": s_nation.astype(np.int64),
            "s_phone": _phones(rng, n_supp, s_nation),
            "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_supp), 2),
            # TPC-H q16/q20 need "Customer Complaints" / special comments;
            # seed a few
            "s_comment": _comments(
                rng, n_supp, every=19,
                instead="wake Customer slyly Complaints haggle"),
        }
    )

    c_nation = rng.integers(0, 25, n_cust)
    customer = pa.table(
        {
            "c_custkey": np.arange(1, n_cust + 1, dtype=np.int64),
            "c_name": _numbered("Customer#", np.arange(1, n_cust + 1)),
            "c_address": _comments(rng, n_cust, 3),
            "c_nationkey": c_nation.astype(np.int64),
            "c_phone": _phones(rng, n_cust, c_nation),
            "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_cust), 2),
            "c_mktsegment": _take_strings(_SEGMENTS, rng.integers(0, 5, n_cust)),
            "c_comment": _comments(rng, n_cust),
        }
    )

    p1 = rng.integers(0, len(_TYPES_P1), n_part)
    p2 = rng.integers(0, len(_TYPES_P2), n_part)
    p3 = rng.integers(0, len(_TYPES_P3), n_part)
    p_type = _take_strings(
        [f"{a} {b} {c}" for a in _TYPES_P1 for b in _TYPES_P2 for c in _TYPES_P3],
        (p1 * len(_TYPES_P2) + p2) * len(_TYPES_P3) + p3)
    brand_m = rng.integers(1, 6, n_part)
    brand_n = rng.integers(1, 6, n_part)
    c1 = rng.integers(0, len(_CONTAINERS_P1), n_part)
    c2 = rng.integers(0, len(_CONTAINERS_P2), n_part)
    part = pa.table(
        {
            "p_partkey": np.arange(1, n_part + 1, dtype=np.int64),
            # spec shape: five space-joined color words (q9/q20 filter on
            # these; see _COLOR_WORDS)
            "p_name": _join_words(
                _COLOR_WORDS,
                rng.integers(0, len(_COLOR_WORDS), (n_part, 5)).astype(np.uint8)),
            "p_mfgr": _take_strings(
                [f"Manufacturer#{m}" for m in range(1, 6)], brand_m - 1),
            "p_brand": _take_strings(
                [f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)],
                (brand_m - 1) * 5 + brand_n - 1),
            "p_type": p_type,
            "p_size": rng.integers(1, 51, n_part).astype(np.int32),
            "p_container": _take_strings(
                [f"{a} {b}" for a in _CONTAINERS_P1 for b in _CONTAINERS_P2],
                c1 * len(_CONTAINERS_P2) + c2),
            "p_retailprice": np.round(
                900 + (np.arange(1, n_part + 1) % 1000) / 10
                + 100 * (np.arange(1, n_part + 1) % 10), 2
            ),
            "p_comment": _comments(rng, n_part, 3),
        }
    )

    ps_part = np.repeat(np.arange(1, n_part + 1, dtype=np.int64), 4)
    ps_supp = (
        (ps_part + (np.tile(np.arange(4), n_part) * (n_supp // 4 + 1)))
        % n_supp
    ) + 1
    partsupp = pa.table(
        {
            "ps_partkey": ps_part,
            "ps_suppkey": ps_supp.astype(np.int64),
            "ps_availqty": rng.integers(1, 10_000, n_psupp).astype(np.int32),
            "ps_supplycost": np.round(rng.uniform(1.0, 1000.0, n_psupp), 2),
            "ps_comment": _comments(rng, n_psupp),
        }
    )

    o_cust = rng.integers(1, n_cust + 1, n_ord).astype(np.int64)
    o_date = _dates(rng, n_ord)
    lines_per_order = rng.integers(1, 8, n_ord)
    n_li = int(lines_per_order.sum())

    li_order = np.repeat(np.arange(1, n_ord + 1, dtype=np.int64), lines_per_order)
    li_odate = np.repeat(o_date, lines_per_order)
    li_linenumber = (
        np.arange(n_li) - np.repeat(
            np.cumsum(lines_per_order) - lines_per_order, lines_per_order
        ) + 1
    ).astype(np.int32)
    li_part = rng.integers(1, n_part + 1, n_li).astype(np.int64)
    # supplier chosen among the 4 suppliers of that part (partsupp relation)
    which = rng.integers(0, 4, n_li)
    li_supp = ((li_part + which * (n_supp // 4 + 1)) % n_supp + 1).astype(np.int64)
    qty = rng.integers(1, 51, n_li).astype(np.float64)
    extprice = np.round(qty * (90000 + (li_part % 20001) + 100) / 100.0, 2)
    discount = np.round(rng.integers(0, 11, n_li) / 100.0, 2)
    tax = np.round(rng.integers(0, 9, n_li) / 100.0, 2)
    shipdate = li_odate + rng.integers(1, 122, n_li)
    commitdate = li_odate + rng.integers(30, 91, n_li)
    receiptdate = shipdate + rng.integers(1, 31, n_li)
    today = 10452  # 1998-08-14-ish cutoff for status
    returnflag = np.where(
        receiptdate <= 10225, np.where(rng.random(n_li) < 0.5, 0, 1), 2)
    still_open = shipdate > today - 61

    lineitem = pa.table(
        {
            "l_orderkey": li_order,
            "l_partkey": li_part,
            "l_suppkey": li_supp,
            "l_linenumber": li_linenumber,
            "l_quantity": qty,
            "l_extendedprice": extprice,
            "l_discount": discount,
            "l_tax": tax,
            "l_returnflag": _take_strings("RAN", returnflag),
            "l_linestatus": _take_strings("FO", still_open),
            "l_shipdate": pa.array(
                shipdate.astype("int32"), type=pa.int32()
            ).cast(pa.date32()),
            "l_commitdate": pa.array(
                commitdate.astype("int32"), type=pa.int32()
            ).cast(pa.date32()),
            "l_receiptdate": pa.array(
                receiptdate.astype("int32"), type=pa.int32()
            ).cast(pa.date32()),
            "l_shipinstruct": _take_strings(
                _INSTRUCTIONS, rng.integers(0, len(_INSTRUCTIONS), n_li)),
            "l_shipmode": _take_strings(
                _SHIPMODES, rng.integers(0, len(_SHIPMODES), n_li)),
            "l_comment": _comments(rng, n_li, 4),
        }
    )

    # order status/totalprice derived from lineitems
    import pandas as pd

    li_df = pd.DataFrame(
        {
            "o": li_order,
            "rev": extprice * (1 + tax),
            "open": still_open,
        }
    )
    per_order = li_df.groupby("o").agg(total=("rev", "sum"), any_open=("open", "any"),
                                       all_open=("open", "all"))
    totalprice = np.round(per_order["total"].reindex(
        np.arange(1, n_ord + 1)).fillna(0.0).to_numpy(), 2)
    any_open = per_order["any_open"].reindex(np.arange(1, n_ord + 1)).fillna(False).to_numpy()
    all_open = per_order["all_open"].reindex(np.arange(1, n_ord + 1)).fillna(False).to_numpy()
    status = np.where(all_open, 0, np.where(any_open, 1, 2))

    orders = pa.table(
        {
            "o_orderkey": np.arange(1, n_ord + 1, dtype=np.int64),
            "o_custkey": o_cust,
            "o_orderstatus": _take_strings("OPF", status),
            "o_totalprice": totalprice,
            "o_orderdate": pa.array(o_date, type=pa.int32()).cast(pa.date32()),
            "o_orderpriority": _take_strings(
                _PRIORITIES, rng.integers(0, 5, n_ord)),
            "o_clerk": _take_strings(
                _numbered("Clerk#", np.arange(n_clerks)).to_pylist(),
                rng.integers(1, n_clerks, n_ord)),
            "o_shippriority": np.zeros(n_ord, dtype=np.int32),
            # q13 needs 'special requests' patterns in o_comment
            "o_comment": _comments(
                rng, n_ord, every=17,
                instead="blithely special foxes requests nag"),
        }
    )

    return {
        "region": region,
        "nation": nation,
        "supplier": supplier,
        "customer": customer,
        "part": part,
        "partsupp": partsupp,
        "orders": orders,
        "lineitem": lineitem,
    }


def register_tpch(ctx, sf: float = 0.01, seed: int = 0) -> dict:
    """Generate + register all TPC-H tables in a SessionContext; returns the
    pyarrow tables (for oracle comparison)."""
    tables = gen_tpch(sf, seed)
    for name, arrow in tables.items():
        ctx.register_arrow(name, arrow)
    return tables
