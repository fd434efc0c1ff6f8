"""Pandas oracle for the 22 TPC-H queries: the benchmark's plain reference.

A copy of tests/tpch_oracle.py, kept here so that no later PR can change the
yardstick: an independent pandas implementation of each query, straight from
the spec's text, and the comparison that decides ``correct``. Comparison is
order-insensitive (sorted multiset, since f32 sums may swap near-ties of an
ORDER BY) with the float tolerance written below as numbers. They are the
32-bit mode's ``precision.oracle_rtol()`` / ``oracle_atol()`` as of PR 25
(f32 scatter-add over N addends accumulates ~eps_f32*sqrt(N); 5e-4 covers
N up to ~10^7), fixed here so that loosening ``precision.py`` loosens
nothing the benchmark checks.
"""

from __future__ import annotations

import numpy as np
import pandas as pd

#: floats agree within RTOL relative and ATOL absolute; every other column,
#: and the row and column counts, exactly
RTOL = 5e-4
ATOL = 1e-4


def _days(s: str) -> int:
    return (np.datetime64(s) - np.datetime64("1970-01-01")).astype(int)


def _to_days(col):
    """pandas date-ish column -> int days since epoch. Object columns that
    are NOT date-like (plain strings) pass through unchanged — newer pandas
    raises DateParseError on them instead of best-effort parsing."""
    if col.dtype == object or str(col.dtype).startswith("date"):
        try:
            return pd.Series(
                [(pd.Timestamp(v) - pd.Timestamp("1970-01-01")).days
                 if v is not None else None for v in col]
            )
        except (ValueError, TypeError):
            return col
    return col


def load_pandas(arrow_tables: dict) -> dict:
    out = {}
    for name, t in arrow_tables.items():
        df = t.to_pandas()
        for c in df.columns:
            if str(t.schema.field(c).type) == "date32[day]":
                df[c] = pd.Series(
                    (pd.to_datetime(df[c]) - pd.Timestamp("1970-01-01")).dt.days
                )
        out[name] = df
    return out


def q1(T):
    l = T["lineitem"]
    l = l[l.l_shipdate <= _days("1998-09-02")].copy()
    l["disc_price"] = l.l_extendedprice * (1 - l.l_discount)
    l["charge"] = l.disc_price * (1 + l.l_tax)
    g = l.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"),
    ).reset_index()
    return g.sort_values(["l_returnflag", "l_linestatus"]).reset_index(drop=True)


def q2(T):
    p, s, ps, n, r = T["part"], T["supplier"], T["partsupp"], T["nation"], T["region"]
    eu = r[r.r_name == "EUROPE"]
    nn = n.merge(eu, left_on="n_regionkey", right_on="r_regionkey")
    ss = s.merge(nn, left_on="s_nationkey", right_on="n_nationkey")
    j = ps.merge(ss, left_on="ps_suppkey", right_on="s_suppkey")
    pp = p[(p.p_size == 15) & p.p_type.str.endswith("BRASS")]
    j = j.merge(pp, left_on="ps_partkey", right_on="p_partkey")
    mins = j.groupby("ps_partkey")["ps_supplycost"].min().rename("min_cost")
    j = j.merge(mins, left_on="ps_partkey", right_index=True)
    j = j[j.ps_supplycost == j.min_cost]
    out = j[["s_acctbal", "s_name", "n_name", "p_partkey", "p_mfgr",
             "s_address", "s_phone", "s_comment"]]
    out = out.sort_values(
        ["s_acctbal", "n_name", "s_name", "p_partkey"],
        ascending=[False, True, True, True],
    ).reset_index(drop=True)
    return out


def q3(T):
    c, o, l = T["customer"], T["orders"], T["lineitem"]
    c = c[c.c_mktsegment == "BUILDING"]
    o = o[o.o_orderdate < _days("1995-03-15")]
    l = l[l.l_shipdate > _days("1995-03-15")].copy()
    j = l.merge(o, left_on="l_orderkey", right_on="o_orderkey").merge(
        c, left_on="o_custkey", right_on="c_custkey"
    )
    j["rev"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"]).agg(
        revenue=("rev", "sum")
    ).reset_index()
    g = g.sort_values(["revenue", "o_orderdate"], ascending=[False, True])
    return g[["l_orderkey", "revenue", "o_orderdate",
              "o_shippriority"]].reset_index(drop=True)


def q4(T):
    o, l = T["orders"], T["lineitem"]
    o = o[(o.o_orderdate >= _days("1993-07-01")) & (o.o_orderdate < _days("1993-10-01"))]
    good = l[l.l_commitdate < l.l_receiptdate].l_orderkey.unique()
    o = o[o.o_orderkey.isin(good)]
    g = o.groupby("o_orderpriority").size().rename("order_count").reset_index()
    return g.sort_values("o_orderpriority").reset_index(drop=True)


def q5(T):
    c, o, l, s, n, r = (T["customer"], T["orders"], T["lineitem"],
                        T["supplier"], T["nation"], T["region"])
    r = r[r.r_name == "ASIA"]
    o = o[(o.o_orderdate >= _days("1994-01-01")) & (o.o_orderdate < _days("1995-01-01"))]
    j = (l.merge(o, left_on="l_orderkey", right_on="o_orderkey")
          .merge(c, left_on="o_custkey", right_on="c_custkey")
          .merge(s, left_on="l_suppkey", right_on="s_suppkey"))
    j = j[j.c_nationkey == j.s_nationkey]
    j = j.merge(n, left_on="s_nationkey", right_on="n_nationkey").merge(
        r, left_on="n_regionkey", right_on="r_regionkey")
    j["rev"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby("n_name").agg(revenue=("rev", "sum")).reset_index()
    return g.sort_values("revenue", ascending=False).reset_index(drop=True)


def _sql_sum(series):
    """SQL SUM semantics: empty input -> NULL (NaN), not 0."""
    return series.sum() if len(series) else np.nan


def q6(T):
    l = T["lineitem"]
    m = l[(l.l_shipdate >= _days("1994-01-01")) & (l.l_shipdate < _days("1995-01-01"))
          & (l.l_discount >= 0.05) & (l.l_discount <= 0.07) & (l.l_quantity < 24)]
    return pd.DataFrame({"revenue": [_sql_sum(m.l_extendedprice * m.l_discount)]})


def q7(T):
    s, l, o, c, n = (T["supplier"], T["lineitem"], T["orders"], T["customer"],
                     T["nation"])
    j = (l.merge(s, left_on="l_suppkey", right_on="s_suppkey")
          .merge(o, left_on="l_orderkey", right_on="o_orderkey")
          .merge(c, left_on="o_custkey", right_on="c_custkey")
          .merge(n.add_prefix("n1_"), left_on="s_nationkey",
                 right_on="n1_n_nationkey")
          .merge(n.add_prefix("n2_"), left_on="c_nationkey",
                 right_on="n2_n_nationkey"))
    j = j[(j.l_shipdate >= _days("1995-01-01")) & (j.l_shipdate <= _days("1996-12-31"))]
    j = j[((j.n1_n_name == "FRANCE") & (j.n2_n_name == "GERMANY"))
          | ((j.n1_n_name == "GERMANY") & (j.n2_n_name == "FRANCE"))]
    j = j.copy()
    j["l_year"] = pd.to_datetime(
        j.l_shipdate, unit="D", origin="1970-01-01"
    ).dt.year
    j["volume"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby(["n1_n_name", "n2_n_name", "l_year"]).agg(
        revenue=("volume", "sum")).reset_index()
    g.columns = ["supp_nation", "cust_nation", "l_year", "revenue"]
    return g.sort_values(["supp_nation", "cust_nation", "l_year"]).reset_index(
        drop=True)


def q8(T):
    p, s, l, o, c, n, r = (T["part"], T["supplier"], T["lineitem"], T["orders"],
                           T["customer"], T["nation"], T["region"])
    p = p[p.p_type == "ECONOMY ANODIZED STEEL"]
    o = o[(o.o_orderdate >= _days("1995-01-01")) & (o.o_orderdate <= _days("1996-12-31"))]
    r = r[r.r_name == "AMERICA"]
    j = (l.merge(p, left_on="l_partkey", right_on="p_partkey")
          .merge(s, left_on="l_suppkey", right_on="s_suppkey")
          .merge(o, left_on="l_orderkey", right_on="o_orderkey")
          .merge(c, left_on="o_custkey", right_on="c_custkey")
          .merge(n.add_prefix("n1_"), left_on="c_nationkey",
                 right_on="n1_n_nationkey")
          .merge(r, left_on="n1_n_regionkey", right_on="r_regionkey")
          .merge(n.add_prefix("n2_"), left_on="s_nationkey",
                 right_on="n2_n_nationkey"))
    j = j.copy()
    j["o_year"] = pd.to_datetime(j.o_orderdate, unit="D",
                                 origin="1970-01-01").dt.year
    j["volume"] = j.l_extendedprice * (1 - j.l_discount)
    j["brazil_volume"] = np.where(j.n2_n_name == "BRAZIL", j.volume, 0.0)
    g = j.groupby("o_year").agg(
        num=("brazil_volume", "sum"), den=("volume", "sum")).reset_index()
    g["mkt_share"] = g.num / g.den
    return g[["o_year", "mkt_share"]].sort_values("o_year").reset_index(drop=True)


def q9(T):
    p, s, l, ps, o, n = (T["part"], T["supplier"], T["lineitem"],
                         T["partsupp"], T["orders"], T["nation"])
    p = p[p.p_name.str.contains("green")]
    j = (l.merge(p, left_on="l_partkey", right_on="p_partkey")
          .merge(s, left_on="l_suppkey", right_on="s_suppkey")
          .merge(ps, left_on=["l_suppkey", "l_partkey"],
                 right_on=["ps_suppkey", "ps_partkey"])
          .merge(o, left_on="l_orderkey", right_on="o_orderkey")
          .merge(n, left_on="s_nationkey", right_on="n_nationkey"))
    j = j.copy()
    j["o_year"] = pd.to_datetime(j.o_orderdate, unit="D",
                                 origin="1970-01-01").dt.year
    j["amount"] = (j.l_extendedprice * (1 - j.l_discount)
                   - j.ps_supplycost * j.l_quantity)
    g = j.groupby(["n_name", "o_year"]).agg(sum_profit=("amount", "sum"))
    g = g.reset_index()
    g.columns = ["nation", "o_year", "sum_profit"]
    return g.sort_values(["nation", "o_year"], ascending=[True, False]).reset_index(
        drop=True)


def q10(T):
    c, o, l, n = T["customer"], T["orders"], T["lineitem"], T["nation"]
    o = o[(o.o_orderdate >= _days("1993-10-01")) & (o.o_orderdate < _days("1994-01-01"))]
    l = l[l.l_returnflag == "R"]
    j = (l.merge(o, left_on="l_orderkey", right_on="o_orderkey")
          .merge(c, left_on="o_custkey", right_on="c_custkey")
          .merge(n, left_on="c_nationkey", right_on="n_nationkey"))
    j = j.copy()
    j["rev"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby(["c_custkey", "c_name", "c_acctbal", "c_phone", "n_name",
                   "c_address", "c_comment"]).agg(revenue=("rev", "sum"))
    g = g.reset_index()
    g = g.sort_values("revenue", ascending=False)
    return g[["c_custkey", "c_name", "revenue", "c_acctbal", "n_name",
              "c_address", "c_phone", "c_comment"]].reset_index(drop=True)


def q11(T):
    ps, s, n = T["partsupp"], T["supplier"], T["nation"]
    n = n[n.n_name == "GERMANY"]
    j = ps.merge(s, left_on="ps_suppkey", right_on="s_suppkey").merge(
        n, left_on="s_nationkey", right_on="n_nationkey")
    j = j.copy()
    j["value"] = j.ps_supplycost * j.ps_availqty
    total = j.value.sum() * 0.0001
    g = j.groupby("ps_partkey").agg(value=("value", "sum")).reset_index()
    g = g[g.value > total]
    return g.sort_values("value", ascending=False).reset_index(drop=True)


def q12(T):
    o, l = T["orders"], T["lineitem"]
    l = l[l.l_shipmode.isin(["MAIL", "SHIP"])]
    l = l[(l.l_commitdate < l.l_receiptdate) & (l.l_shipdate < l.l_commitdate)]
    l = l[(l.l_receiptdate >= _days("1994-01-01")) & (l.l_receiptdate < _days("1995-01-01"))]
    j = l.merge(o, left_on="l_orderkey", right_on="o_orderkey").copy()
    j["high"] = j.o_orderpriority.isin(["1-URGENT", "2-HIGH"]).astype(int)
    j["low"] = (~j.o_orderpriority.isin(["1-URGENT", "2-HIGH"])).astype(int)
    g = j.groupby("l_shipmode").agg(
        high_line_count=("high", "sum"), low_line_count=("low", "sum")
    ).reset_index()
    return g.sort_values("l_shipmode").reset_index(drop=True)


def q13(T):
    c, o = T["customer"], T["orders"]
    o = o[~o.o_comment.str.contains("special.*requests", regex=True)]
    cnt = o.groupby("o_custkey").size()
    c = c.copy()
    c["c_count"] = c.c_custkey.map(cnt).fillna(0).astype(int)
    g = c.groupby("c_count").size().rename("custdist").reset_index()
    return g.sort_values(["custdist", "c_count"], ascending=[False, False]).reset_index(
        drop=True)


def q14(T):
    l, p = T["lineitem"], T["part"]
    l = l[(l.l_shipdate >= _days("1995-09-01")) & (l.l_shipdate < _days("1995-10-01"))]
    j = l.merge(p, left_on="l_partkey", right_on="p_partkey").copy()
    j["rev"] = j.l_extendedprice * (1 - j.l_discount)
    promo = np.where(j.p_type.str.startswith("PROMO"), j.rev, 0.0)
    return pd.DataFrame(
        {"promo_revenue": [100.0 * promo.sum() / j.rev.sum()]}
    )


def q15(T):
    l, s = T["lineitem"], T["supplier"]
    l = l[(l.l_shipdate >= _days("1996-01-01")) & (l.l_shipdate < _days("1996-04-01"))]
    l = l.copy()
    l["rev"] = l.l_extendedprice * (1 - l.l_discount)
    rev = l.groupby("l_suppkey").agg(total_revenue=("rev", "sum")).reset_index()
    top = rev[rev.total_revenue == rev.total_revenue.max()]
    j = s.merge(top, left_on="s_suppkey", right_on="l_suppkey")
    return j[["s_suppkey", "s_name", "s_address", "s_phone",
              "total_revenue"]].sort_values("s_suppkey").reset_index(drop=True)


def q16(T):
    p, ps, s = T["part"], T["partsupp"], T["supplier"]
    p = p[(p.p_brand != "Brand#45")
          & ~p.p_type.str.startswith("MEDIUM POLISHED")
          & p.p_size.isin([49, 14, 23, 45, 19, 3, 36, 9])]
    bad = s[s.s_comment.str.contains("Customer.*Complaints", regex=True)].s_suppkey
    j = ps[~ps.ps_suppkey.isin(bad)].merge(
        p, left_on="ps_partkey", right_on="p_partkey")
    g = j.groupby(["p_brand", "p_type", "p_size"])["ps_suppkey"].nunique()
    g = g.rename("supplier_cnt").reset_index()
    return g.sort_values(
        ["supplier_cnt", "p_brand", "p_type", "p_size"],
        ascending=[False, True, True, True],
    ).reset_index(drop=True)


def q17(T):
    l, p = T["lineitem"], T["part"]
    p = p[(p.p_brand == "Brand#23") & (p.p_container == "MED BOX")]
    j = l.merge(p, left_on="l_partkey", right_on="p_partkey")
    avg = l.groupby("l_partkey")["l_quantity"].mean().rename("avg_qty")
    j = j.merge(avg, left_on="p_partkey", right_index=True)
    j = j[j.l_quantity < 0.2 * j.avg_qty]
    return pd.DataFrame({"avg_yearly": [_sql_sum(j.l_extendedprice) / 7.0]})


def q18(T):
    c, o, l = T["customer"], T["orders"], T["lineitem"]
    big = l.groupby("l_orderkey")["l_quantity"].sum()
    big = big[big > 300].index
    o = o[o.o_orderkey.isin(big)]
    j = (o.merge(c, left_on="o_custkey", right_on="c_custkey")
          .merge(l, left_on="o_orderkey", right_on="l_orderkey"))
    g = j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                   "o_totalprice"]).agg(sum_qty=("l_quantity", "sum")).reset_index()
    g = g.sort_values(["o_totalprice", "o_orderdate"], ascending=[False, True])
    return g.reset_index(drop=True)


def q19(T):
    l, p = T["lineitem"], T["part"]
    j = l.merge(p, left_on="l_partkey", right_on="p_partkey")
    sm = ["SM CASE", "SM BOX", "SM PACK", "SM PKG"]
    md = ["MED BAG", "MED BOX", "MED PKG", "MED PACK"]
    lg = ["LG CASE", "LG BOX", "LG PACK", "LG PKG"]
    common = j.l_shipmode.isin(["AIR", "AIR REG"]) & (
        j.l_shipinstruct == "DELIVER IN PERSON")
    b1 = ((j.p_brand == "Brand#12") & j.p_container.isin(sm)
          & (j.l_quantity >= 1) & (j.l_quantity <= 11)
          & (j.p_size >= 1) & (j.p_size <= 5) & common)
    b2 = ((j.p_brand == "Brand#23") & j.p_container.isin(md)
          & (j.l_quantity >= 10) & (j.l_quantity <= 20)
          & (j.p_size >= 1) & (j.p_size <= 10) & common)
    b3 = ((j.p_brand == "Brand#34") & j.p_container.isin(lg)
          & (j.l_quantity >= 20) & (j.l_quantity <= 30)
          & (j.p_size >= 1) & (j.p_size <= 15) & common)
    m = j[b1 | b2 | b3]
    return pd.DataFrame(
        {"revenue": [_sql_sum(m.l_extendedprice * (1 - m.l_discount))]}
    )


def q20(T):
    s, n, ps, p, l = (T["supplier"], T["nation"], T["partsupp"], T["part"],
                      T["lineitem"])
    p = p[p.p_name.str.startswith("forest")]
    l = l[(l.l_shipdate >= _days("1994-01-01")) & (l.l_shipdate < _days("1995-01-01"))]
    sold = l.groupby(["l_partkey", "l_suppkey"])["l_quantity"].sum().rename(
        "qty").reset_index()
    j = ps[ps.ps_partkey.isin(p.p_partkey)].merge(
        sold, how="left",
        left_on=["ps_partkey", "ps_suppkey"], right_on=["l_partkey", "l_suppkey"])
    j["qty"] = j.qty.fillna(0.0)
    j = j[j.ps_availqty > 0.5 * j.qty]
    # NOTE: rows with zero sold quantity satisfy availqty > 0 iff availqty > 0
    good_supp = j.ps_suppkey.unique()
    n = n[n.n_name == "CANADA"]
    out = s[s.s_suppkey.isin(good_supp)].merge(
        n, left_on="s_nationkey", right_on="n_nationkey")
    return out[["s_name", "s_address"]].sort_values("s_name").reset_index(drop=True)


def q21(T):
    s, l, o, n = T["supplier"], T["lineitem"], T["orders"], T["nation"]
    n = n[n.n_name == "SAUDI ARABIA"]
    o = o[o.o_orderstatus == "F"]
    l1 = l[l.l_receiptdate > l.l_commitdate]
    j = (l1.merge(s, left_on="l_suppkey", right_on="s_suppkey")
           .merge(o, left_on="l_orderkey", right_on="o_orderkey")
           .merge(n, left_on="s_nationkey", right_on="n_nationkey"))
    # exists l2: same order, different supplier
    multi = l.groupby("l_orderkey")["l_suppkey"].nunique()
    j = j[j.l_orderkey.map(multi) > 1]
    # not exists l3: same order, different supplier, late
    late = l[l.l_receiptdate > l.l_commitdate]
    late_pairs = late.groupby("l_orderkey")["l_suppkey"].nunique()
    only_late_supp = j.l_orderkey.map(late_pairs).fillna(0)
    j = j[only_late_supp == 1]
    g = j.groupby("s_name").size().rename("numwait").reset_index()
    g = g.sort_values(["numwait", "s_name"], ascending=[False, True])
    return g.reset_index(drop=True)


def q22(T):
    c, o = T["customer"], T["orders"]
    c = c.copy()
    c["cntrycode"] = c.c_phone.str[:2]
    codes = ["13", "31", "23", "29", "30", "18", "17"]
    c = c[c.cntrycode.isin(codes)]
    avg_bal = c[c.c_acctbal > 0.0].c_acctbal.mean()
    c = c[c.c_acctbal > avg_bal]
    c = c[~c.c_custkey.isin(o.o_custkey)]
    g = c.groupby("cntrycode").agg(
        numcust=("c_acctbal", "size"), totacctbal=("c_acctbal", "sum")
    ).reset_index()
    return g.sort_values("cntrycode").reset_index(drop=True)


ORACLES = {f"q{i}": globals()[f"q{i}"] for i in range(1, 23)}


#: what one comparison measures, each with the most it may read: the
#: guarantee of the configurations' files, as numbers
LIMITS = {"rows_off": 0, "columns_off": 0, "cells_differing": 0,
          "float_gap_in_tolerances": 1.0}


def measure_results(got: pd.DataFrame, exp: pd.DataFrame,
                    rtol: float = RTOL, atol: float = ATOL) -> dict:
    """Order-insensitive multiset comparison -> the numbers of `LIMITS`:
    how far the row and column counts are off, how many cells of the other
    columns differ (a NaN against a number among them), and the widest
    float gap as a multiple of its tolerance, ``|got - exp| / (atol + rtol
    * |exp|)``: `np.testing.assert_allclose`'s own test, read and not
    asserted."""
    numbers = {"rows_off": abs(len(got) - len(exp)),
               "columns_off": abs(len(got.columns) - len(exp.columns)),
               "cells_differing": 0, "float_gap_in_tolerances": 0.0}
    if numbers["rows_off"] or numbers["columns_off"] or len(exp) == 0:
        return numbers
    g = got.copy()
    e = exp.copy()
    g.columns = list(range(len(g.columns)))
    e.columns = list(range(len(e.columns)))
    for c in e.columns:
        e[c] = _to_days(e[c])
    # normalize floats for sorting stability
    sort_cols = list(e.columns)
    g = g.sort_values(sort_cols, kind="stable").reset_index(drop=True)
    e = e.sort_values(sort_cols, kind="stable").reset_index(drop=True)
    for c in e.columns:
        ge, ee = g[c], e[c]
        if pd.api.types.is_float_dtype(ee) or pd.api.types.is_float_dtype(ge):
            ga = ge.astype(float).to_numpy()
            ea = ee.astype(float).to_numpy()
            same = (ga == ea) | (np.isnan(ga) & np.isnan(ea))
            finite = np.isfinite(ga) & np.isfinite(ea)
            numbers["cells_differing"] += int((~same & ~finite).sum())
            gaps = np.abs(ga - ea)[finite] / (atol + rtol * np.abs(ea[finite]))
            if gaps.size:
                numbers["float_gap_in_tolerances"] = max(
                    numbers["float_gap_in_tolerances"], float(gaps.max()))
        else:
            numbers["cells_differing"] += sum(
                1 for a, b in zip(ge, ee) if a != b)
    return numbers


def compare_results(got: pd.DataFrame, exp: pd.DataFrame,
                    rtol: float = RTOL, atol: float = ATOL):
    """`measure_results` held to `LIMITS`. Raises AssertionError on a
    mismatch."""
    numbers = measure_results(got, exp, rtol, atol)
    over = {name: value for name, value in numbers.items()
            if value > LIMITS[name]}
    assert not over, f"over the limit: {over} (limits {LIMITS})"
