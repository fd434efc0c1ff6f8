"""TPC-H SINGLE-NODE correctness: engine vs pandas oracle on generated data.

This validates the engine itself against an independent oracle; the
distributed tiers (mesh / coordinator, static + adaptive — the analogue of
the reference's `tpch_correctness_test.rs`) live in
tests/test_tpch_distributed.py.
"""

import os
import re

import pytest

from datafusion_distributed_tpu.data.tpchgen import gen_tpch
from datafusion_distributed_tpu.sql.context import SessionContext

from tpch_oracle import ORACLES, compare_results, load_pandas

QUERIES_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "queries", "tpch")
SF = 0.002
SEED = 7


@pytest.fixture(scope="module")
def tpch_env():
    tables = gen_tpch(sf=SF, seed=SEED)
    ctx = SessionContext()
    for name, arrow in tables.items():
        ctx.register_arrow(name, arrow)
    return ctx, load_pandas(tables)


@pytest.mark.parametrize("qname", [f"q{i}" for i in range(1, 23)])
def test_tpch_query(tpch_env, qname):
    ctx, pdf = tpch_env
    with open(os.path.join(QUERIES_DIR, f"{qname}.sql")) as f:
        sql = f.read()
    got = ctx.sql(sql).to_pandas()
    exp = ORACLES[qname](pdf)
    # the oracles return every row in the query's ORDER BY; a trailing
    # LIMIT keeps the first N
    limit = re.search(r"\blimit\s+(\d+)\s*;?\s*$", sql, re.IGNORECASE)
    if limit:
        exp = exp.head(int(limit.group(1)))
    compare_results(got, exp)
