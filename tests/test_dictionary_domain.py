"""A GROUP BY over dictionary keys is given a group table as wide as the
keys' domain (`sql/planner.py _agg_slots`, `DIRECT_MAX_SLOTS`), so its
groups are addressed by their codes past ``max_slots`` too; a table keyed
by anything else keeps the cap. What the program counts of it
(`group_slots`, `scatter_reductions`), the TPC-H plans and programs that
stay the parent's, and the ClickBench generator's phrase law."""

import json
import os
import time
from dataclasses import replace

import numpy as np
import pyarrow as pa
import pytest

from datafusion_distributed_tpu.data import clickbenchgen
from datafusion_distributed_tpu.plan.physical import HashAggregateExec
from datafusion_distributed_tpu.runtime.tracing import layer_report
from datafusion_distributed_tpu.sql.context import SessionContext

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2500000001  # more than 32 signed bits hold


def _counters(ctx, sql: str):
    """-> (pandas answer, the request's layer-report counters, retries,
    the plan's aggregates' slots), the request traced."""
    ctx.config.distributed_options["tracing"] = "on"
    try:
        df = ctx.sql(sql)
        got = df.to_pandas()
    finally:
        ctx.config.distributed_options.pop("tracing", None)
    (row,) = [r for r in layer_report() if r["request"] == df.request_id]
    slots = [n.num_slots for n in df.physical_plan().collect(
        lambda n: isinstance(n, HashAggregateExec))]
    return got, row["counters"], df.last_retry_count, slots


def _keyed(rows: int, keys: int, nulls: bool, dictionary: bool):
    rng = np.random.default_rng(11)
    codes = rng.permutation(np.arange(rows) % keys)
    k = np.char.add("key", codes.astype(str)) if dictionary else codes
    valid = (rng.random(rows) > 0.05) if nulls else None
    return pa.table({
        "k": pa.array(k, mask=None if valid is None else ~valid),
        "v": pa.array(rng.integers(0, 100, rows))})


@pytest.mark.parametrize("nulls", [False, True], ids=["no_null", "nulls"])
def test_a_dictionary_key_past_max_slots_is_addressed_by_its_codes(nulls):
    """768 codes (769 with NULL) under ``max_slots`` 256: the table is 1024
    slots wide and direct, one attempt, the answer pandas'."""
    ctx = SessionContext()
    ctx.config.planner = replace(ctx.config.planner, max_slots=256)
    arrow = _keyed(6_000, 3 * 256, nulls, dictionary=True)
    ctx.register_arrow("t", arrow)
    domain = 3 * 256 + nulls
    got, counters, retries, slots = _counters(
        ctx, "SELECT k, COUNT(*) AS c, SUM(v) AS s FROM t GROUP BY k")
    assert slots == [1024] and retries == 0
    assert counters["direct_groupings"] == 1
    assert counters["group_slots"] == domain
    # past none of the cut: every reduction still a dense pass
    assert counters["scatter_reductions"] == 0
    want = arrow.to_pandas().groupby("k", dropna=False).agg(
        c=("v", "size"), s=("v", "sum")).reset_index()
    key = lambda f: f.k.fillna("<null>")  # noqa: E731
    got = got.assign(k=key(got)).sort_values("k", ignore_index=True)
    want = want.assign(k=key(want)).sort_values("k", ignore_index=True)
    assert got.k.tolist() == want.k.tolist()
    assert got.c.tolist() == want.c.tolist()
    assert got.s.tolist() == want.s.tolist()


def test_a_hash_keyed_group_by_keeps_max_slots():
    """The same 768 keys as integers have no domain known when the plan is
    made: the claim loop's table stays capped at ``max_slots``, which no
    retry widens, so the groups overflow it as they did before."""
    from datafusion_distributed_tpu.runtime.errors import (
        CapacityOverflowError,
    )

    ctx = SessionContext()
    ctx.config.planner = replace(ctx.config.planner, max_slots=256)
    ctx.register_arrow("t", _keyed(6_000, 3 * 256, False, dictionary=False))
    df = ctx.sql("SELECT k, COUNT(*) AS c FROM t GROUP BY k")
    (agg,) = df.physical_plan().collect(
        lambda n: isinstance(n, HashAggregateExec))
    assert agg.num_slots == 256
    with pytest.raises(CapacityOverflowError):
        df.to_pandas()


@pytest.fixture(scope="module")
def tpch_ctx():
    from datafusion_distributed_tpu.data.tpchgen import gen_tpch

    ctx = SessionContext()
    for name, arrow in gen_tpch(sf=0.002, seed=7).items():
        ctx.register_arrow(name, arrow)
    return ctx


def _tpch_sql(query: str) -> str:
    with open(os.path.join(ROOT, "benchmarks", "queries", "tpch",
                           f"{query}.sql")) as f:
        return f.read()


def _clickbench_sql(query: str) -> str:
    with open(os.path.join(ROOT, "benchmarks", "queries", "clickbench",
                           f"{query}.sql")) as f:
        return f.read()


@pytest.mark.parametrize("query,group_slots", [("q1", 6), ("q6", 1)])
def test_tpch_aggregates_reduce_without_a_scatter(tpch_ctx, query,
                                                  group_slots):
    """q1's domain of 6 and q6's global aggregate: dense passes only."""
    _, counters, retries, _ = _counters(tpch_ctx, _tpch_sql(query))
    assert counters["scatter_reductions"] == 0 and retries == 0
    assert counters["group_slots"] == group_slots


def test_clickbench_q12_past_the_dense_cut_counts_its_scatters():
    """60,000 rows of hits draw more phrases than `_DENSE_MAX_DOMAIN`: the
    direct grouping's count and its slot-presence pass are scatters."""
    from datafusion_distributed_tpu.ops.aggregate import _DENSE_MAX_DOMAIN

    arrow = clickbenchgen.gen_clickbench(60_000, SEED)
    ctx = SessionContext()
    ctx.register_arrow("hits", arrow)
    got, counters, retries, slots = _counters(ctx, _clickbench_sql("q12"))
    domain = len(set(arrow.column("SearchPhrase").to_pylist()))
    assert domain > _DENSE_MAX_DOMAIN and retries == 0
    assert counters["direct_groupings"] == 1
    assert counters["group_slots"] == domain
    assert slots[0] >= domain
    assert counters["scatter_reductions"] >= 2
    phrases = arrow.column("SearchPhrase").to_pandas()
    counts = phrases[phrases != ""].value_counts()
    assert got.c.tolist() == counts.head(10).tolist()
    assert all(counts[p] == c for p, c in zip(got.SearchPhrase, got.c))


# the parent's TPC-H plans at SF0.002, seed 7: every aggregate's slots in
# pre-order, and the sha256 of q1's and q6's lowered text (no debug info);
# none of their keys is a dictionary column whose domain outgrows the table
# the other bounds give, so the domain's floor changes no plan
_PARENT_SLOTS = {
    "q1": [32], "q2": [2048], "q3": [32768], "q4": [32], "q5": [128],
    "q6": [16], "q7": [128], "q8": [1024], "q9": [32768], "q10": [32768],
    "q11": [2048], "q12": [32], "q13": [4096, 2048], "q14": [16],
    "q15": [128], "q16": [4096, 4096], "q17": [16, 2048],
    "q18": [32768, 16384], "q19": [16], "q20": [16384], "q21": [128],
    "q22": [256]}
_PARENT_LOWERED_SHA256 = {
    "q1": "c6396ec35857d06ac56bcdeb2210a3436e8920be92509d62ac637ce39df349fd",
    "q6": "17363421edff4a3a8ccacd96ad694c016569d7e6cdf843830258eebfdd5709da"}


@pytest.mark.parametrize("query", sorted(_PARENT_SLOTS,
                                         key=lambda q: int(q[1:])))
def test_tpch_plans_keep_the_parents_group_tables(tpch_ctx, query):
    plan = tpch_ctx.sql(_tpch_sql(query)).physical_plan()
    assert [n.num_slots for n in plan.collect(
        lambda n: isinstance(n, HashAggregateExec))] == _PARENT_SLOTS[query]


@pytest.mark.parametrize("query", sorted(_PARENT_LOWERED_SHA256))
def test_tpch_q1_and_q6_lower_to_the_parents_text(tpch_ctx, query):
    import hashlib

    from datafusion_distributed_tpu.plan import physical as phys
    from datafusion_distributed_tpu.plan.physical import (
        DistributedTaskContext,
    )
    from datafusion_distributed_tpu.spans import NULL_TRACER

    plan = tpch_ctx.sql(_tpch_sql(query)).physical_plan()
    prog = phys._prepare_program(
        plan, DistributedTaskContext(), None, False, None, None, NULL_TRACER)
    text = prog.fn.lower(prog.inputs, prog.params).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        _PARENT_LOWERED_SHA256[query])


# -- the generator's phrase law ----------------------------------------------

def test_the_generator_is_deterministic_by_seed():
    a = clickbenchgen.gen_clickbench(20_000, SEED)
    assert a.equals(clickbenchgen.gen_clickbench(20_000, SEED))
    b = clickbenchgen.gen_clickbench(20_000, SEED + 1)
    assert not a.column("SearchPhrase").equals(b.column("SearchPhrase"))
    assert a.num_columns == 25 and a.schema.equals(b.schema)


def test_a_million_rows_follow_the_phrase_law_without_a_row_loop():
    """At 1M rows: the empty share within 1% of the stated one, the
    distinct phrases within five standard deviations of what Zipf over
    6,019,102 phrases predicts for the non-empty rows drawn, and made in
    a time no Python loop over a million rows keeps to."""
    start = time.perf_counter()
    arrow = clickbenchgen.gen_clickbench(1_000_000, SEED)
    took = time.perf_counter() - start
    assert took < 10.0
    phrases = arrow.column("SearchPhrase").to_pandas()
    empty = float((phrases == "").mean())
    assert abs(empty - clickbenchgen.EMPTY_PHRASE_SHARE) < 0.01
    drawn = int((phrases != "").sum())
    ranks = np.arange(1, clickbenchgen.SEARCH_PHRASES + 1, dtype=np.float64)
    p = ranks ** -clickbenchgen.ZIPF_EXPONENT
    p /= p.sum()
    missed = np.exp(drawn * np.log1p(-p))
    mean, sd = float((1 - missed).sum()), float(
        np.sqrt((missed * (1 - missed)).sum()))
    distinct = phrases[phrases != ""].nunique()
    assert abs(distinct - mean) < 5 * sd + 1, (distinct, mean, sd)
    # the most popular phrase draws about 1/H(6,019,102) of them
    top = phrases[phrases != ""].value_counts().iloc[0]
    assert abs(top / drawn - p[0]) < 0.01


def test_a_phrase_is_a_function_of_its_rank():
    """Distinct ranks never share a text; 1-5 words; the seed shuffles the
    words and nothing else."""
    ranks = np.concatenate([np.arange(1, 20_001),
                            np.arange(clickbenchgen.SEARCH_PHRASES - 999,
                                      clickbenchgen.SEARCH_PHRASES + 1)])
    texts = clickbenchgen.phrase_texts(ranks, SEED).to_pylist()
    assert len(set(texts)) == len(texts) and "" not in texts
    words = [len(t.split(" ")) for t in texts]
    assert min(words) == 1 and max(words) == 5
    again = clickbenchgen.phrase_texts(ranks[::-1], SEED).to_pylist()
    assert again == texts[::-1]
    other = clickbenchgen.phrase_texts(ranks, SEED + 1).to_pylist()
    assert [len(t.split(" ")) for t in other] == words and other != texts


def test_the_config_states_the_phrase_law():
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           "clickbench-half-direct.json")) as f:
        assumed = " ".join(json.load(f)["assumed"])
    assert f"{clickbenchgen.SEARCH_PHRASES:,}" in assumed
    assert f"{clickbenchgen.EMPTY_PHRASE_SHARE:.0%}" in assumed
    assert f"exponent {clickbenchgen.ZIPF_EXPONENT}" in assumed
    assert "below 2^31" in assumed
