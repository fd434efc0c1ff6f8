"""Median over the traced run's queries of the benchmark's span around
``ctx.sql(text)``: parse, bind, logical plan (``bench.parse``, host clock)."""

import statistics

UNIT = "ms"
LAYER = "front end"
SOURCE = "program_span"
MOVES = "query_p50_s"


def read(record: dict):
    spans = [q["spans"]["bench.parse"] for q in record["queries"]
             if q.get("spans") and "bench.parse" in q["spans"]]
    return statistics.median(spans) * 1e3 if spans else None
