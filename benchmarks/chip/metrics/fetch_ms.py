"""Median over the traced run's queries of the benchmark's span around
device table -> pandas frame on the host (``bench.fetch``, host clock)."""

import statistics

UNIT = "ms"
LAYER = "result fetch"
SOURCE = "program_span"
MOVES = "query_p50_s"


def read(record: dict):
    spans = [q["spans"]["bench.fetch"] for q in record["queries"]
             if q.get("spans") and "bench.fetch" in q["spans"]]
    return statistics.median(spans) * 1e3 if spans else None
