"""Median over the traced run's queries of the benchmark's span around
the tier's collect call, ended by ``block_until_ready`` (``bench.execute``,
host clock)."""

import statistics

UNIT = "ms"
LAYER = "execution"
SOURCE = "program_span"
MOVES = "query_p50_s"


def read(record: dict):
    spans = [q["spans"]["bench.execute"] for q in record["queries"]
             if q.get("spans") and "bench.execute" in q["spans"]]
    return statistics.median(spans) * 1e3 if spans else None
