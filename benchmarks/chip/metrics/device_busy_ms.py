"""Profiler trace: the union of the intervals in which an XLA op runs on the
busiest device, inside the traced queries, a query."""

UNIT = "ms"
LAYER = "operators"
SOURCE = "device_trace"
MOVES = "query_p50_s"


def read(record: dict):
    trace = record["trace"]
    if not trace or not trace["devices"] or not trace["queries"]:
        return None
    return trace["busiest_busy_s"] / trace["queries"] * 1e3
