"""Profiler trace: summed durations of all-to-all, all-gather, all-reduce,
reduce-scatter and collective-permute ops on the first device, a query.
None where the trace holds no collective (one chip)."""

UNIT = "ms"
LAYER = "exchange, mesh tier"
SOURCE = "device_trace"
MOVES = "query_p50_s"


def read(record: dict):
    trace = record["trace"]
    if not trace or not trace["queries"] or not trace["collective_ops"]:
        return None
    return trace["collective_s"] / trace["queries"] * 1e3
