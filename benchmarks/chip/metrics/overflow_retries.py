"""Sum of ``df.last_retry_count`` over the window's queries: each retry is
a failed attempt, a new plan and a second execution. None on a tier that
does not report it."""

UNIT = "count"
LAYER = "overflow retry"
SOURCE = "program_counter"
MOVES = "query_p50_s"


def read(record: dict):
    counts = [q["retries"] for q in record["queries"]
              if q.get("retries") is not None]
    return sum(counts) if counts else None
