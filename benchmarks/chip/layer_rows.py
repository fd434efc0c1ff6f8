"""The program's own report of a traced window, for the metric files that
read it (``metrics/*.py`` with ``SOURCE`` ``program_span`` or
``program_counter`` and a ``workloads`` list): `runtime/tracing.py
layer_report`, one row a request, read from the process-wide trace store in
the benchmark's own process after the window. A recording profiler session
is the program's switch, so only the window's requests are there: the
warm-up and every ``--trace 0`` run leave the store empty.

A program that has no such report (a parent commit from before it) gives no
rows, and every metric built on this returns None."""

import statistics


def rows(record: dict) -> list:
    """The rows of the requests begun inside the window. The store's clock
    is ``time.monotonic`` and the traffic loop's ``time.perf_counter``:
    one clock on Linux, so a row is kept from the first query's start to
    the last one's end (the by-hand checks run several cells in one
    process)."""
    try:
        from datafusion_distributed_tpu.runtime import tracing
    except ImportError:
        return []
    report = getattr(tracing, "layer_report", None)
    if report is None or not record["queries"]:
        return []
    opened = min(q["start"] for q in record["queries"])
    closed = max(q.get("end", float("inf")) for q in record["queries"])
    return [row for row in report() if opened <= row["t0_s"] <= closed]


def median(record: dict, value):
    """Median over the window's requests of ``value(row)``, leaving out the
    requests where it is None (a request that never reached that layer).
    None where no request has it."""
    values = [v for v in map(value, rows(record)) if v is not None]
    return statistics.median(values) if values else None



def coordinator_sum(record: dict, table, kinds: tuple):
    """Median over the window's requests that went through the coordinator
    (they hold a ``schedule`` span) of the sum of ``table(row)[kind]`` over
    the ``kinds`` the request holds. None where no such request holds any."""
    def held(row):
        if "schedule" not in row["self_s"]:
            return None
        values = [table(row)[kind] for kind in kinds if kind in table(row)]
        return sum(values) if values else None

    return median(record, held)
