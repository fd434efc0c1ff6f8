"""Median over the traced window's requests that went through the
coordinator of the worker tasks they ran: one a ``worker_execute`` span
(`runtime/worker.py`), from `tracing.layer_report`
(``counters["tasks"]``): 9 for q1 at four tasks a stage (four partial
aggregates, four final ones, the root). The number to read the tier's
summed times by (``launch_ms``, ``device_wait_ms``, ``input_wait_ms``,
``worker_host_ms`` are sums over these tasks' threads). A request that went
through no coordinator, or a program from before the counter, reports
none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "count"
LAYER = "coordinator scheduling"
SOURCE = "program_counter"
MOVES = "query_p50_s"


def read(record: dict):
    def tasks(row):
        if "schedule" not in row["self_s"]:
            return None
        return row["counters"].get("tasks")

    return LAYERS["median"](record, tasks)
