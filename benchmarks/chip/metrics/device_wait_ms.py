"""Median over the traced window's requests of the time the program's
threads were blocked on the device for a program's small outputs: the self
time of the ``sync`` spans (`plan/physical.py execute_plan`,
`runtime/mesh_executor.py`, `runtime/worker.py`: the pull of the flag
vector, which waits for the program to finish, the metric values, a row
count), from `tracing.layer_report` (``self_s["sync"]``). It holds the
device's own work and whatever else the chip had queued, so it is the host
clock's view of ``device_busy_ms`` plus the waiting. In ``coord4-q1`` it is
a SUM over the worker threads' tasks (``worker_tasks`` of them, up to four
at once, sharing one chip), not a critical path. A program from before the
span reports none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "ms"
LAYER = "execution"
SOURCE = "program_span"
MOVES = "query_p50_s"


def read(record: dict):
    def wait(row):
        self_s = row["self_s"].get("sync")
        return None if self_s is None else self_s * 1e3

    return LAYERS["median"](record, wait)
