"""Median over the traced window's requests of the program's ``launch``
spans, summed a request: the jitted call ``prog.fn(inputs, params)`` up to
its return, inside ``execute`` (`plan/physical.py execute_plan`) and
``mesh.execute`` (`runtime/mesh_executor.py`): the host's share of a
program's call (flattening the inputs, the argument checks, the enqueue),
not the device's work, which ``device_wait_ms`` holds. From
`tracing.layer_report` (``total_s["launch"]``). In ``coord4-q1`` it is a
SUM over the worker threads' tasks (``worker_tasks`` of them, up to four
at once), not a critical path. A program from before the span, or a
request that launched nothing, reports none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "ms"
LAYER = "execution"
SOURCE = "program_span"
MOVES = "query_p50_s"


def read(record: dict):
    def launch(row):
        total = row["total_s"].get("launch")
        return None if total is None else total * 1e3

    return LAYERS["median"](record, launch)
