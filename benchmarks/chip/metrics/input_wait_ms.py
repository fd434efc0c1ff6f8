"""Median over the traced window's requests that went through the
coordinator of the time its worker tasks were blocked, not working: the
self time of the program's ``wait`` spans, ``input_wait`` (a consumer
task waiting for its producer stage: `runtime/peer.py
PeerShuffleScanExec.load`, `runtime/streams.py StreamScanExec.task_slice`)
and ``gate_wait`` (a stage-shared program's first-call gate,
`plan/physical.py execute_plan`), from `tracing.layer_report`
(``self_s["wait"]``). A SUM over the worker threads' tasks
(``worker_tasks`` of them): four consumers waiting for the same producers
count four times, so it can pass the query's wall; it is what to take out
of the summed ``worker_execute`` time before reading the rest. A request
that went through no coordinator, or a program from before the spans,
reports none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "ms"
LAYER = "exchange, coordinator tier"
SOURCE = "program_span"
MOVES = "query_p50_s"
KINDS = ("wait",)


def read(record: dict):
    total = LAYERS["coordinator_sum"](record, lambda row: row["self_s"], KINDS)
    return None if total is None else total * 1e3
