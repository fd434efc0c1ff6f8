"""Median over the traced window's requests of the bytes the coordinator
tier's exchanges move through the host: the ``bytes`` of the program's
``d2h`` spans (a stage's output pulled off the device, `ops/table.py
host_view`) and ``h2d`` spans (host-staged inputs of the next stage,
`plan/physical.py execute_plan`), / 1e6, from `tracing.layer_report`. A
request that went through no coordinator, or whose stages exchanged
nothing through the host, reports none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "MB"
LAYER = "exchange, coordinator tier"
SOURCE = "program_counter"
MOVES = "query_p50_s"
KINDS = ("d2h", "h2d")


def read(record: dict):
    total = LAYERS["coordinator_sum"](
        record, lambda row: row["counters"]["bytes"], KINDS)
    return None if total is None else total / 1e6
