"""Median over the traced window's requests of the host time of the
coordinator tier's exchanges: the self time of the program's ``exchange``,
``transfer``, ``d2h``, ``regroup`` and ``h2d`` spans (`runtime/coordinator.py`,
`ops/table.py host_view`: a stage's outputs pulled to the host, regrouped
by destination and staged as the next stage's inputs), from
`tracing.layer_report`. Not the sum of ``worker_execute``. A request that
went through no coordinator (no ``schedule`` span) reports none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "ms"
LAYER = "exchange, coordinator tier"
SOURCE = "program_span"
MOVES = "query_p50_s"
KINDS = ("exchange", "transfer", "d2h", "regroup", "h2d")


def read(record: dict):
    total = LAYERS["coordinator_sum"](record, lambda row: row["self_s"], KINDS)
    return None if total is None else total * 1e3
