"""Median over the traced window's requests of the coordinator's own host
time: the self time of the program's ``query``, ``schedule``, ``stage``,
``task``, ``attempt``, ``dispatch``, ``codec`` and ``rpc`` spans
(`runtime/coordinator.py`, `runtime/worker.py`: planning the stages,
encoding and shipping each task, collecting its result), children taken
out, from `tracing.layer_report`. Not the sum of ``worker_execute``: the
stage programs are the ``execute`` kind. A request that went through no
coordinator (no ``schedule`` span) reports none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "ms"
LAYER = "coordinator scheduling"
SOURCE = "program_span"
MOVES = "query_p50_s"
KINDS = ("query", "schedule", "stage", "task", "attempt", "dispatch",
         "codec", "rpc")


def read(record: dict):
    total = LAYERS["coordinator_sum"](record, lambda row: row["self_s"], KINDS)
    return None if total is None else total * 1e3
