"""Median over the traced window's requests of the aggregates, grouped or
global, whose reductions ran as dense masked passes over a small domain
known when the program was traced, and not as scatters: the
``dense_aggregates`` counter of the requests that hold an ``execute`` or a
``mesh.execute`` span (`ops/aggregate.py _reduce_by_slot`, counted when the
program is traced and kept with the cached executable), from
`tracing.layer_report`. A program from before the counter reports none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "count"
LAYER = "operators"
SOURCE = "program_counter"
MOVES = "query_p50_s"


def read(record: dict):
    def dense(row):
        if not {"execute", "mesh.execute"} & set(row["self_s"]):
            return None
        return row["counters"].get("dense_aggregates")

    return LAYERS["median"](record, dense)
