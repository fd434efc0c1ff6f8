"""Median over the traced window's requests of the slots of the largest
group table their programs built or addressed: the ``group_slots`` counter
of the requests that hold an ``execute`` or a ``mesh.execute`` span (a
direct grouping's domain, the claim loop's table, 1 for a global
aggregate; `plan/physical.py HashAggregateExec`, counted when the program
is traced and kept with the cached executable), from
`tracing.layer_report`. With ``scatter_reductions`` it says which lowering
ran at which size. A program from before the counter reports none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "count"
LAYER = "operators"
SOURCE = "program_counter"
MOVES = "query_p50_s"


def read(record: dict):
    def slots(row):
        if not {"execute", "mesh.execute"} & set(row["self_s"]):
            return None
        return row["counters"].get("group_slots")

    return LAYERS["median"](record, slots)
