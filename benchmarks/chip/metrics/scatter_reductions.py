"""Median over the traced window's requests of the per-slot reductions,
and the slot-presence passes of direct groupings, that lowered as scatters
and not as dense passes: the ``scatter_reductions`` counter of the requests
that hold an ``execute`` or a ``mesh.execute`` span (`ops/aggregate.py
hash_aggregate`: a domain past `_DENSE_MAX_DOMAIN`, or a table the claim
loop built; counted when the program is traced), from
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
    def scatters(row):
        if not {"execute", "mesh.execute"} & set(row["self_s"]):
            return None
        return row["counters"].get("scatter_reductions")

    return LAYERS["median"](record, scatters)
