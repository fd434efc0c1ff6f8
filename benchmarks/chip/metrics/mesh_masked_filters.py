"""Median over the traced window's requests of the filters of the mesh
tier's SPMD program that handed their mask to an aggregate and did not
compact: the ``masked_filters`` counter of the requests that hold a
``mesh.execute`` span (`runtime/mesh_executor.py execute_on_mesh`, counted
when the program is traced and kept with the cached executable), from
`tracing.layer_report`. A program whose span lacks the counter reads 0."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "count"
LAYER = "operators"
SOURCE = "program_counter"
MOVES = "query_p50_s"


def read(record: dict):
    def masked(row):
        if "mesh.execute" not in row["self_s"]:
            return None
        return row["counters"].get("masked_filters")

    return LAYERS["median"](record, masked)
