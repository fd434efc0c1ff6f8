"""Median over the traced window's requests of the program's
``mesh.stack_inputs`` spans (`runtime/mesh_executor.py execute_on_mesh`:
every task's slice of every leaf, stacked on the first device before
``shard_map`` re-places it): host work inside ``execute_ms`` before the
SPMD program starts, from `tracing.layer_report`."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "ms"
LAYER = "mesh input placement"
SOURCE = "program_span"
MOVES = "query_p50_s"


def read(record: dict):
    def stacking(row):
        total = row["total_s"].get("mesh.stack_inputs")
        return None if total is None else total * 1e3

    return LAYERS["median"](record, stacking)
