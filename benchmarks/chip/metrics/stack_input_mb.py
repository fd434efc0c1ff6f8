"""Median over the traced window's requests of the bytes the mesh tier
stacks a request: the ``bytes`` counter of the program's
``mesh.stack_inputs`` spans (`runtime/mesh_executor.py execute_on_mesh`:
the stacked ``[tasks, ...]`` copy of every leaf's columns), / 1e6, from
`tracing.layer_report`."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "MB"
LAYER = "mesh input placement"
SOURCE = "program_counter"
MOVES = "query_p50_s"


def read(record: dict):
    def stacked(row):
        nbytes = row["counters"]["bytes"].get("mesh.stack_inputs")
        return None if nbytes is None else nbytes / 1e6

    return LAYERS["median"](record, stacked)
