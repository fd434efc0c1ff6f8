"""Median over the traced window's requests of the scans whose task inputs
were a partitioned table's partitions used where they lie, as the mesh's
shards: the ``partitioned_scans`` counter of the requests that hold a
``mesh.execute`` span (`runtime/mesh_executor.py place_partitions`, counted
when the program is traced and kept with the cached executable), from
`tracing.layer_report`. 1 for ClickBench q12 over `hits` registered as
four partitions, 0 where the mesh tier slices a table on chip 0 and copies
the slices to their chips. A program from before the counter reports
none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "count"
LAYER = "mesh input placement"
SOURCE = "program_counter"
MOVES = "query_p50_s"


def read(record: dict):
    def scans(row):
        if "mesh.execute" not in row["self_s"]:
            return None
        return row["counters"].get("partitioned_scans")

    return LAYERS["median"](record, scans)
