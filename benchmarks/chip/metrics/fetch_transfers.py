"""Median over the traced window's requests of the device-to-host pulls
of the result fetch: the ``transfers`` counter of the program's ``fetch``
span (`Table.to_pandas`, `io/parquet.py table_to_arrow`: the row count,
then each column's data and validity), from `tracing.layer_report`."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "count"
LAYER = "result fetch"
SOURCE = "program_counter"
MOVES = "query_p50_s"


def read(record: dict):
    def pulls(row):
        if "fetch" not in row["self_s"]:
            return None
        return row["counters"]["transfers"]

    return LAYERS["median"](record, pulls)
