"""Median over the traced window's requests of the times the result fetch
blocked on the device: the ``round_trips`` counter of the program's
``fetch`` span (`ops/table.py fetch_host_buffers`, behind `Table.to_pandas`
and `io/parquet.py table_to_arrow`: one where the result's buffers are
copied whole and together, two where the row count is waited for first),
from `tracing.layer_report`. ``fetch_transfers`` counts the buffers copied;
this counts the waits. A program from before the counter reports none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "count"
LAYER = "result fetch"
SOURCE = "program_counter"
MOVES = "query_p50_s"


def read(record: dict):
    def waits(row):
        if "fetch" not in row["self_s"]:
            return None
        return row["counters"].get("round_trips")

    return LAYERS["median"](record, waits)
