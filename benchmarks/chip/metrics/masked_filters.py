"""Median over the traced window's requests of the filters that handed
their mask to an aggregate and did not compact: the ``masked_filters``
counter of the program's ``execute`` spans (`plan/physical.py
ExecutionPlan.execute_masked`, counted when the program is traced and
kept with the cached executable), from `tracing.layer_report`. A program
from before the counter reports none."""

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
        if "execute" not in row["self_s"]:
            return None
        return row["counters"].get("masked_filters")

    return LAYERS["median"](record, masked)
