"""Median over the traced window's requests of the program's ``prepare``
spans (`plan/physical.py execute_plan` up to the call of the jitted
function: hoisting, fingerprint, leaf loads, program-cache lookup): host
work inside ``execute_ms`` before the device starts, from
`tracing.layer_report`."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "ms"
LAYER = "execution"
SOURCE = "program_span"
MOVES = "query_p50_s"


def read(record: dict):
    def prepare(row):
        total = row["total_s"].get("prepare")
        return None if total is None else total * 1e3

    return LAYERS["median"](record, prepare)
