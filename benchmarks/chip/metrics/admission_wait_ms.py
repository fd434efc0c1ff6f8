"""Median over the traced window's requests of the wait between a
query's submit and its admission: the program's ``queued`` span
(`runtime/serving.py submit` to `_drive`), from `tracing.layer_report`.
With one closed-loop client it is the hand-over to the session's driver
thread; under load it is the admission queue. A request that was never
queued reports none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "ms"
LAYER = "serving"
SOURCE = "program_span"
MOVES = "query_p50_s"


def read(record: dict):
    def queued(row):
        wait = row["self_s"].get("queued")
        return None if wait is None else wait * 1e3

    return LAYERS["median"](record, queued)
