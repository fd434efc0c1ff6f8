"""Median over the traced window's requests of the blocking device-to-host
reads its programs' small outputs cost: the ``syncs`` counter of the
program's ``sync`` spans (one for a flag vector, one a metric value read
with its own ``int(v)``, one a row count), from `tracing.layer_report`
(``counters["syncs"]``). One for a query of the single-node tier; tens in
``coord4-q1``, where every task pulls its node metrics and its row count
one value at a time. The result fetch is not among them
(``fetch_round_trips``). A program from before the counter, or a request
that ran no program, reports none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "count"
LAYER = "execution"
SOURCE = "program_counter"
MOVES = "query_p50_s"


def read(record: dict):
    def syncs(row):
        if "sync" not in row["self_s"]:
            return None
        return row["counters"].get("syncs")

    return LAYERS["median"](record, syncs)
