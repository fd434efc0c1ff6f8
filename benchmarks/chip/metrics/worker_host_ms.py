"""Median over the traced window's requests that went through the
coordinator of the Python inside its workers' tasks: the self time of the
``worker`` (``worker_execute``: `runtime/worker.py`), ``prepare``
(``prepare``, ``program_lookup``) and ``execute`` kinds, that is what is
left of a task once its ``launch``, ``sync``, ``wait`` and ``h2d`` spans
are taken out: fingerprinting the plan twice, the shared-program lookup,
the metrics store, the span bookkeeping. From `tracing.layer_report`. A
SUM over the worker threads' tasks (``worker_tasks`` of them, up to four
at once, sharing one interpreter), not a critical path. A request that
went through no coordinator, or a program whose ``worker_execute`` is not
yet the ``worker`` kind, reports none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "ms"
LAYER = "execution"
SOURCE = "program_span"
MOVES = "query_p50_s"
KINDS = ("worker", "prepare", "execute")


def read(record: dict):
    def python(row):
        self_s = row["self_s"]
        if "schedule" not in self_s or "worker" not in self_s:
            return None
        return sum(self_s.get(kind, 0.0) for kind in KINDS) * 1e3

    return LAYERS["median"](record, python)
