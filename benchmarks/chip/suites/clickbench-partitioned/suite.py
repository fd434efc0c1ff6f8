"""ClickBench's `hits` as row partitions, a partition a chip: the suite of
`suites/clickbench` (its query texts, its oracle and its comparison, the
configuration's copy of the reference) over a table the program registers
as `io/parquet.py Partitions`. The functions are those of
`suites/tpch/suite.py`'s header.

- ``load(scale, seed, cache_dir)``   -> {"hits": Partitions}: the program's
  ``gen_clickbench_partitions`` at ``ceil(HITS_ROWS * scale)`` rows in
  ``PARTITIONS`` partitions, partition i drawn from ``(seed, i)``, made in
  every run (at scale 1 ~10 GB of Arrow on the host; nothing is cached,
  ``cache_dir`` is not used).
- ``expected(query, tables)``        -> `suites/clickbench`'s, over every
  partition's rows.
- ``sql``, ``measure``, ``LIMITS``, ``compare``, ``frame``,
  ``least_bytes``: `suites/clickbench`'s (``least_bytes`` reads the whole
  table's ``num_rows``).

Set-up needs a program that registers a table as partitions, a partition a
device (`io/parquet.py Partitions`, `data/clickbenchgen.py
gen_clickbench_partitions`). Without them the whole `hits` lands on one chip
(13.4 of its 16 GB) before the mesh tier slices it there: `load` refuses
such a program at once, with its reason and exit code 1, before any data is
made.
"""

from __future__ import annotations

import importlib.util
import math
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_clickbench_suite",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "clickbench", "suite.py"))
whole = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(whole)

sql = whole.sql
measure = whole.measure
compare = whole.compare
LIMITS = whole.LIMITS
frame = whole.frame
least_bytes = whole.least_bytes
HITS_ROWS = whole.HITS_ROWS

#: the row partitions of `hits`, one a chip of a four-chip host
PARTITIONS = 4


def load(scale: float, seed: int, cache_dir: str) -> dict:
    from datafusion_distributed_tpu.data import clickbenchgen
    from datafusion_distributed_tpu.io import parquet

    if not (hasattr(parquet, "Partitions")
            and hasattr(clickbenchgen, "gen_clickbench_partitions")):
        raise SystemExit(
            "suites/clickbench-partitioned: this program cannot register a "
            "table as partitions, a partition a device (io/parquet.py "
            "Partitions): the whole hits would land on one chip; not run")
    rows = math.ceil(HITS_ROWS * scale)
    return {"hits": parquet.Partitions(clickbenchgen.gen_clickbench_partitions(
        rows, seed, PARTITIONS))}


def expected(query: str, tables: dict, counts=None):
    """`suites/clickbench`'s reference over every partition's rows."""
    import pyarrow as pa

    return whole.expected(query, {
        name: pa.concat_tables(t.tables) if hasattr(t, "tables") else t
        for name, t in tables.items()}, counts)
