"""The ClickBench suite: `hits` from the seed, query texts, the reference's
answers. The functions are those of `suites/tpch/suite.py`'s header.

- ``load(scale, seed, cache_dir)``   -> {"hits": pyarrow.Table}: the
  program's ``gen_clickbench`` at ``ceil(HITS_ROWS * scale)`` rows, made in
  every run (at scale 0.5 a seed's table is ~5 GB on the host; nothing is
  cached, ``cache_dir`` is not used).
- ``sql(query)``                     -> ``queries/<query>.sql``.
- ``expected(query, tables)``        -> ``(candidates, limit)``: the pandas
  oracle's candidate rows (`oracle.py`) and the text's trailing LIMIT.
- ``measure(got, expected)``, ``LIMITS``, ``compare``: `oracle.py`.
- ``frame(arrow)``                   -> a pyarrow result as a pandas frame.
- ``least_bytes(query, tables)``     -> rows x 4 B x the columns the text
  names (``queries/<query>.columns.json``), or None without a sidecar.

Set-up inside a run needs a program whose generator draws `SearchPhrase`
from ClickBench's 6,019,102 phrases without a Python object a row, and
whose planner gives a GROUP BY over dictionary keys a group table as wide
as their domain (`sql/planner.py DIRECT_MAX_SLOTS`). Without the first,
50M rows are made a row at a time; without the second, q12's 2.1M phrases
meet a table of at most ``max_slots`` (1Mi as planned), the claim loop
probes a full table round after round over 64Mi rows, and no answer comes
inside a run (none in 260 s on a v5e). `load` refuses such a program at
once, with its reason and exit code 1, before any data is made.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import re

_HERE = os.path.dirname(os.path.abspath(__file__))
_QUERIES = os.path.join(_HERE, "queries")

_spec = importlib.util.spec_from_file_location(
    "chipbench_clickbench_oracle", os.path.join(_HERE, "oracle.py"))
oracle = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(oracle)

compare = oracle.compare_results
measure = oracle.measure_results
LIMITS = oracle.LIMITS

#: rows of the real `hits` table (ClickHouse/ClickBench)
HITS_ROWS = 99_997_497


def load(scale: float, seed: int, cache_dir: str) -> dict:
    from datafusion_distributed_tpu.data import clickbenchgen
    from datafusion_distributed_tpu.sql import planner

    if not (hasattr(clickbenchgen, "SEARCH_PHRASES")
            and hasattr(planner, "DIRECT_MAX_SLOTS")):
        raise SystemExit(
            "suites/clickbench: this program has no generator at ClickBench's "
            "shapes (data/clickbenchgen.py SEARCH_PHRASES) or no group table "
            "sized by its dictionary keys' domain (sql/planner.py "
            "DIRECT_MAX_SLOTS): q12 would not run inside a run; not run")
    rows = math.ceil(HITS_ROWS * scale)
    return {"hits": clickbenchgen.gen_clickbench(rows, seed)}


def sql(query: str) -> str:
    with open(os.path.join(_QUERIES, f"{query}.sql")) as f:
        return f.read()


def _columns(query: str):
    path = os.path.join(_QUERIES, f"{query}.columns.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)["columns"]


def expected(query: str, tables: dict, counts=None):
    """-> (the oracle's candidates, the LIMIT). The oracle gets each table
    as a pandas frame of the columns the query's sidecar names."""
    frames = {name: tables[name].select(cols).to_pandas()
              for name, cols in _columns(query).items()}
    limit = re.search(r"\blimit\s+(\d+)\s*;?\s*$", sql(query), re.IGNORECASE)
    return oracle.ORACLES[query](frames, counts), int(limit.group(1))


def frame(arrow):
    return arrow.to_pandas()


def least_bytes(query: str, tables: dict):
    columns = _columns(query)
    if columns is None:
        return None
    return sum(tables[name].num_rows * 4 * len(cols)
               for name, cols in columns.items())
