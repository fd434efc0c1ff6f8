"""The TPC-H suite: data from the seed, query texts, the reference's answers.

run.py finds this file by the ``suite`` a configuration names and uses only
the functions below. Another suite (TPC-DS, ClickBench) is another
directory with the same functions.

- ``load(scale, seed, cache_dir)``   -> {table: pyarrow.Table}, made by the
  program's ``gen_tpch(scale, seed)`` once per checkout and seed, then
  mapped from Arrow IPC files under ``cache_dir``.
- ``sql(query)``                     -> the query's text (spec 2.4
  validation parameters), from ``queries/<query>.sql``.
- ``expected(query, tables)``        -> the pandas oracle's answer, every
  row in the query's order, cut to a trailing LIMIT.
- ``measure(got, expected)``         -> the numbers one comparison rests on,
  and ``LIMITS``, the most each may read (``oracle.measure_results``;
  tolerances fixed in ``oracle.py``); ``compare`` raises AssertionError
  where one is over.
- ``frame(arrow)``                   -> a pyarrow result as a pandas frame in
  the oracle's conventions (dates as days since the epoch).
- ``least_bytes(query, tables)``     -> the fewest bytes the query must read:
  rows x 4 B x the columns its text names (``queries/<query>.columns.json``,
  32-bit device columns), or None where no sidecar exists.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil

_HERE = os.path.dirname(os.path.abspath(__file__))
_QUERIES = os.path.join(_HERE, "queries")

_spec = importlib.util.spec_from_file_location(
    "chipbench_tpch_oracle", os.path.join(_HERE, "oracle.py"))
oracle = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(oracle)

compare = oracle.compare_results
measure = oracle.measure_results
LIMITS = oracle.LIMITS


def load(scale: float, seed: int, cache_dir: str) -> dict:
    import pyarrow as pa

    path = os.path.join(cache_dir, f"tpch-sf{scale:g}-seed{seed}")
    if not os.path.isdir(path):
        from datafusion_distributed_tpu.data.tpchgen import gen_tpch

        # in the order a later run lists the cache, so that every run
        # registers the tables in the same order
        tables = dict(sorted(gen_tpch(scale, seed).items()))
        # written under a temporary name, then renamed: a run that is cut
        # while writing leaves no half cache behind
        tmp = f"{path}.tmp{os.getpid()}"
        os.makedirs(tmp)
        for name, table in tables.items():
            with pa.OSFile(os.path.join(tmp, f"{name}.arrow"), "wb") as f:
                with pa.ipc.new_file(f, table.schema) as writer:
                    writer.write_table(table)
        try:
            os.rename(tmp, path)
        except OSError:  # another run made it meanwhile
            shutil.rmtree(tmp)
        return tables
    tables = {}
    for entry in sorted(os.listdir(path)):
        name, ext = os.path.splitext(entry)
        if ext == ".arrow":
            source = pa.memory_map(os.path.join(path, entry), "r")
            tables[name] = pa.ipc.open_file(source).read_all()
    return tables


def sql(query: str) -> str:
    with open(os.path.join(_QUERIES, f"{query}.sql")) as f:
        return f.read()


def _columns(query: str):
    path = os.path.join(_QUERIES, f"{query}.columns.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)["columns"]


class _Frames(dict):
    """{table: pandas frame}, each converted when the oracle first asks for
    it, and only the columns the query's sidecar names where there is one
    (a column the oracle needs and the sidecar lacks is a KeyError)."""

    def __init__(self, tables: dict, columns):
        super().__init__()
        self._tables = tables
        self._columns = columns or {}

    def __missing__(self, name: str):
        table = self._tables[name]
        if name in self._columns:
            table = table.select(self._columns[name])
        self[name] = oracle.load_pandas({name: table})[name]
        return self[name]


def expected(query: str, tables: dict):
    answer = oracle.ORACLES[query](_Frames(tables, _columns(query)))
    limit = re.search(r"\blimit\s+(\d+)\s*;?\s*$", sql(query), re.IGNORECASE)
    if limit:
        answer = answer.head(int(limit.group(1)))
    return answer


def frame(arrow):
    return oracle.load_pandas({"result": arrow})["result"]


def least_bytes(query: str, tables: dict):
    columns = _columns(query)
    if columns is None:
        return None
    return sum(tables[name].num_rows * 4 * len(cols)
               for name, cols in columns.items())
