"""The TPC-H suite for a scale whose seeds do not fit a machine's disk: every
function is `suites/tpch/suite.py`'s, loaded by path, and `load` alone is
replaced by one that generates and keeps nothing.

At SF10 a seed's tables are 9.9 GB as Arrow IPC files and the chip machine
has 80 GB free (chip run, PR 34): a check of a PR draws a dozen seeds in each
of two checkouts. The contract has data made anew in every run anyway;
generating costs 45-60 s of set-up at SF10 where mapping a cached seed costs
none, and writing a new seed's cache cost about as much as it saved.

Set-up inside a run needs a program that builds its text columns and its
string dictionaries without a Python object a row (`tpchgen`'s chunked Arrow
text columns, `Dictionary.from_arrow`; both PR 34). A program without them
takes 513 s a run at SF10, 420 of them set-up, with 39 GB of the host's 45 GiB
in use (chip run, PR 34): past a run's time limit. `load` refuses such a
program at once, with its reason and exit code 1, before any data is made."""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_tpch_suite",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "tpch", "suite.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

oracle = _tpch.oracle
compare, measure, LIMITS = _tpch.compare, _tpch.measure, _tpch.LIMITS
sql, expected, frame, least_bytes = (
    _tpch.sql, _tpch.expected, _tpch.frame, _tpch.least_bytes)


def load(scale: float, seed: int, cache_dir: str) -> dict:
    """-> {table: pyarrow.Table}, made from the seed in every run, in the
    order the cached suite registers them; ``cache_dir`` is not used."""
    from datafusion_distributed_tpu.data import tpchgen
    from datafusion_distributed_tpu.ops.table import Dictionary

    if not (hasattr(tpchgen, "tpch_cardinalities")
            and hasattr(Dictionary, "from_arrow")):
        raise SystemExit(
            "suites/tpch-uncached: this program makes text columns and string "
            "dictionaries a row at a time (no chunked Arrow text columns in "
            "data/tpchgen.py, no Dictionary.from_arrow): set-up at SF10 takes "
            "420 s and 39 GB of host memory, past a run's time limit; not run")
    return dict(sorted(tpchgen.gen_tpch(scale, seed).items()))
