"""On-chip smoke: TPC-H through the engine's normal entry points on a TPU.

    python chip_smoke.py [--seed 0] [--sf 1.0] [--chips 1|4]

One process, no children. With one chip (the default) it generates TPC-H
at ``--sf``, registers it on the device, and runs q1, q6, q3, q5

- directly: ``ctx.sql(text).collect_table()``, each twice (cold with
  compile, then warm);
- served: one ``ServingSession`` (four in-process workers, four tasks, all
  on the one chip) that takes the same four queries together from threads.

With ``--chips 4`` it runs, after the same set-up, only the mesh tier:
q1 and q3 through ``collect_distributed_table(num_tasks=4)``, each as one
SPMD program over four chips. q5 is NOT in that list (ISSUE 23 asked for
q1, q3, q5): its four-chip program at SF1 took the v5e compiler 1337 s in
the sandbox and came to 1.1 GB of generated code, more chip time, four
chips at once, than PR 23 had (ROADMAP S3, S8).

Every result is compared with the pandas oracle (tests/tpch_oracle.py, on
the host) at ``precision.oracle_rtol()``. A mismatch, an exception or an
exhausted overflow retry ends the run non-zero. Without a TPU, or with
fewer chips than ``--chips``, it exits non-zero before generating data.

Each phase prints JSON lines; the last line of stdout is
``{"ok": ..., "device": {"platform", "kind", "count"}}``. The seconds it
prints are a smoke on one run, not a measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import threading
import time
import traceback
from typing import NamedTuple

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, "tests"))

from datafusion_distributed_tpu import hostenv, native, precision  # noqa: E402
from datafusion_distributed_tpu.data.tpchgen import gen_tpch  # noqa: E402
from datafusion_distributed_tpu.runtime.mesh_executor import (  # noqa: E402
    make_mesh,
)
from datafusion_distributed_tpu.runtime.serving import (  # noqa: E402
    ServingSession,
)
from datafusion_distributed_tpu.sql.context import (  # noqa: E402
    SessionContext,
)
from tpch_oracle import ORACLES, compare_results, load_pandas  # noqa: E402

QUERIES_DIR = os.path.join(_HERE, "benchmarks", "queries", "tpch")
ONE_CHIP_QUERIES = ("q1", "q6", "q3", "q5")
MESH_QUERIES = ("q1", "q3")  # q5: see the header


class Query(NamedTuple):
    name: str
    sql: str
    expected: object  # pandas.DataFrame from the oracle


def emit(**record) -> None:
    print(json.dumps(record), flush=True)


def setup(sf: float, seed: int):
    """Generate TPC-H and register it as `data/tpchgen.py register_tpch`
    does. -> (ctx, oracle frames)."""
    t0 = time.perf_counter()
    tables = gen_tpch(sf, seed)
    t1 = time.perf_counter()
    ctx = SessionContext()
    for name, arrow in tables.items():
        ctx.register_arrow(name, arrow)
    t2 = time.perf_counter()
    emit(phase="setup", sf=sf, seed=seed,
         rows={name: t.num_rows for name, t in tables.items()},
         generate_s=t1 - t0, register_s=t2 - t1,
         native_host_plane=native.available())
    return ctx, load_pandas(tables)


def load_queries(names, frames) -> list:
    """Query texts from benchmarks/queries/tpch/ with the oracle's answer.
    The oracle returns every row in the query's order; a trailing LIMIT is
    applied to it here."""
    out = []
    for name in names:
        with open(os.path.join(QUERIES_DIR, f"{name}.sql")) as f:
            sql = f.read()
        t0 = time.perf_counter()
        expected = ORACLES[name](frames)
        limit = re.search(r"\blimit\s+(\d+)\s*;?\s*$", sql, re.IGNORECASE)
        if limit:
            expected = expected.head(int(limit.group(1)))
        emit(phase="oracle", query=name, rows=len(expected),
             seconds=time.perf_counter() - t0)
        out.append(Query(name, sql, expected))
    return out


def _frame(arrow):
    """Arrow result -> pandas in the oracle's conventions (dates as days)."""
    return load_pandas({"result": arrow})["result"]


def phase_direct(ctx, queries) -> None:
    """Each query twice through ``ctx.sql(text).collect_table()`` (which
    ``to_pandas`` runs): cold (with compile) and warm, both to a pandas
    result on the host."""
    for q in queries:
        seconds = []
        for _ in range(2):
            t0 = time.perf_counter()
            df = ctx.sql(q.sql)
            got = df.to_pandas()
            seconds.append(time.perf_counter() - t0)
            compare_results(got, q.expected)
        emit(phase="direct", query=q.name, cold_s=seconds[0],
             warm_s=seconds[1], retries=df.last_retry_count, rows=len(got))


def phase_served(ctx, queries) -> None:
    """The queries submitted together, one client thread each, to one
    ServingSession over four in-process workers, four tasks a stage."""
    results: dict = {}

    def client(srv, q: Query) -> None:
        try:
            handle = srv.submit(q.sql)
            results[q.name] = (handle.result(), handle.wall_s())
        except BaseException as e:  # re-raised on the main thread below
            results[q.name] = (e, None)

    t0 = time.perf_counter()
    with ServingSession(ctx, num_workers=4, num_tasks=4) as srv:
        threads = [threading.Thread(target=client, args=(srv, q))
                   for q in queries]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        stats = srv.stats()
    for q in queries:
        arrow, wall_s = results[q.name]
        if isinstance(arrow, BaseException):
            raise arrow
        compare_results(_frame(arrow), q.expected)
        emit(phase="served", query=q.name, wall_s=wall_s,
             rows=arrow.num_rows)
    emit(phase="served", queries=len(queries), total_s=wall,
         admitted_total=stats["admitted_total"],
         completed=stats["completed"], latency=stats["latency"])
    if stats["completed"].get("done") != len(queries):
        raise RuntimeError(f"served phase: not every query done: {stats}")


def phase_mesh(ctx, queries, num_tasks: int = 4) -> None:
    """Each query through ``collect_distributed_table`` (which
    ``collect_distributed`` runs) as ONE SPMD program over a mesh of
    ``num_tasks`` devices (``make_mesh`` refuses more tasks than devices)."""
    mesh = make_mesh(num_tasks)
    emit(phase="mesh", devices=[str(d) for d in mesh.devices.flat])
    for q in queries:
        t0 = time.perf_counter()
        df = ctx.sql(q.sql)
        got = _frame(df.collect_distributed(mesh=mesh))
        seconds = time.perf_counter() - t0
        compare_results(got, q.expected)
        emit(phase="mesh", query=q.name, cold_s=seconds,
             retries=df.last_retry_count, rows=len(got))


def _memory(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({"device": str(d),
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), found "
              f"{len(devices)} {device['platform']} device(s)",
              file=sys.stderr)
        emit(ok=False, device=device)
        return 1

    try:
        emit(phase="start", compile_cache_dir=hostenv.compile_cache_dir(),
             precision=precision.MODE, oracle_rtol=precision.oracle_rtol())
        ctx, frames = setup(args.sf, args.seed)
        emit(phase="setup", memory=_memory(devices))
        if args.chips == 1:
            queries = load_queries(ONE_CHIP_QUERIES, frames)
            phase_direct(ctx, queries)
            emit(phase="direct", memory=_memory(devices))
            phase_served(ctx, queries)
            emit(phase="served", memory=_memory(devices))
        else:
            phase_mesh(ctx, load_queries(MESH_QUERIES, frames),
                       num_tasks=args.chips)
            emit(phase="mesh", memory=_memory(devices))
    except Exception:
        traceback.print_exc()
        emit(ok=False, device=device)
        return 1
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
